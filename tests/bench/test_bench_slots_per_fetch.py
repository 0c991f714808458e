"""The reader of `engine.slots_per_fetch`: slots rendered over the
program's `render.fetches` counter, on synthetic run contexts shaped as
the frames cells build them (`trace_ctx`: the engine's `stats()` and
the window's slot count), and on the stats of an engine whose steps ran
with a scripted device step."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import common  # noqa: E402

NAME = "engine.slots_per_fetch"


def reader():
    return common.load_module(ROOT / "bench" / "metrics" / f"{NAME}.py",
                              "test_metric_engine_slots_per_fetch")


def ctx(counters=None, slots=10):
    trace = {} if counters is None else {"spans": {}, "counters": counters}
    return {"stats": {"trace": trace} if trace else {}, "slots": slots}


@pytest.mark.parametrize("slots,fetches,want", [
    (1508, 377, 4.0),     # full buckets of 4, one fetch a step
    (1508, 1508, 1.0),    # one fetch a slot
    (10, 4, 2.5),         # a step of 2 slots and repairs beside steps of 4
])
def test_reads_slots_over_fetches(slots, fetches, want):
    got = reader().read(ctx({"render.fetches": fetches}, slots=slots))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("run", [
    ctx(),                       # a program without spans or counters
    ctx({"hash.lookups": 8}),    # a program without the fetch counter
    ctx({"render.fetches": 0}, slots=0),
], ids=["no_trace", "no_counter", "no_fetch"])
def test_reads_nothing_without_fetches(run):
    assert reader().read(run) is None


def test_manifest_entry_moves_rays_per_s():
    """The metric has no `workloads` list, so every cell that reports
    `rays_per_s` reports it: each such cell serves through the engine."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in manifest["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "slots", "better": "higher",
                     "source": "program_counter", "layer": "engine",
                     "moves": "rays_per_s"}


def test_reads_an_engine_with_a_scripted_device_step():
    """An engine given a 4-argument device step fetches nothing itself:
    the counter stays empty and the reader reads nothing, as on a
    program from before the counter."""
    from repro.hero.engine import ServeEngine
    from repro.hero.scheduler import EngineConfig

    class Art:
        scene = "s"

    def device_step(scene, artifact, ro, rd):
        return np.zeros(ro.shape, np.float32)

    eng = ServeEngine(Art(), EngineConfig(slots=4, slot_rays=8),
                      device_step=device_step)
    eng.render(np.zeros((40, 3), np.float32), np.ones((40, 3), np.float32),
               scene="s")
    run = {"stats": eng.stats(), "slots": eng.stats()["items_rendered"]}
    assert run["slots"] == 5
    assert reader().read(run) is None
