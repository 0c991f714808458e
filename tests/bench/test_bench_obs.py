"""The readers of the program's spans and counters (`engine.host_ms`,
`render.budget_fill`, `device_idle.engine`): on synthetic run contexts,
on a hand-built trace with known idle stretches inside and outside an
`engine.step` span, and on a tiny frames cell run end to end on the CPU,
where the program's own count of active samples equals the benchmark's
occupancy oracle for the same slots."""
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import common  # noqa: E402
from bench import trace as tr  # noqa: E402

CELL = "ngp19.fresh800"
SEED = 2 ** 33 + 91


def reader(name):
    return common.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                              "test_metric_" + name.replace(".", "_"))


def ctx(spans=None, counters=None, slots=10):
    trace = {} if spans is None else {"spans": spans, "counters": counters or {}}
    return {"stats": {"trace": trace} if trace else {}, "slots": slots}


def agg(total_s):
    return {"count": 1, "total_s": total_s}


def test_engine_host_ms_reads_step_less_wait_per_slot():
    r = reader("engine.host_ms")
    run = ctx({"engine.step": agg(0.5), "render.wait": agg(0.46)}, slots=10)
    assert r.read(run) == pytest.approx(4.0)
    assert r.read(ctx({"engine.step": agg(0.5)}, slots=10)) == pytest.approx(50.0)
    assert r.read(ctx()) is None  # a program without spans
    assert r.read(ctx({"engine.step": agg(0.5)}, slots=0)) is None


def test_render_budget_fill_reads_active_over_budget():
    r = reader("render.budget_fill")
    run = ctx({}, {"render.active_samples": 4565, "render.budget_samples": 10000})
    assert r.read(run) == pytest.approx(45.65)
    assert r.read(ctx({}, {})) is None
    assert r.read(ctx()) is None


def events_with_known_idle():
    E = tr.Event
    H, D = "/host:CPU", "/device:TPU:0"
    return [
        E(H, "t", "bench.window", 0, 100),
        E(H, "t", "bench.step", 8, 54),          # not a program span
        E(H, "t", "engine.step", 10, 50),        # 10-60
        E(H, "t", "engine.admit", 10, 10),       # 10-20
        E(H, "t", "render.slot", 20, 30),        # 20-50
        E(H, "t", "render.wait", 30, 15),        # 30-45
        E(H, "t", "host.gc", 52, 4),             # 52-56
        E(H, "t", "engine.submit", 60, 5),       # idle, outside the step
        E(D, "XLA Ops", "fusion.1", 0, 5),       # busy 0-5
        E(D, "XLA Ops", "fusion.2", 25, 15),     # busy 25-40
        E(D, "XLA Ops", "fusion.3", 70, 10),     # busy 70-80
        E("/device:TPU:1", "XLA Ops", "fusion.9", 0, 100),  # second chip
    ]


def test_idle_split_names_idle_inside_engine_step():
    r = reader("device_idle.engine")
    window_s, split = r.idle_split(events_with_known_idle())
    # Idle 5-25, 40-70, 80-100; inside the step 10-25 and 40-60.
    assert window_s == pytest.approx(100e-9)
    want = {"engine.admit": 10, "render.slot": 10, "render.wait": 5,
            "engine.step": 6, "host.gc": 4}
    assert split == {k: pytest.approx(v * 1e-9) for k, v in want.items()}


def test_idle_split_without_program_spans_is_none():
    r = reader("device_idle.engine")
    parent = [e for e in events_with_known_idle()
              if not e.name.startswith(r.PROGRAM_SPANS)]
    assert r.idle_split(parent) is None


def test_device_idle_engine_reads_the_newest_trace(tmp_path, monkeypatch):
    r = reader("device_idle.engine")
    monkeypatch.setattr(common, "OUT", tmp_path)
    assert r.read({}) is None  # no trace written
    for mtime, name in ((1000, "new"), (10, "old")):
        f = tmp_path / "trace" / "cell" / "plugins" / "profile" / name / "h.xplane.pb"
        f.parent.mkdir(parents=True)
        f.write_bytes(b"")
        os.utime(f, (mtime, mtime))
    assert r.latest_trace().parent.name == "new"
    monkeypatch.setattr(tr, "read_xplane", lambda path: events_with_known_idle())
    assert r.read({}) == pytest.approx(35.0)


@pytest.fixture()
def tiny_traced_run(monkeypatch, tmp_path):
    from bench import run as bench_run
    from bench.traffic import generate

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(common, "OUT", tmp_path / "out")
    cfg = common.load_config("ngp-paper-t19")
    cfg["model"].update(log2_table_size=9, base_resolution=4,
                        max_resolution=32, hidden_dim=16, color_hidden_dim=16)
    cfg.update(image_hw=16, n_train_views=3, train_steps=10)
    mix = generate.load_mix("fresh_frames")
    mix.update(image_hw=32, frames=400, check_items=8)
    return bench_run.run_cell(CELL, SEED, 0.5, True, require_tpu=False,
                              cfg_override=cfg, mix_override=mix)


def test_program_counts_match_the_oracle_on_a_tiny_cell(tiny_traced_run):
    line, out = tiny_traced_run
    run = out["trace_ctx"]
    counters = run["stats"]["trace"]["counters"]
    spans = run["stats"]["trace"]["spans"]
    assert run["slots"] > 0
    assert counters["render.active_samples"] == run["active_samples"]
    assert counters["render.budget_samples"] == run["budget"] * run["slots"]
    assert spans["render.slot"]["count"] == run["slots"]
    got = line["metrics"]
    assert got["render.budget_fill"]["value"] == pytest.approx(
        got["render.sample_fill"]["value"], abs=1e-9)
    assert got["engine.host_ms"]["value"] > 0
    assert "device_idle.engine" not in got  # the CPU trace has no device plane

