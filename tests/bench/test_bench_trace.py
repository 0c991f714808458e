"""The trace reduction (`bench/trace.py`): reading an `.xplane.pb` written
by JAX's profiler, and reducing a recorded TPU trace (a committed slice of
a `ngp19.fresh800` window: its device and `bench.*` host-span events) to
busy and idle time, device time by program and kernel, and idle gaps
named by host spans."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace as tr  # noqa: E402

FIXTURE = Path(__file__).with_name("fixtures") / "tpu_window_slice.json"


def fixture_events():
    raw = json.loads(FIXTURE.read_text())
    return [tr.Event(*e) for e in raw["events"]], raw


def test_reads_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    (pb,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    events = tr.read_xplane(pb)
    names = {e.name for e in events}
    assert tr.WINDOW_SPAN in names and "bench.step" in names
    win = next(e for e in events if e.name == tr.WINDOW_SPAN)
    step = next(e for e in events if e.name == "bench.step")
    assert win.start_ns <= step.start_ns and step.end_ns <= win.end_ns


def test_busy_idle_programs_kernels_and_gaps_of_a_recorded_trace():
    events, raw = fixture_events()
    want = raw["expected"]
    red = tr.reduce_events(events)
    assert red.n_devices == 1
    assert red.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert red.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0.0 < red.busy_s < red.window_s
    n, s = red.program(r"_slot_march_impl")
    assert (n, s) == (want["march_programs"], pytest.approx(want["march_s"], rel=1e-9))
    n, s = red.kernel(r"hash_gather")
    assert (n, s) == (want["hash_gather_ops"], pytest.approx(want["hash_gather_s"], rel=1e-9))
    n, s = red.kernel(r"ray_march")
    assert (n, s) == (want["ray_march_ops"], pytest.approx(want["ray_march_s"], rel=1e-9))
    assert sum(red.gap_by_span.values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-9)
    assert set(red.gap_by_span) <= {"bench.step", "bench.submit",
                                    "bench.traffic", "bench.result", "other"}
    assert red.gap_by_span.get("bench.step", 0.0) > 0.0
    bd = red.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["idle_gaps"][0][1] == max(g for _, g in red.gaps)


def test_gap_goes_to_the_span_that_covers_it():
    E = tr.Event
    events = [
        E("/host:CPU", "t", "bench.window", 0, 100),
        E("/host:CPU", "t", "bench.step", 0, 50),
        E("/host:CPU", "t", "bench.traffic", 60, 30),
        E("/device:TPU:0", "XLA Ops", "fusion.1", 10, 20),      # busy 10-30
        E("/device:TPU:0", "XLA Ops", "hash_gather.3", 25, 15),  # busy to 40
        E("/device:TPU:0", "XLA Modules", "jit_f(7)", 10, 30),
    ]
    red = tr.reduce_events(events)
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx(30e-9)
    assert red.programs == {"jit_f": (1, pytest.approx(30e-9))}
    # gaps: 0-10 (step), 40-100 (traffic covers 60-90 = 30 > step's 10)
    assert red.gap_by_span["bench.step"] == pytest.approx(10e-9)
    assert red.gap_by_span["bench.traffic"] == pytest.approx(60e-9)
