"""`repro.obs`: span nesting on a fake clock, counters,
`snapshot`/`reset`, the garbage-collector span, JAX's compile clock,
spans from many threads, and the serve engine's own spans under the
`device_step=` fake."""
import gc
import sys
import threading

import numpy as np
import pytest

from repro import obs
from repro.hero.engine import ServeEngine
from repro.hero.scheduler import EngineConfig


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def spans_of(rec):
    return {k: (v["count"], v["total_s"])
            for k, v in rec.snapshot()["spans"].items()}


def test_span_nesting_and_totals():
    clk = FakeClock()
    rec = obs.Recorder(clock=clk)
    with rec.span("a", scene="x"):
        clk.advance(1)
        with rec.span("b"):
            clk.advance(2)
            with rec.span("c"):
                clk.advance(4)
        with rec.span("b") as sp:
            sp.set(items=3)
            clk.advance(8)
        clk.advance(16)
    assert spans_of(rec) == {"a": (1, 31), "b": (2, 14), "c": (1, 4)}


def test_span_closes_on_error():
    clk = FakeClock()
    rec = obs.Recorder(clock=clk)
    with pytest.raises(ValueError):
        with rec.span("outer"):
            with rec.span("inner"):
                clk.advance(1)
                raise ValueError
    with rec.span("after"):
        clk.advance(2)
    assert spans_of(rec) == {"outer": (1, 1), "inner": (1, 1),
                             "after": (1, 2)}


def test_counters_snapshot_and_reset():
    rec = obs.Recorder(clock=FakeClock())
    rec.count("x", 3)
    rec.count("x")
    rec.count("y", 0.5)
    with rec.span("s"):
        pass
    snap = rec.snapshot()
    assert snap["counters"] == {"x": 4, "y": 0.5}
    assert set(snap["spans"]) == {"s"}
    rec.reset()
    assert rec.snapshot() == {"spans": {}, "counters": {}}
    snap["counters"]["x"] = 99  # a snapshot is a copy
    assert rec.snapshot()["counters"] == {}


def test_gc_span_fires_on_collect():
    rec = obs.Recorder()
    gc.callbacks.append(rec.on_gc)
    gc.disable()  # the one collection is the explicit one
    try:
        with rec.span("outer"):
            gc.collect()
    finally:
        gc.enable()
        gc.callbacks.remove(rec.on_gc)
    snap = rec.snapshot()
    assert snap["spans"]["host.gc"]["count"] == 1
    assert snap["counters"] == {}
    assert (snap["spans"]["host.gc"]["total_s"]
            <= snap["spans"]["outer"]["total_s"])


def test_install_is_idempotent():
    obs.install()
    obs.install()
    assert gc.callbacks.count(obs.PROCESS.on_gc) == 1


def test_compile_clock_counts_backend_compiles():
    import jax
    import jax.numpy as jnp

    clock = obs.CompileClock()
    jax.jit(lambda x: x * 3 + 1)(jnp.ones((7, 5))).block_until_ready()
    snap = clock.snapshot()
    assert snap["compiles"] >= 1 and snap["compile_s"] > 0
    assert {"cache_hits", "cache_misses"} <= set(snap)


def test_spans_from_many_threads_sum_exactly():
    rec = obs.Recorder()
    n_threads, n = 12, 500

    def work():
        for _ in range(n):
            with rec.span("t.outer"):
                with rec.span("t.inner"):
                    rec.count("t.ops")

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    snap = rec.snapshot()
    assert snap["counters"]["t.ops"] == n_threads * n
    outer, inner = snap["spans"]["t.outer"], snap["spans"]["t.inner"]
    assert outer["count"] == inner["count"] == n_threads * n
    assert inner["total_s"] <= outer["total_s"]


class FakeArtifact:
    scene = "a"

    def resident_bytes(self):
        return 1


def fake_engine(R):
    return ServeEngine({"a": FakeArtifact()},
                       EngineConfig(slots=2, slot_rays=R),
                       device_step=lambda scene, art, ro, rd: ro * 2.0)


def test_engine_stats_trace_under_fake_device_step():
    R = 8
    eng = fake_engine(R)
    eng.submit(np.ones((5 * R, 3), np.float32), np.ones((5 * R, 3), np.float32))
    eng.reset_stats()
    assert eng.stats()["trace"] == {"spans": {}, "counters": {}}
    eng.submit(np.ones((R, 3), np.float32), np.ones((R, 3), np.float32))
    steps = [eng.step() for _ in range(4)]  # 6 items in 2-slot steps, idle
    assert steps == [2, 2, 2, 0]
    st = eng.stats()
    spans = st["trace"]["spans"]
    assert {k: v["count"] for k, v in spans.items()} == {
        "engine.submit": 1, "engine.step": 4, "engine.admit": 4,
        "engine.scatter": 3}
    step = spans["engine.step"]
    kids = spans["engine.admit"]["total_s"] + spans["engine.scatter"]["total_s"]
    assert kids <= step["total_s"]
    assert st["device_steps"] == 3
    eng.reset_stats()
    assert eng.stats()["trace"] == {"spans": {}, "counters": {}}


def test_engines_keep_their_own_trace():
    """Two engines in one process: each reads and clears its own spans,
    and the process's garbage collections land in neither."""
    R = 8
    a, b = fake_engine(R), fake_engine(R)
    obs.install()
    a.submit(np.ones((3 * R, 3), np.float32), np.ones((3 * R, 3), np.float32))
    a.drain()
    gc.collect()
    b.submit(np.ones((R, 3), np.float32), np.ones((R, 3), np.float32))
    assert a.stats()["trace"]["spans"]["engine.step"]["count"] == 3
    assert set(b.stats()["trace"]["spans"]) == {"engine.submit"}
    assert "host.gc" not in a.stats()["trace"]["spans"]
    assert obs.PROCESS.snapshot()["spans"]["host.gc"]["count"] >= 1
    b.reset_stats()
    assert a.stats()["trace"]["spans"]["engine.submit"]["count"] == 1
