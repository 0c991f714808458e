"""Host spans and counters of the program's layers, on the profiler's clock.

A `Recorder` holds one owner's aggregates: each `ServeEngine` keeps its
own, so its `stats()` and `reset_stats()` read and clear its own work
only. `Recorder.span(name, **attrs)` is a context manager. It enters a
`jax.profiler.TraceAnnotation`, so an active profiler session records the
span on its host plane, on the device trace's clock, with `attrs` as the
event's stats. It also sums, per name, how often the span closed and its
total seconds. With no profiler session a span costs the TraceMe's
enabled check, two clock reads and those sums: a few microseconds of
host time.

`Recorder.count(name, n)` adds to a counter. `snapshot()` reads spans
and counters, `reset()` clears both.

The garbage collector belongs to the process, not to an engine:
`install()` (idempotent), called by the serving entry points, records
each collection as a `host.gc` span in `PROCESS`.

`CompileClock` sums JAX's compile events for scripts that report where
their set-up time goes.
"""
from __future__ import annotations

import gc
import threading
import time
from typing import Callable, Dict, List

from jax.profiler import TraceAnnotation

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds JAX spent lowering and compiling top-level computations,
    backend compilations, and persistent compile-cache hits and misses,
    summed since registration. (Tracing is left out: nested jits record
    nested trace events, which would count twice.)"""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration
        if event == BACKEND_COMPILE:
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}


class Span:
    """One open span (see `Recorder.span`)."""

    __slots__ = ("_rec", "_name", "_tm", "_t0")

    def __init__(self, rec: "Recorder", name: str, attrs: Dict):
        self._rec, self._name = rec, name
        self._tm = TraceAnnotation(name, **attrs)

    def set(self, **attrs) -> None:
        """Attach attributes known only once the span is open."""
        self._tm.set_metadata(**attrs)

    def __enter__(self) -> "Span":
        self._tm.__enter__()
        self._t0 = self._rec._clock()
        return self

    def __exit__(self, *exc) -> bool:
        self._rec._add(self._name, self._rec._clock() - self._t0)
        self._tm.__exit__(*exc)
        return False


class Recorder:
    """Span aggregates and counters. `clock` is any zero-argument seconds
    callable (a fake one makes duration assertions exact)."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self._clock = clock
        # Re-entrant: a collection may start while `_add` holds the lock,
        # and its `host.gc` span then closes on the same thread.
        self._lock = threading.RLock()
        self._spans: Dict[str, List[float]] = {}  # name -> [count, total]
        self._counters: Dict[str, float] = {}
        self._gc_span = None

    def _add(self, name: str, total: float) -> None:
        with self._lock:
            agg = self._spans.get(name)
            if agg is None:
                self._spans[name] = [1, total]
            else:
                agg[0] += 1
                agg[1] += total

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def count(self, name: str, n: float = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def snapshot(self) -> Dict:
        """{"spans": {name: {count, total_s}}, "counters": {...}}."""
        with self._lock:
            return {
                "spans": {k: {"count": int(c), "total_s": t}
                          for k, (c, t) in self._spans.items()},
                "counters": dict(self._counters),
            }

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self._counters.clear()

    def on_gc(self, phase: str, info: Dict) -> None:
        """A `gc.callbacks` hook: one `host.gc` span per collection.
        (Collections run one at a time, so one open span suffices.)"""
        if phase == "start":
            sp = self.span("host.gc", generation=info["generation"])
            self._gc_span = sp
            sp.__enter__()
        else:
            sp, self._gc_span = self._gc_span, None
            if sp is not None:  # None: installed mid-collection
                sp.__exit__(None, None, None)


PROCESS = Recorder()


def install() -> None:
    """Record the garbage collector's collections in `PROCESS`, once per
    process."""
    if PROCESS.on_gc not in gc.callbacks:
        gc.callbacks.append(PROCESS.on_gc)
