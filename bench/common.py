"""Shared harness pieces: set-up accounting from JAX's monitoring events,
host spans, the device description, and metric readers found by name."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import time
from pathlib import Path
from typing import Dict, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / "out"

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds JAX spent lowering and compiling top-level computations,
    backend compilations, and persistent compile-cache hits and misses,
    summed since registration (tracing is left out: nested jits record
    nested trace events, which would count twice)."""

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

    def snapshot(self) -> Dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}


class Spans:
    """Host spans around calls into each layer: written into the
    profiler's trace (`TraceAnnotation`) and summed in memory."""

    def __init__(self):
        self.seconds: Dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        from jax.profiler import TraceAnnotation

        t = time.perf_counter()
        with TraceAnnotation(name):
            yield
        self.seconds[name] = self.seconds.get(name, 0.0) + time.perf_counter() - t


def device_info(n_chips: int) -> Dict:
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs[:n_chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def load_config(name: str) -> Dict:
    path = BENCH / "configs" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration {name!r} ({path})")
    return json.loads(path.read_text())


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(cfg: Dict):
    """The configuration's plain reference, the file its config names."""
    return load_module(BENCH / "configs" / cfg["reference"],
                       "bench_reference_" + Path(cfg["reference"]).stem)


def read_metric(name: str, run) -> Optional[float]:
    """Per-layer metric `name` from its reader `metrics/<name>.py`, or
    None where the run holds nothing for it to read."""
    mod = load_module(BENCH / "metrics" / f"{name}.py",
                      "bench_metric_" + name.replace(".", "_"))
    return mod.read(run)
