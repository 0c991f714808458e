"""Single registry of runnable benchmarks.

Every benchmark harness registers here as (module, entry point,
description); `benchmarks/run.py` dispatches by name and `--list`
enumerates without importing the (jax-heavy) bench modules — entries are
resolved lazily at dispatch time.

Entry points follow one convention: `main(argv) -> int | None` (argparse
over the given argv, non-zero return = failure).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Callable, Dict, Optional


@dataclasses.dataclass(frozen=True)
class Bench:
    name: str
    module: str
    attr: str
    description: str

    def resolve(self) -> Callable:
        return getattr(importlib.import_module(self.module), self.attr)


_REGISTRY: Dict[str, Bench] = {}


def register(name: str, module: str, attr: str, description: str) -> None:
    _REGISTRY[name] = Bench(name, module, attr, description)


def get(name: str) -> Optional[Bench]:
    return _REGISTRY.get(name)


def names() -> Dict[str, str]:
    """name -> description for --list."""
    return {b.name: b.description for b in _REGISTRY.values()}


register(
    "paper_tables", "benchmarks.paper_tables", "main",
    "paper Table II/III + Fig. 4 + lambda ablation + roofline tables",
)
register(
    "batched_search", "benchmarks.batched_search", "main",
    "policies/sec: scalar vs batched vs full population scoring",
)
register(
    "render_throughput", "benchmarks.render_throughput", "main",
    "render-engine rays/sec + fused-vs-reference parity (BENCH_render.json)",
)
register(
    "closed_loop", "benchmarks.closed_loop", "main",
    "closed-loop search: policies/sec + Pareto frontier (BENCH_search.json)",
)
register(
    "lm_search", "benchmarks.lm_search", "main",
    "LM-workload closed-loop search: policies/sec + Pareto frontier "
    "(BENCH_lm.json)",
)
register(
    "serve", "benchmarks.serve_throughput", "main",
    "hero.serve request-batching render service: requests/sec + latency "
    "percentiles (BENCH_serve.json)",
)
register(
    "serve_burst", "benchmarks.serve_burst", "main",
    "multi-scene engine under bursty arrivals: p50/p95-under-load + LRU "
    "cache behavior (BENCH_serve.json 'burst' key)",
)
register(
    "artifact_size", "benchmarks.artifact_size", "main",
    "packed-artifact bytes by policy + codec throughput + roundtrip PSNR "
    "parity gates (BENCH_artifact.json)",
)
register(
    "autotune_quant_matmul", "benchmarks.autotune_quant_matmul", "main",
    "regenerate the committed packed-matmul block-size autotune table for "
    "this backend (src/repro/kernels/autotune_table.json)",
)
