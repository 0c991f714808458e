"""Backend detection shared by the raw Pallas kernels and `ops.py`.

`repro.kernels.ops` is the canonical entry point for all kernels: it
dispatches between the compiled Pallas path (TPU), interpret-mode Pallas
(CPU validation), and the pure-jnp references. The raw kernel modules use
`resolve_interpret` so that calling them directly still does the right
thing per backend (compiled on TPU, interpreted elsewhere), but callers
should prefer `ops` — it adds the reference fallback and keeps the
dispatch policy in one place.
"""
from __future__ import annotations

import os
import platform
from pathlib import Path
from typing import Dict, Optional

import jax

# The persistent compile cache's fixed home when JAX_COMPILATION_CACHE_DIR
# is unset: `.jax_cache/` at the checkout root (listed in .gitignore). A
# fixed path, because the path is part of what a later run must find.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    JAX_COMPILATION_CACHE_DIR, when set, is the cache (JAX reads it
    itself) and nothing else is configured. Otherwise the cache lives in
    `CHECKOUT_CACHE_DIR`. Call before the first compile: JAX decides at
    its first compile whether a cache is in use."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """None = auto: compiled on TPU, interpret-mode elsewhere."""
    if interpret is None:
        return not on_tpu()
    return bool(interpret)


def runner_fingerprint() -> Dict[str, object]:
    """Identity of the machine + kernel backend a benchmark ran on.

    Embedded in every BENCH_*.json so the regression gates can refuse to
    compare numbers produced by different backends (compiled Pallas on a
    TPU vs interpret-mode on some CPU) or different machines — the root
    cause of the recurring stale-baseline wart. `kernel_backend`,
    `jax_backend`, and `device_kind` are the comparability key; the rest
    is context for a human refreshing a baseline.
    """
    dev = jax.devices()[0]
    return {
        "kernel_backend": "interpret" if resolve_interpret(None)
        else "compiled",
        "jax_backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", "unknown"),
        "device_count": jax.device_count(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "jax_version": jax.__version__,
        "cpu_count": os.cpu_count(),
    }


BACKEND_KEYS = ("kernel_backend", "jax_backend", "device_kind")


def fingerprint_mismatch(a: Optional[dict], b: Optional[dict]):
    """Why two runner fingerprints are not comparable, or None if they are.

    Missing fingerprints (pre-PR-8 baselines) are treated as mismatched:
    a baseline without provenance cannot gate anything honestly.
    """
    if not a or not b:
        return "runner fingerprint missing (pre-layout-PR baseline?)"
    diffs = [
        f"{k}: {a.get(k)!r} vs {b.get(k)!r}"
        for k in BACKEND_KEYS
        if a.get(k) != b.get(k)
    ]
    return "; ".join(diffs) if diffs else None
