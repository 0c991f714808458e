"""Measured block-size table for the quantized matmul kernels.

The Pallas grid (bm, bn, bk) that wins depends on the backend (compiled
MXU tiles on TPU vs interpret-mode Python execution on CPU, where fewer,
larger grid steps dominate), on the problem shape, and on the packed bit
width. Rather than guess, we measure once per backend and commit the
result next to the code — the same policy as the bench baselines:

  - `autotune_table.json` (this directory) maps a backend key
    (`backend_key()`: ``"tpu:<device_kind>"`` or
    ``"interpret:<jax_backend>"``) to a list of measured entries
    ``{m, k, n, bits, bm, bn, bk, ms, default_ms}``.
  - `lookup_block(m, k, n, bits)` picks the nearest measured entry in
    log-shape space for the current backend, falling back to the fixed
    128^3 default when the table has no entries for this backend. The
    block choice never changes numerics (integer accumulation is exact),
    only speed.
  - `benchmarks/autotune_quant_matmul.py` regenerates the table on a new
    runner; `benchmarks/render_throughput.py --quick` gates that the
    tuned choice never loses to the default.

`HardwareTarget.describe()` records `backend_key()` so artifacts carry
which table their numbers were produced under.
"""
from __future__ import annotations

import json
import math
import os
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import jax

from repro.kernels.backend import on_tpu

DEFAULT_BLOCK: Tuple[int, int, int] = (128, 128, 128)
TABLE_ENV = "REPRO_AUTOTUNE_TABLE"
_TABLE_PATH = Path(__file__).with_name("autotune_table.json")
_CACHE: Dict[str, list] = {}


def backend_key() -> str:
    """Table key for the current JAX backend/kernel-execution mode."""
    if on_tpu():
        kind = getattr(jax.devices()[0], "device_kind", "tpu")
        return f"tpu:{kind}"
    return f"interpret:{jax.default_backend()}"


def table_path() -> Path:
    return Path(os.environ.get(TABLE_ENV, _TABLE_PATH))


def load_table(path: Optional[Path] = None) -> dict:
    path = Path(path) if path else table_path()
    key = str(path)
    if key not in _CACHE:
        try:
            _CACHE[key] = json.loads(path.read_text())
        except (OSError, ValueError):
            _CACHE[key] = {"version": 1, "entries": {}}
    return _CACHE[key]


def clear_cache() -> None:
    _CACHE.clear()


def _score(entry: dict, m: int, k: int, n: int, bits: int) -> float:
    d = 0.0
    for key, v in (("m", m), ("k", k), ("n", n)):
        d += abs(math.log(max(v, 1) / max(int(entry[key]), 1)))
    d += abs(int(entry["bits"]) - bits) / 8.0
    return d


def lookup_block(
    m: int,
    k: int,
    n: int,
    bits: int = 8,
    *,
    fixed_bk: Optional[int] = None,
    table: Optional[dict] = None,
    key: Optional[str] = None,
) -> Tuple[int, int, int]:
    """(bm, bn, bk) for this problem: nearest measured entry on the
    current backend, or the 128^3 default when nothing was measured.

    `fixed_bk` pins the K-tile (a tile-native weight layout bakes its bk
    into the words) — only entries measured at that bk are considered,
    and the fallback keeps it.
    """
    entries = (table or load_table()).get("entries", {}).get(
        key or backend_key(), []
    )
    # Matmul entries are untagged; other kernels' entries carry a
    # "kernel" tag and live in the same per-backend list.
    entries = [e for e in entries if "kernel" not in e]
    if fixed_bk is not None:
        entries = [e for e in entries if int(e["bk"]) == int(fixed_bk)]
    if not entries:
        bm, bn, bk = DEFAULT_BLOCK
        return (bm, bn, int(fixed_bk) if fixed_bk else bk)
    best = min(entries, key=lambda e: _score(e, m, k, n, bits))
    return (int(best["bm"]), int(best["bn"]), int(best["bk"]))


# ---------------------------------------------------------------------------
# Measurement (used by benchmarks/autotune_quant_matmul.py and tests)
# ---------------------------------------------------------------------------
def _time_call(fn, repeats: int = 5) -> float:
    import time

    fn()  # warm / compile
    t0 = time.perf_counter()
    for _ in range(repeats):
        fn()
    return (time.perf_counter() - t0) / repeats * 1e3


def _operands(m: int, k: int, n: int, bits: int, seed: int):
    import numpy as np
    import jax.numpy as jnp

    from repro.quant.packing import pack_codes

    rng = np.random.RandomState(seed)
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    wq = pack_codes(rng.randint(lo, hi + 1, size=(k, n)), bits, scale=0.02)
    x = jnp.asarray(rng.randint(-128, 128, size=(m, k)), jnp.int8)
    return x, wq, jnp.float32(0.1), jnp.int32(7)


def time_block(
    m: int,
    k: int,
    n: int,
    bits: int,
    block: Tuple[int, int, int],
    repeats: int = 5,
    seed: int = 0,
) -> float:
    """Measured ms/call of the packed kernel for one (bm, bn, bk) on the
    operand recipe shared with `measure_entry` — the never-loses gate in
    `benchmarks/render_throughput.py` replays tuned-vs-default with this."""
    from repro.kernels.quant_matmul import quant_matmul_packed

    x, wq, sx, zx = _operands(m, k, n, bits, seed)
    bm, bn, bk = block

    def run():
        quant_matmul_packed(
            x, wq.words, wq.offset, sx, wq.scale, zx,
            bits=bits, bm=bm, bn=bn, bk=bk,
        ).block_until_ready()

    return _time_call(run, repeats)


def measure_entry(
    m: int,
    k: int,
    n: int,
    bits: int,
    candidates: Optional[List[Tuple[int, int, int]]] = None,
    repeats: int = 5,
    seed: int = 0,
) -> dict:
    """Measure candidate blocks for one (M, K, N, bits) packed matmul and
    return the winning table entry (with the 128^3 default time recorded
    so the never-loses gate can replay the comparison)."""
    if candidates is None:
        candidates = default_candidates(m, k, n)
    timed = {}
    for cand in candidates:
        timed[tuple(cand)] = time_block(m, k, n, bits, cand, repeats, seed)
    if DEFAULT_BLOCK not in timed:
        timed[DEFAULT_BLOCK] = time_block(
            m, k, n, bits, DEFAULT_BLOCK, repeats, seed
        )
    best = min(timed, key=timed.get)
    return {
        "m": m, "k": k, "n": n, "bits": bits,
        "bm": best[0], "bn": best[1], "bk": best[2],
        "ms": round(timed[best], 4),
        "default_ms": round(timed[DEFAULT_BLOCK], 4),
    }


def default_candidates(m: int, k: int, n: int) -> List[Tuple[int, int, int]]:
    """Small MXU-aligned candidate grid clipped to the padded problem."""
    def clip(opts, dim):
        padded = -(-max(dim, 1) // 128) * 128
        keep = sorted({min(o, padded) for o in opts})
        return [o for o in keep if o % 128 == 0] or [128]

    cands = []
    for bm in clip((128, 256, 512, 1024), m):
        for bn in clip((128, 256), n):
            for bk in clip((128, 256), k):
                cands.append((bm, bn, bk))
    return cands


# ---------------------------------------------------------------------------
# Ray-march kernel: (br, bs, bt) blocks, same table / same policy
# ---------------------------------------------------------------------------
# Entries share the per-backend list with the matmul entries but carry
# `"kernel": "ray_march"` plus {r, s, g, br, bs, bt, ms, default_ms};
# `lookup_block` above filters them out, and `lookup_ray_march` only sees
# them. Block choice never changes numerics (the march is an exact
# {0,1} mask), only speed.

# The smallest blocks the TPU tiling accepts: br rays on sublanes (8),
# bs samples on lanes (128), and a bt table chunk that holds a whole
# 32^3 grid's (x, y) columns in one one-hot matmul.
RAY_MARCH_DEFAULT: Tuple[int, int, int] = (8, 128, 1024)


def _ray_march_entries(table: Optional[dict], key: Optional[str]) -> list:
    entries = (table or load_table()).get("entries", {}).get(
        key or backend_key(), []
    )
    return [e for e in entries if e.get("kernel") == "ray_march"]


def lookup_ray_march(
    n_rays: int,
    n_samples: int,
    resolution: int,
    *,
    table: Optional[dict] = None,
    key: Optional[str] = None,
) -> Tuple[int, int, int]:
    """(br, bs, bt) for an (n_rays, n_samples) march over a resolution^3
    grid: nearest measured entry in log-shape space, or the fixed
    default when this backend has no measurements."""
    entries = _ray_march_entries(table, key)
    if not entries:
        return RAY_MARCH_DEFAULT

    def score(e):
        d = 0.0
        for k_, v in (("r", n_rays), ("s", n_samples), ("g", resolution)):
            d += abs(math.log(max(v, 1) / max(int(e[k_]), 1)))
        return d

    best = min(entries, key=score)
    return (int(best["br"]), int(best["bs"]), int(best["bt"]))


def _ray_march_operands(r: int, s: int, g: int, seed: int):
    import numpy as np
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    occ = jnp.asarray((rng.rand(g, g, g) < 0.3).astype(np.float32))
    ro = jnp.asarray(rng.randn(r, 3).astype(np.float32) * 0.5)
    rd = rng.randn(r, 3).astype(np.float32)
    rd = jnp.asarray(rd / np.linalg.norm(rd, axis=1, keepdims=True))
    t = jnp.asarray(np.linspace(0.05, 2.5, s, dtype=np.float32))
    return occ, ro, rd, t


def time_ray_march_block(
    r: int,
    s: int,
    g: int,
    block: Tuple[int, int, int],
    repeats: int = 5,
    seed: int = 0,
) -> float:
    """Measured ms/call of the march kernel for one (br, bs, bt) on the
    operand recipe shared with `measure_ray_march_entry` — the
    never-loses gate replays tuned-vs-default with this."""
    from repro.kernels.ray_march import ray_march

    occ, ro, rd, t = _ray_march_operands(r, s, g, seed)
    br, bs, bt = block

    def run():
        ray_march(occ, ro, rd, t, br=br, bs=bs, bt=bt).block_until_ready()

    return _time_call(run, repeats)


def measure_ray_march_entry(
    r: int,
    s: int,
    g: int,
    candidates: Optional[List[Tuple[int, int, int]]] = None,
    repeats: int = 5,
    seed: int = 0,
) -> dict:
    """Measure candidate blocks for one (rays, samples, resolution) march
    and return the winning tagged table entry."""
    if candidates is None:
        candidates = ray_march_candidates(r, s, g)
    timed = {}
    for cand in candidates:
        timed[tuple(cand)] = time_ray_march_block(r, s, g, cand, repeats, seed)
    if RAY_MARCH_DEFAULT not in timed:
        timed[RAY_MARCH_DEFAULT] = time_ray_march_block(
            r, s, g, RAY_MARCH_DEFAULT, repeats, seed
        )
    best = min(timed, key=timed.get)
    return {
        "kernel": "ray_march", "r": r, "s": s, "g": g,
        "br": best[0], "bs": best[1], "bt": best[2],
        "ms": round(timed[best], 4),
        "default_ms": round(timed[RAY_MARCH_DEFAULT], 4),
    }


def ray_march_candidates(r: int, s: int, g: int) -> List[Tuple[int, int, int]]:
    """Small candidate grid of chip-legal blocks (br a multiple of 8, bs
    and bt multiples of 128), clipped to the padded problem."""
    rp = -(-max(r, 1) // 8) * 8
    brs = sorted({min(o, rp) for o in (8, 16, 32)})
    sp = -(-max(s, 1) // 128) * 128
    bss = sorted({min(o, sp) for o in (128, 256)})
    gp = -(-(g * g) // 128) * 128
    bts = sorted({min(o, gp) for o in (256, 512, 1024)})
    return [(br, bs, bt) for br in brs for bs in bss for bt in bts]


def save_table(entries_by_key: Dict[str, list],
               path: Optional[Path] = None) -> Path:
    path = Path(path) if path else table_path()
    path.write_text(json.dumps(
        {"version": 1, "entries": entries_by_key}, indent=2
    ) + "\n")
    clear_cache()
    return path
