"""Public jit'd entry points for the Pallas kernels — the CANONICAL entry.

`use_pallas="auto"` runs the kernels on TPU backends and falls back to the
jnp reference elsewhere; `True` forces interpret-mode Pallas (Python-level
execution of the kernel body — the CPU validation path), `False` forces
the reference.

Call kernels through this module rather than the raw `pallas_call`
wrappers: this layer owns the backend dispatch policy (Pallas vs
reference) and keeps kw defaults consistent. The raw entries auto-detect
`interpret` via `repro.kernels.backend` so direct calls stay correct, but
they never fall back to the reference.
"""
from __future__ import annotations

from collections import Counter

import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels import autotune as _autotune
from repro.kernels.backend import on_tpu as _on_tpu
from repro.kernels.alpha_composite import alpha_composite as _alpha_pallas
from repro.kernels.decode_attention_kernel import (
    decode_attention as _decode_pallas,
)
from repro.kernels.flash_attention_kernel import (
    flash_attention as _flash_pallas,
)
from repro.kernels.quant_matmul import (
    quant_matmul as _qmm_pallas,
    quant_matmul_packed as _qmm_packed_pallas,
)
from repro.kernels.ray_march import ray_march as _ray_march_pallas
from repro.quant.packing import tile_layout_bk as _tile_layout_bk


# How the "auto" calls resolved, counted per (entry, "compiled" |
# "reference") at trace time: a run on the chip asserts from this that no
# "auto" call fell back to the reference.
AUTO_RESOLVED: Counter = Counter()


def _resolve(use_pallas, entry: str):
    if use_pallas == "auto":
        run = _on_tpu()
        AUTO_RESOLVED[(entry, "compiled" if run else "reference")] += 1
        return run, not run
    return bool(use_pallas), True  # explicit True => interpret off-TPU


def _fill_blocks(kw, m, k, n, bits, fixed_bk=None):
    """Fill missing bm/bn/bk from the measured autotune table (falls back
    to 128^3). Explicit caller kwargs always win; a tile-native weight
    pins bk to its repack tile."""
    if all(b in kw for b in ("bm", "bn", "bk")):
        if fixed_bk is not None and kw["bk"] != fixed_bk:
            raise ValueError(
                f"bk={kw['bk']} conflicts with tile-native layout bk="
                f"{fixed_bk}"
            )
        return kw
    bm, bn, bk = _autotune.lookup_block(m, k, n, bits, fixed_bk=fixed_bk)
    kw.setdefault("bm", bm)
    kw.setdefault("bn", bn)
    kw.setdefault("bk", bk)
    return kw


def quant_matmul(x_codes, w_codes, sx, sw, zx, use_pallas="auto", **kw):
    run, interpret = _resolve(use_pallas, "quant_matmul")
    if not run:
        return ref.quant_matmul_ref(x_codes, w_codes, sx, sw, zx)
    kw = _fill_blocks(kw, x_codes.shape[0], x_codes.shape[1],
                      w_codes.shape[1], 8)
    return _qmm_pallas(
        x_codes, w_codes, sx, sw, zx,
        interpret=interpret and not _on_tpu(), **kw,
    )


def quant_matmul_packed(x_codes, wq, sx, sw, zx, use_pallas="auto", **kw):
    """`quant_matmul` over a sub-byte `PackedTensor` weight operand
    (`repro.quant.packing`). The Pallas path expands packed tiles to
    int8-range codes inside the kernel (unpack-on-load) and understands
    both word layouts — the storage-planar order and the
    `kernels/repack.py` tile-native order, whose repack bk pins the
    kernel's K-tile; the reference unpacks with the pure-jnp codec
    (layout-aware) and reuses `quant_matmul_ref`. Missing block sizes
    come from the measured autotune table."""
    run, interpret = _resolve(use_pallas, "quant_matmul_packed")
    if not run:
        return ref.quant_matmul_packed_ref(x_codes, wq, sx, sw, zx)
    layout = getattr(wq, "layout", "planar")
    fixed_bk = _tile_layout_bk(layout)
    kw = _fill_blocks(kw, x_codes.shape[0], x_codes.shape[1], wq.cols,
                      wq.bits, fixed_bk=fixed_bk)
    return _qmm_packed_pallas(
        x_codes, wq.words, wq.offset, sx, sw, zx, bits=wq.bits,
        layout=layout, interpret=interpret and not _on_tpu(), **kw,
    )


def hash_encode(corner_idx, corner_w, table_cat, level_rows,
                cells_cat=None, direct=()):
    """Fused multi-level hash-grid encode: XLA gathers of the corner rows
    + trilinear interpolation.

    corner_idx    (L, B, 8) int32 — per-level in-table corner indices
    corner_w      (L, B, 8) f32   — matching trilinear weights
    table_cat     (T, F)    f32   — all level tables stacked row-wise
    level_rows    L static ints   — each level's row count in table_cat
    cells_cat     (D, 8*F)  f32   — cell-major rows of the direct-indexed
                                    levels, stacked in level order: row c
                                    of a level's block holds its 8 corner
                                    rows of the cell whose corner 0 is row
                                    c (`fast_render.repack_fused_pack`)
    direct        L static bools  — which levels read `cells_cat`; empty
                                    for none

    A hashed level gathers 8 rows of `table_cat` a point, all such levels
    in ONE gather; a direct level gathers ONE row of `cells_cat` a point,
    at its corner-0 index, all such levels in one more gather. With no
    direct level the program is the single gather alone.

    Returns (B, L*F) features in level-major column order — bit-identical
    to gathering each level's table separately and concatenating (pinned
    by tests on the CPU; on a TPU the trilinear sum over cell-row values
    may round differently in the last bit).
    """
    L, B, C = corner_idx.shape
    rows = [int(r) for r in level_rows]
    if len(rows) != L or sum(rows) != table_cat.shape[0]:
        raise ValueError(f"level_rows {rows} do not split a "
                         f"{table_cat.shape[0]}-row table into {L} levels")
    direct = [bool(d) for d in direct] or [False] * L
    if len(direct) != L:
        raise ValueError(f"direct has {len(direct)} flags for {L} levels")
    offs = np.concatenate([[0], np.cumsum(rows)[:-1]]).astype(np.int32)
    if not any(direct):
        flat = (corner_idx + jnp.asarray(offs)[:, None, None]).reshape(-1)
        vals = hash_gather(flat, table_cat).reshape(L, B, C, -1)
    else:
        cell_lv = [l for l in range(L) if direct[l]]
        hash_lv = [l for l in range(L) if not direct[l]]
        cell_rows = [rows[l] for l in cell_lv]
        if cells_cat is None or cells_cat.shape != (
                sum(cell_rows), C * table_cat.shape[1]):
            raise ValueError(
                f"direct levels {cell_lv} need a ({sum(cell_rows)}, "
                f"{C * table_cat.shape[1]}) cell-major table")
        coffs = np.concatenate([[0], np.cumsum(cell_rows)[:-1]])
        cell_idx = jnp.stack([corner_idx[l, :, 0] + int(o)
                              for l, o in zip(cell_lv, coffs)])
        cell = hash_gather(cell_idx.reshape(-1), cells_cat).reshape(
            len(cell_lv), B, C, -1)
        part = {l: cell[i] for i, l in enumerate(cell_lv)}
        if hash_lv:
            hash_idx = jnp.stack([corner_idx[l] + int(offs[l])
                                  for l in hash_lv])
            hashed = hash_gather(hash_idx.reshape(-1), table_cat).reshape(
                len(hash_lv), B, C, -1)
            part.update({l: hashed[i] for i, l in enumerate(hash_lv)})
        vals = jnp.stack([part[l] for l in range(L)])
    feats = jnp.sum(vals * corner_w[..., None], axis=2)  # (L, B, F)
    return jnp.moveaxis(feats, 0, 1).reshape(B, -1)


def fused_field_query(corner_idx, corner_w, table_cat, level_rows,
                      wq, act, use_pallas="auto", cells_cat=None, direct=(),
                      **kw):
    """hash_encode -> quantized matmul, the fused first-layer field query
    of `FastRenderEngine`'s integer path.

    `cells_cat` / `direct` are `hash_encode`'s: the cell-major rows of the
    direct-indexed levels and which levels read them.
    `act` carries the activation grid of the first linear layer (the
    FusedPack layer dict fields): sx scale, zx int zero point (int8-
    shifted), zx_f float zero point, qmax code ceiling, off int8 shift.
    `wq` is the layer's `PackedTensor` (planar or tile-native). Returns
    the f32 pre-activation (B, N).
    """
    enc = hash_encode(corner_idx, corner_w, table_cat, level_rows,
                      cells_cat, direct)
    codes = jnp.clip(jnp.round(enc / act["sx"] + act["zx_f"]), 0.0,
                     act["qmax"])
    ci8 = (codes - act["off"]).astype(jnp.int8)
    return quant_matmul_packed(ci8, wq, act["sx"], wq.scale, act["zx"],
                               use_pallas=use_pallas, **kw)


def alpha_composite(sigma, rgb, delta, use_pallas="auto", **kw):
    """kw passes through to the kernel — notably `early_stop=True` enables
    the transmittance-based chunk skipping (ignored by the reference)."""
    run, interpret = _resolve(use_pallas, "alpha_composite")
    if not run:
        return ref.alpha_composite_ref(sigma, rgb, delta)
    return _alpha_pallas(
        sigma, rgb, delta, interpret=interpret and not _on_tpu(), **kw
    )


def ray_march(occ, rays_o, rays_d, t, use_pallas="auto", **kw):
    """Active-sample mask (R, S) f32 {0,1} from marching the occupancy
    grid — exactly `ref.ray_march_ref` (and `occupancy_lookup` on the
    renderer's sample points); the block choice never changes the mask.
    `t` must be non-decreasing for `early_stop=True` (the default);
    missing br/bs/bt come from the measured autotune table."""
    run, interpret = _resolve(use_pallas, "ray_march")
    if not run:
        return ref.ray_march_ref(occ, rays_o, rays_d, t)
    if not all(b in kw for b in ("br", "bs", "bt")):
        br, bs, bt = _autotune.lookup_ray_march(
            rays_o.shape[0], t.shape[0], occ.shape[0]
        )
        kw.setdefault("br", br)
        kw.setdefault("bs", bs)
        kw.setdefault("bt", bt)
    return _ray_march_pallas(
        occ, rays_o, rays_d, t, interpret=interpret and not _on_tpu(), **kw
    )


def hash_gather(indices, table):
    """table[indices] through XLA's gather, on every backend (exact: the
    rows come back bit for bit)."""
    return ref.hash_gather_ref(indices, table)


def decode_attention(q, k, v, length, use_pallas="auto", **kw):
    run, interpret = _resolve(use_pallas, "decode_attention")
    if not run:
        return ref.decode_attention_ref(q, k, v, length)
    return _decode_pallas(
        q, k, v, length, interpret=interpret and not _on_tpu(), **kw
    )


def flash_attention(q, k, v, causal=True, use_pallas="auto", **kw):
    run, interpret = _resolve(use_pallas, "flash_attention")
    if not run:
        return ref.flash_attention_ref(q, k, v, causal=causal)
    return _flash_pallas(
        q, k, v, causal=causal, interpret=interpret and not _on_tpu(), **kw
    )
