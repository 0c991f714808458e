"""Pallas TPU kernel: quantized matmul with int32 accumulation.

The TPU-native realization of HERO's bit-serial MLP unit (DESIGN.md §3):
the bit-serial PE's *numerics* are exact integer MACs, which int8 codes
with an int32 accumulator reproduce exactly for any b <= 8 (the per-unit
bit width only changes the code range, not the arithmetic); the bit-serial
*timing* lives in repro/hwsim. The MXU gets dense int8 tiles — serializing
bits on a systolic array would waste it.

Tiling: (bm x bk) @ (bk x bn) with an int32 VMEM accumulator scratch; K is
the innermost (sequential) grid axis so the accumulator carries across K
tiles — the standard Pallas matmul schedule, MXU-aligned (128) tiles.

Prefer `repro.kernels.ops.quant_matmul` (the canonical entry): it adds the
pure-jnp reference fallback. This raw entry auto-detects `interpret`
(compiled on TPU, interpret-mode elsewhere) when left at None.

`quant_matmul_packed` is the unpack-on-load variant: the weight operand
arrives as sub-byte bit-plane words (`repro.quant.packing` layout) and
each K-tile is expanded to int8-range codes INSIDE the kernel before the
MXU dot — the weight stream through HBM/VMEM is the packed bytes, not an
int8 inflation. bk=128 keeps tiles group-aligned (128 * bits is always a
multiple of 32), so a tile's words are self-contained.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret


def _qmm_kernel(x_ref, w_ref, sx_ref, sw_ref, zx_ref, o_ref, acc_ref, *, n_k):
    """One (bm, bn) output tile, accumulated over the K grid axis.

    x int8 codes (asymmetric, zero point zx), w int8 codes (symmetric):
      out = (sum_k (x - zx) * w) * sx * sw
          = (sum_k x*w  -  zx * sum_k w) * sx * sw
    Both terms accumulate exactly in int32 on the MXU. Zero-padded K tiles
    contribute 0 to both terms (padded x rows are 0 AND padded w rows are
    0, so x*w = 0 and wsum picks up nothing).
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 operands straight into the MXU (the v5e MXU takes no int32
    # operands), int32 accumulation via preferred_element_type.
    w = w_ref[...]
    prod = jax.lax.dot_general(
        x_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    wsum = jnp.sum(w.astype(jnp.int32), axis=0, keepdims=True)  # (1, bn)
    acc_ref[...] += prod - zx_ref[0, 0] * wsum

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = (
            acc_ref[...].astype(jnp.float32) * sx_ref[0, 0] * sw_ref[0, 0]
        )


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def quant_matmul(
    x_codes: jnp.ndarray,  # (M, K) int8 activation codes
    w_codes: jnp.ndarray,  # (K, N) int8 weight codes
    sx: jnp.ndarray,  # scalar f32 activation scale
    sw: jnp.ndarray,  # scalar f32 weight scale
    zx: jnp.ndarray,  # scalar int32 activation zero point
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Returns f32 (M, N) = dequant((x - zx) @ w) * sx * sw."""
    interpret = resolve_interpret(interpret)
    M, K = x_codes.shape
    K2, N = w_codes.shape
    assert K == K2, (x_codes.shape, w_codes.shape)
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    xp = jnp.pad(x_codes, ((0, pm), (0, pk)))
    wp = jnp.pad(w_codes, ((0, pk), (0, pn)))
    Mp, Kp, Np = M + pm, K + pk, N + pn
    n_k = Kp // bk

    out = pl.pallas_call(
        functools.partial(_qmm_kernel, n_k=n_k),
        grid=(Mp // bm, Np // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(
        xp,
        wp,
        jnp.asarray(sx, jnp.float32).reshape(1, 1),
        jnp.asarray(sw, jnp.float32).reshape(1, 1),
        jnp.asarray(zx, jnp.int32).reshape(1, 1),
    )
    return out[:M, :N]


# ---------------------------------------------------------------------------
# Packed-weight variant: sub-byte words in, int8 codes inside the kernel.
# ---------------------------------------------------------------------------
def _unpack_tile(words, bits: int, bk: int):
    """Storage-layout bit-plane words ((bk//32)*bits, bn) -> unsigned
    codes (bk, bn).

    Planar rows are group-major (row g*bits + p): one reshape splits the
    (group, plane) axes, then a single broadcast shift/mask expands every
    plane word across its 32 code rows at once and the plane sum (planes
    occupy disjoint bit positions, so + == |) collapses back. O(1) traced
    ops regardless of groups x bits — the old per-plane slice + concat
    loop emitted O(groups*bits) ops per tile trace.
    """
    n_groups = bk // 32
    bn = words.shape[-1]
    w = words.reshape(n_groups, bits, 1, bn)
    pos = jax.lax.broadcasted_iota(jnp.int32, (n_groups, bits, 32, bn), 2)
    pln = jax.lax.broadcasted_iota(jnp.int32, (n_groups, bits, 32, bn), 1)
    u = jnp.sum(((w >> pos) & 1) << pln, axis=1, dtype=jnp.int32)
    return u.reshape(bk, bn)


def _unpack_tile_native(words, bits: int, bk: int):
    """``tile:<bk>``-layout words ((bk//32)*bits, bn) -> unsigned codes
    (bk, bn).

    The repack (`kernels/repack.py`) made rows plane-major within the
    tile (row p*gt + g), so the reshape here splits (plane, group)
    directly off the rows the BlockSpec delivered — no permutation, no
    slicing; just the broadcast shift/mask and the plane sum.
    """
    gt = bk // 32
    bn = words.shape[-1]
    w = words.reshape(bits, gt, 1, bn)
    pos = jax.lax.broadcasted_iota(jnp.int32, (bits, gt, 32, bn), 2)
    pln = jax.lax.broadcasted_iota(jnp.int32, (bits, gt, 32, bn), 0)
    u = jnp.sum(((w >> pos) & 1) << pln, axis=0, dtype=jnp.int32)
    return u.reshape(bk, bn)


def _qmm_packed_kernel(
    x_ref, w_ref, sx_ref, sw_ref, zx_ref, off_ref, o_ref, acc_ref,
    *, n_k, bits, bk, k_rows, tile_native,
):
    """Packed-weight version of `_qmm_kernel`: identical accumulation
    algebra, but the weight tile is expanded from bit-plane words first.
    Rows past the true K are forced to code 0 so zero-padded K tiles
    contribute nothing to either the product or the wsum correction
    (padded words decode to offset garbage, not 0 — the mask, not the
    padding, owns that invariant). Codes clip to the int8 MXU range: only
    the paper-exact 8-bit grid's -129 level can clamp (one LSB), exactly
    as the unpacked int8 path clamps at build time.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    unpack = _unpack_tile_native if tile_native else _unpack_tile
    u = unpack(w_ref[...], bits, bk)
    q = u + off_ref[0, 0]
    row = jax.lax.broadcasted_iota(jnp.int32, q.shape, 0) + k * bk
    q = jnp.where(row < k_rows, q, 0)
    w = jnp.clip(q, -128, 127)  # clip BEFORE narrowing: int8 cannot wrap
    prod = jax.lax.dot_general(
        x_ref[...], w.astype(jnp.int8), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    wsum = jnp.sum(w, axis=0, keepdims=True)  # (1, bn)
    acc_ref[...] += prod - zx_ref[0, 0] * wsum

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = (
            acc_ref[...].astype(jnp.float32) * sx_ref[0, 0] * sw_ref[0, 0]
        )


@functools.partial(
    jax.jit, static_argnames=("bits", "bm", "bn", "bk", "interpret", "layout")
)
def quant_matmul_packed(
    x_codes: jnp.ndarray,  # (M, K) int8 activation codes
    w_words: jnp.ndarray,  # int32 bit-plane words (layout below)
    w_offset: jnp.ndarray,  # scalar int32 code offset (q = u + offset)
    sx: jnp.ndarray,  # scalar f32 activation scale
    sw: jnp.ndarray,  # scalar f32 weight scale
    zx: jnp.ndarray,  # scalar int32 activation zero point
    bits: int = 8,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: Optional[bool] = None,
    layout: str = "planar",
) -> jnp.ndarray:
    """f32 (M, N) = ((x - zx) @ unpack(w)) * sx * sw, weights packed.

    `layout="planar"`: w_words is the storage codec's (ceil(K/32)*bits, N)
    group-major order; rows are padded here, per call, to whole K-tiles.
    `layout="tile:<bk>"`: w_words was repacked once by
    `kernels/repack.py` to exactly ceil(K/bk) plane-major tile blocks —
    no row padding happens on the call path, and `bk` must equal the
    repack tile (enforced).
    """
    interpret = resolve_interpret(interpret)
    assert bk % 32 == 0, bk
    tile_native = layout != "planar"
    if tile_native:
        assert layout == f"tile:{bk}", (layout, bk)
    M, K = x_codes.shape
    wr, N = w_words.shape
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    xp = jnp.pad(x_codes, ((0, pm), (0, pk)))
    wrows = (bk // 32) * bits
    if tile_native:
        assert wr == ((K + pk) // bk) * wrows, (w_words.shape, K, bits, bk)
        wp = jnp.pad(w_words, ((0, 0), (0, pn)))
    else:
        groups = -(-K // 32)
        assert wr == groups * bits, (w_words.shape, K, bits)
        wr_full = ((K + pk) // 32) * bits
        wp = jnp.pad(w_words, ((0, wr_full - wr), (0, pn)))
    Mp, Kp, Np = M + pm, K + pk, N + pn
    n_k = Kp // bk

    out = pl.pallas_call(
        functools.partial(
            _qmm_packed_kernel, n_k=n_k, bits=bits, bk=bk, k_rows=K,
            tile_native=tile_native,
        ),
        grid=(Mp // bm, Np // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((wrows, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(
        xp,
        wp,
        jnp.asarray(sx, jnp.float32).reshape(1, 1),
        jnp.asarray(sw, jnp.float32).reshape(1, 1),
        jnp.asarray(zx, jnp.int32).reshape(1, 1),
        jnp.asarray(w_offset, jnp.int32).reshape(1, 1),
    )
    return out[:M, :N]
