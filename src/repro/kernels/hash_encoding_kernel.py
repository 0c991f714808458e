"""Pallas TPU kernel: hash-table gather as a one-hot MXU matmul.

TPUs have no efficient per-lane random gather; for VMEM-resident hash
levels (T <= 2^14 = `ONEHOT_MAX_ROWS`) the classic trick re-expresses the
8-corner gather as (points*8, T_tile) one-hot x (T_tile, F) matmul,
accumulated over T tiles (DESIGN.md §3). The one-hot never leaves VMEM;
the MXU does the "gather". Features are padded to the 128-lane boundary
by the wrapper. The work is P*T MACs per call, so larger tables are
refused here: `repro.kernels.ops.hash_encode` sends them to XLA's gather.

Prefer `repro.kernels.ops.hash_gather` (the canonical entry): it adds the
XLA-take reference fallback and the domain routing. This raw entry
auto-detects `interpret` (compiled on TPU, interpret-mode elsewhere) when
left at None.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

# The kernel's domain: tables of at most this many rows. Past it the
# one-hot work (P*T MACs) and its VMEM temporaries grow without bound.
ONEHOT_MAX_ROWS = 2 ** 14


def _hash_gather_kernel(idx_ref, table_ref, out_ref, acc_ref, *, bt, n_t):
    """Block: (bp indices) x (bt table rows, F). Grid: (P/bp, T/bt)."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = idx_ref[...]  # (bp, 1) int32 global row ids
    base = t * bt
    local = idx - base  # (bp, 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], bt), 1)
    onehot = (cols == local).astype(table_ref.dtype)  # (bp, bt)
    # HIGHEST: an f32 table must not round through one bf16 MXU pass; the
    # one-hot products are then exact and the gather is bit-identical.
    acc_ref[...] += jax.lax.dot_general(
        onehot, table_ref[...], (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    @pl.when(t == n_t - 1)
    def _done():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bp", "bt", "interpret"))
def hash_gather(
    indices: jnp.ndarray,  # (P,) int32 rows into the level table
    table: jnp.ndarray,  # (T, F) level features
    bp: int = 256,
    bt: int = 1024,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Returns (P, F) = table[indices] via one-hot matmuls. Raises for a
    table of more than `ONEHOT_MAX_ROWS` rows (outside the domain)."""
    interpret = resolve_interpret(interpret)
    P = indices.shape[0]
    T, F = table.shape
    if T > ONEHOT_MAX_ROWS:
        raise ValueError(
            f"hash_gather: a {T}-row table is outside the one-hot kernel's "
            f"domain (<= {ONEHOT_MAX_ROWS} rows); use "
            "repro.kernels.ops.hash_gather, which routes it to XLA's gather"
        )
    pf = (-F) % 128
    pt = (-T) % bt
    pp = (-P) % bp
    tab = jnp.pad(table, ((0, pt), (0, pf)))
    # out-of-range pad indices hit no one-hot column -> zero rows
    idx = jnp.pad(indices, (0, pp), constant_values=-1).reshape(-1, 1)
    Pp, Tp, Fp = P + pp, T + pt, F + pf
    n_t = Tp // bt

    out = pl.pallas_call(
        functools.partial(_hash_gather_kernel, bt=bt, n_t=n_t),
        grid=(Pp // bp, n_t),
        in_specs=[
            pl.BlockSpec((bp, 1), lambda p, t: (p, 0)),
            pl.BlockSpec((bt, Fp), lambda p, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((bp, Fp), lambda p, t: (p, 0)),
        out_shape=jax.ShapeDtypeStruct((Pp, Fp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bp, Fp), jnp.float32)],
        interpret=interpret,
    )(idx, tab)
    return out[:P, :F]
