"""Pallas TPU kernel: volume-rendering alpha compositing.

The CUDA reference walks each ray serially with early termination. TPU
adaptation (DESIGN.md §3): rays are the vector dimension (blocks of 128
lanes), samples are walked by a SEQUENTIAL grid axis with the running
transmittance carried in a VMEM scratch accumulator — TPU grids execute
in order, so the carried accumulator is the idiomatic scan. No per-lane
early-exit branch (SIMD lanes would diverge), but whole sample-chunks CAN
be skipped once every ray in the block is saturated: a carried block-done
flag gates the chunk body with `pl.when` (`early_stop=True`). Skipped
chunks would have contributed at most `t_eps` per channel, so the numerics
match the dense walk to that tolerance.

  alpha_i = 1 - exp(-sigma_i * delta_i)
  T_i     = prod_{j<i} (1 - alpha_j) = exp(-sum_{j<i} sigma_j delta_j)
  color   = sum_i T_i * alpha_i * rgb_i ; acc = sum_i T_i * alpha_i

Prefer `repro.kernels.ops.alpha_composite` (the canonical entry): it adds
the pure-jnp reference fallback. This raw entry auto-detects `interpret`
(compiled on TPU, interpret-mode elsewhere) when left at None.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret


def _composite_kernel(sigma_ref, rgb_ref, delta_ref, color_ref, acc_ref,
                      trans_ref, done_ref, *, early_stop, t_eps):
    """Block: (br rays, bs samples). Grid axis 1 walks sample chunks.

    The exclusive in-chunk transmittance is exp(-(sigma*delta) @ U) with U
    the strictly upper-triangular (bs, bs) ones matrix: one MXU matmul
    gives every sample's exclusive optical-depth prefix sum (Mosaic has
    no cumprod). `rgb_ref` is channel-major (3, br, bs)."""
    s = pl.program_id(1)
    bs = sigma_ref.shape[1]

    @pl.when(s == 0)
    def _init():
        trans_ref[...] = jnp.ones_like(trans_ref)
        color_ref[...] = jnp.zeros_like(color_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        done_ref[...] = jnp.zeros_like(done_ref)

    def _step():
        tau = sigma_ref[...] * delta_ref[...]  # (br, bs) optical depth
        alpha = 1.0 - jnp.exp(-tau)
        row = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
        upper = (row < col).astype(jnp.float32)
        excl = jax.lax.dot_general(
            tau, upper, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )  # (br, bs): sum_{j<i} tau_j
        w = trans_ref[...] * jnp.exp(-excl) * alpha  # weights
        for c in range(3):
            color_ref[:, c:c + 1] += jnp.sum(
                w * rgb_ref[c], axis=1, keepdims=True
            )
        acc_ref[...] += jnp.sum(w, axis=1, keepdims=True)
        trans_ref[...] = trans_ref[...] * jnp.exp(
            -jnp.sum(tau, axis=1, keepdims=True)
        )
        if early_stop:
            # All rays in the block saturated -> skip the remaining chunks.
            done_ref[...] = (
                (jnp.max(trans_ref[...]) < t_eps).astype(jnp.float32).reshape(1, 1)
            )

    if early_stop:
        pl.when(done_ref[0, 0] == 0.0)(_step)
    else:
        _step()


@functools.partial(
    jax.jit, static_argnames=("br", "bs", "interpret", "early_stop", "t_eps")
)
def alpha_composite(
    sigma: jnp.ndarray,  # (R, S) f32
    rgb: jnp.ndarray,  # (R, S, 3) f32
    delta: jnp.ndarray,  # (R, S) f32 sample spacing
    br: int = 128,
    bs: int = 128,
    interpret: Optional[bool] = None,
    early_stop: bool = False,
    t_eps: float = 1e-6,
):
    """Returns (color (R, 3), acc (R, 1)) — white-background compositing is
    the caller's affair (color + (1-acc)*bg)."""
    interpret = resolve_interpret(interpret)
    R, S = sigma.shape
    pr, ps = (-R) % br, (-S) % bs
    # Sample padding contributes zero (sigma = delta = 0). Ray padding is
    # made instantly opaque so it cannot hold a partial block's done flag
    # at trans = 1 forever (padded rows are sliced off the outputs anyway).
    sig = jnp.pad(jnp.pad(sigma, ((0, 0), (0, ps))), ((0, pr), (0, 0)),
                  constant_values=1e4)
    dl = jnp.pad(jnp.pad(delta, ((0, 0), (0, ps))), ((0, pr), (0, 0)),
                 constant_values=1.0)
    rg = jnp.moveaxis(jnp.pad(rgb, ((0, pr), (0, ps), (0, 0))), 2, 0)
    Rp, Sp = R + pr, S + ps
    n_s = Sp // bs

    color, acc = pl.pallas_call(
        functools.partial(
            _composite_kernel, early_stop=early_stop, t_eps=t_eps
        ),
        grid=(Rp // br, n_s),
        in_specs=[
            pl.BlockSpec((br, bs), lambda r, s: (r, s)),
            pl.BlockSpec((3, br, bs), lambda r, s: (0, r, s)),
            pl.BlockSpec((br, bs), lambda r, s: (r, s)),
        ],
        out_specs=[
            pl.BlockSpec((br, 3), lambda r, s: (r, 0)),
            pl.BlockSpec((br, 1), lambda r, s: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Rp, 3), jnp.float32),
            jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((br, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(sig, rg, dl)
    return color[:R], acc[:R]
