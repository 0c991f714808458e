"""Pallas TPU kernel: occupancy-grid ray march for ad-hoc rays.

The CUDA reference (RT-NeRF / Instant-NGP style) walks each ray through
the occupancy grid with a DDA loop and stops at the box exit. TPU
adaptation, same shape as `alpha_composite`: rays are the vector
dimension (blocks of `br` lanes), samples are walked by a SEQUENTIAL
grid axis in chunks of `bs`, and the per-ray analytic box-exit t (slab
test, computed once at s == 0 into a VMEM scratch) drives whole-chunk
early termination — once every ray in the block has exited the scene
box, the remaining sample chunks are skipped via a carried done flag
(`pl.when`), writing exact zeros (a skipped sample is provably outside
the box, so skipping never changes the result, unlike the composite
kernel's t_eps tolerance).

The per-sample occupancy lookup is a gather with data-dependent indices;
TPUs have no per-lane random gather inside a kernel, so it is
re-expressed as one-hot MXU matmuls: the (G, G, G) grid is viewed
as a (G, G*G) matrix whose column x*G + y is the z-column of cell (x, y),
each ray's (1, bs) sample row selects its columns with a (bt, bs) one-hot
per table chunk of `bt` columns (so the one-hot never exceeds (bt, bs) in
VMEM), and a mask over the G z-entries selects the cell value. Rays are
walked by a fori_loop inside the block, which keeps every value 2-D with
samples on the lane axis, as the chip's tiling wants.

Semantics are EXACTLY `repro.kernels.ref.ray_march_ref` — a sample at
o + d * t is active iff strictly inside the [-0.5, 0.5)^3 box and in an
occupied cell — which is itself exactly `occupancy_lookup` on the
renderer's sample points; the parity tests pin bit-equality. `t` must be
non-decreasing (the deterministic eval samples from
`occupancy.ray_t_samples` are), or early termination is disabled by the
wrapper's `early_stop=False`.

Prefer `repro.kernels.ops.ray_march` (the canonical entry): it adds the
pure-jnp reference fallback and the autotuned block sizes. This raw
entry auto-detects `interpret` (compiled on TPU, interpret-mode
elsewhere) when left at None.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

_BIG = 3.0e38  # "never exits" sentinel, comfortably below f32 inf


def _ray_march_kernel(t_ref, ro_ref, rd_ref, occ_ref, out_ref,
                      texit_ref, done_ref, *, g, bt, n_t, early_stop):
    """Block: (br rays, bs samples). Grid axis 1 walks sample chunks.

    Everything stays 2-D with samples on the lane axis: a fori_loop walks
    the block's rays, and each ray's (1, bs) sample row is looked up by a
    (G, bt) x (bt, bs) one-hot matmul per table chunk (Mosaic lowers no
    3-D gathers or broadcasts of the (br, bs, 3) point cloud)."""
    s = pl.program_id(1)
    br = ro_ref.shape[0]

    @pl.when(s == 0)
    def _init():
        # Slab test: conservative per-ray box-exit t. Any t strictly
        # beyond it has the point outside [-0.5, 0.5]^3 on some axis.
        # Degenerate axes (d ~ 0): the axis never bounds the ray when the
        # origin coordinate is inside, and the ray never enters at all
        # when it is outside.
        o = ro_ref[...]  # (br, 3)
        d = rd_ref[...]
        safe = jnp.abs(d) > 1e-12
        inv = 1.0 / jnp.where(safe, d, 1.0)
        t1 = (-0.5 - o) * inv
        t2 = (0.5 - o) * inv
        per_axis = jnp.where(
            safe, jnp.maximum(t1, t2),
            jnp.where(jnp.abs(o) < 0.5, _BIG, -_BIG),
        )
        texit_ref[...] = jnp.min(per_axis, axis=1, keepdims=True)  # (br, 1)
        done_ref[...] = jnp.zeros_like(done_ref)

    def _step():
        t = t_ref[...]  # (1, bs)
        bs = t.shape[1]

        def one_ray(r, carry):
            o = ro_ref[pl.ds(r, 1), :]  # (1, 3)
            d = rd_ref[pl.ds(r, 1), :]
            inside = None
            cell = []
            for ax in range(3):
                p = o[:, ax:ax + 1] + d[:, ax:ax + 1] * t  # (1, bs)
                ins = (p > -0.5) & (p < 0.5)
                inside = ins if inside is None else inside & ins
                unit = jnp.clip(p + 0.5, 0.0, 1.0)
                cell.append(jnp.clip((unit * g).astype(jnp.int32), 0, g - 1))
            row = cell[0] * g + cell[1]  # (1, bs) in [0, G*G)
            zrow = jnp.zeros((g, bs), jnp.float32)
            for c in range(n_t):
                # One-hot "gather" of each sample's (x, y) grid column:
                # (G, bt) x (bt, bs) -> every sample's full z-column.
                rows = jax.lax.broadcasted_iota(jnp.int32, (bt, bs), 0)
                onehot = (rows == row - c * bt).astype(jnp.float32)
                zrow = zrow + jax.lax.dot_general(
                    occ_ref[:, c * bt:(c + 1) * bt], onehot,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            zs = jax.lax.broadcasted_iota(jnp.int32, (g, bs), 0)
            val = jnp.sum(
                jnp.where(zs == cell[2], zrow, 0.0), axis=0, keepdims=True
            )
            out_ref[pl.ds(r, 1), :] = (inside & (val > 0.5)).astype(
                jnp.float32
            )
            return carry

        jax.lax.fori_loop(0, br, one_ray, 0)
        if early_stop:
            # t is non-decreasing: once this chunk's last sample sits
            # strictly past EVERY ray's box exit, all later samples are
            # outside -> later chunks write exact zeros.
            done_ref[...] = (
                (jnp.max(t) > jnp.max(texit_ref[...]))
                .astype(jnp.float32).reshape(1, 1)
            )

    def _skip():
        out_ref[...] = jnp.zeros_like(out_ref)

    if early_stop:
        # Read the flag ONCE before branching: _step updates done_ref for
        # the NEXT chunk, and a second ref read after it would see the new
        # value and let _skip clobber the boundary chunk just computed.
        live = done_ref[0, 0] == 0.0
        pl.when(live)(_step)
        pl.when(jnp.logical_not(live))(_skip)
    else:
        _step()


@functools.partial(
    jax.jit, static_argnames=("br", "bs", "bt", "interpret", "early_stop")
)
def ray_march(
    occ: jnp.ndarray,  # (G, G, G) f32 {0, 1} occupancy
    rays_o: jnp.ndarray,  # (R, 3)
    rays_d: jnp.ndarray,  # (R, 3)
    t: jnp.ndarray,  # (S,) f32 sample depths, non-decreasing
    br: int = 8,
    bs: int = 128,
    bt: int = 1024,
    interpret: Optional[bool] = None,
    early_stop: bool = True,
) -> jnp.ndarray:
    """Returns active (R, S) f32 {0, 1} — see `ref.ray_march_ref`."""
    interpret = resolve_interpret(interpret)
    g = occ.shape[0]
    # (G, G*G) with column x*G + y holding the z-column of cell (x, y).
    occ_t = occ.reshape(g * g, g).T
    bt = min(bt, -(-(g * g) // 128) * 128)  # never pad past one lane tile
    pt = (-(g * g)) % bt
    occ_t = jnp.pad(occ_t, ((0, 0), (0, pt)))
    n_t = (g * g + pt) // bt

    R, S = rays_o.shape[0], t.shape[0]
    pr, ps = (-R) % br, (-S) % bs
    # Ray padding originates far outside the box with zero direction: the
    # slab test gives it texit = -BIG (it never bounds the block's early
    # exit) and every sample lands outside -> exact zero rows. Sample
    # padding uses a huge t: outside the box AND past every exit.
    ro = jnp.pad(rays_o, ((0, pr), (0, 0)), constant_values=10.0)
    rd = jnp.pad(rays_d, ((0, pr), (0, 0)))
    tt = jnp.pad(t, (0, ps), constant_values=1e9).reshape(1, -1)
    Rp, Sp = R + pr, S + ps

    out = pl.pallas_call(
        functools.partial(
            _ray_march_kernel, g=g, bt=bt, n_t=n_t, early_stop=early_stop
        ),
        grid=(Rp // br, Sp // bs),
        in_specs=[
            pl.BlockSpec((1, bs), lambda r, s: (0, s)),
            pl.BlockSpec((br, 3), lambda r, s: (r, 0)),
            pl.BlockSpec((br, 3), lambda r, s: (r, 0)),
            pl.BlockSpec((g, g * g + pt), lambda r, s: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, bs), lambda r, s: (r, s)),
        out_shape=jax.ShapeDtypeStruct((Rp, Sp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((br, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(tt, ro, rd, occ_t)
    return out[:R, :S]
