"""Occupancy grid: empty-space culling for the fused render engine.

Baked ONCE from a (pre)trained field by thresholding density on a dense
grid (Instant NGP's occupancy-grid idea, simplified to a static bake: the
HERO reward loop renders thousands of frames from one frozen geometry, so
there is nothing to keep updating). Baking supersamples each cell and
dilates the result so that a cell is only marked empty when a neighborhood
around it is below the density threshold — culled samples then contribute
~zero weight and the fused renderer matches the dense reference to well
under the 0.1 dB acceptance band.

The grid is registered as a pytree whose resolution/occupancy statistics
are static metadata: jitted renderers can derive static sample budgets
from `occupied_fraction` without retracing per frame.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class OccupancyGrid:
    """Boolean occupancy over the unit cube [0,1]^3, stored as f32 {0,1}."""

    occ: jnp.ndarray  # (G, G, G) f32, 1.0 = occupied
    resolution: int
    threshold: float
    occupied_fraction: float  # host-side stat, static under jit

    @property
    def n_occupied(self) -> int:
        return int(round(self.occupied_fraction * self.resolution**3))


jax.tree_util.register_dataclass(
    OccupancyGrid,
    data_fields=["occ"],
    meta_fields=["resolution", "threshold", "occupied_fraction"],
)


def _dilate_max3(occ: jnp.ndarray, iterations: int) -> jnp.ndarray:
    """3x3x3 max-pool dilation (SAME padding), `iterations` times."""
    for _ in range(iterations):
        occ = jax.lax.reduce_window(
            occ, -jnp.inf, jax.lax.max,
            window_dimensions=(3, 3, 3), window_strides=(1, 1, 1),
            padding="SAME",
        )
    return occ


def dilate_occupancy(grid: "OccupancyGrid", cells: int) -> "OccupancyGrid":
    """Grid with every occupied cell grown by `cells` in Chebyshev
    distance. Any point within `cells / resolution` (L-inf, world units:
    the box is unit-sized) of an occupied cell of the source grid lands
    in an occupied cell of the result — the conservative-coverage
    property the pose-cache warp tier relies on."""
    if cells <= 0:
        return grid
    occ = _dilate_max3(grid.occ, int(cells))
    return OccupancyGrid(
        occ=occ, resolution=grid.resolution, threshold=grid.threshold,
        occupied_fraction=float(jnp.mean(occ)),
    )


def ray_t_samples(rcfg) -> np.ndarray:
    """THE deterministic eval t-samples: (n_samples,) f32, host-computed.

    Single source of truth shared by every non-stratified path — the
    host-side plan/budget oracles here AND the device renderer
    (`fast_render` stages this exact array as a jit constant). Computing
    t once is what makes plan compaction and on-device compaction
    byte-identical end-to-end; `np.linspace` vs `jnp.linspace` differ by
    ~1 ulp and used to be the only divergence between the two paths.
    """
    return np.linspace(rcfg.near, rcfg.far, rcfg.n_samples, dtype=np.float32)


def bake_occupancy(
    params: Dict,
    cfg,  # NGPConfig
    resolution: int = 32,
    threshold: float = 1e-2,
    supersample: int = 2,
    dilate: int = 1,
    chunk: int = 65536,
    spec=None,
) -> OccupancyGrid:
    """Query sigma on a (resolution * supersample)^3 grid of the unit cube,
    max-pool down to resolution^3, threshold, dilate. One-time host loop."""
    from repro.nerf.ngp import ngp_apply

    fine = resolution * supersample
    axis = (np.arange(fine, dtype=np.float32) + 0.5) / fine
    gx, gy, gz = np.meshgrid(axis, axis, axis, indexing="ij")
    pts = np.stack([gx, gy, gz], axis=-1).reshape(-1, 3)
    dirs = np.broadcast_to(
        np.asarray([0.0, 0.0, 1.0], np.float32), pts.shape
    )  # sigma is view-independent

    # params are an argument, not a closure: closed over, the whole hash
    # table would be baked into the program as constants (a cache entry
    # of about the table's size, 64 MB at T=2^19).
    query = jax.jit(
        lambda q, p, d: ngp_apply(q, p, d, cfg, spec)[0],
    )
    sig = np.empty(pts.shape[0], np.float32)
    for s in range(0, pts.shape[0], chunk):
        sig[s : s + chunk] = np.asarray(
            query(params, jnp.asarray(pts[s : s + chunk]),
                  jnp.asarray(dirs[s : s + chunk]))
        )

    sig = jnp.asarray(sig.reshape(fine, fine, fine))
    if supersample > 1:
        sig = jax.lax.reduce_window(
            sig, -jnp.inf, jax.lax.max,
            window_dimensions=(supersample,) * 3,
            window_strides=(supersample,) * 3,
            padding="VALID",
        )
    occ = (sig > threshold).astype(jnp.float32)
    if dilate > 0:
        occ = _dilate_max3(occ, dilate)
    return OccupancyGrid(
        occ=occ,
        resolution=resolution,
        threshold=float(threshold),
        occupied_fraction=float(jnp.mean(occ)),
    )


# ---------------------------------------------------------------------------
# Bake registry: one grid per (weights, config) — shared across env instances
# ---------------------------------------------------------------------------
# The closed-loop search instantiates several envs per scene (one per
# hardware budget, plus batched wrappers); each bake is a dense host-side
# sigma sweep, so re-baking per instantiation multiplies the dominant
# setup cost for identical grids. The registry keys on a fingerprint of
# the frozen pretrained weights plus every bake parameter, so two envs on
# the same scene share ONE grid object while a finetuned/retrained model
# (different weights) still gets its own bake.
_BAKE_REGISTRY: Dict[tuple, OccupancyGrid] = {}
_BAKE_REGISTRY_CAP = 64


def params_fingerprint(params: Dict) -> str:
    """Content hash of a parameter pytree (order-independent leaf paths)."""
    import hashlib

    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in sorted(leaves, key=lambda kv: str(kv[0])):
        h.update(str(path).encode())
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()[:24]


def clear_occupancy_registry() -> None:
    _BAKE_REGISTRY.clear()


def occupancy_registry_size() -> int:
    return len(_BAKE_REGISTRY)


def bake_occupancy_cached(
    params: Dict,
    cfg,  # NGPConfig
    resolution: int = 32,
    threshold: float = 1e-2,
    supersample: int = 2,
    dilate: int = 1,
    chunk: int = 65536,
) -> OccupancyGrid:
    """`bake_occupancy` behind a content-addressed registry: identical
    (weights, config, bake knobs) return the SAME grid object."""
    key = (
        params_fingerprint(params), repr(cfg),
        resolution, float(threshold), supersample, dilate,
    )
    grid = _BAKE_REGISTRY.get(key)
    if grid is None:
        if len(_BAKE_REGISTRY) >= _BAKE_REGISTRY_CAP:
            _BAKE_REGISTRY.clear()  # bakes recompute exactly; cheap reset
        grid = bake_occupancy(
            params, cfg, resolution=resolution, threshold=threshold,
            supersample=supersample, dilate=dilate, chunk=chunk,
        )
        _BAKE_REGISTRY[key] = grid
    return grid


def occupancy_lookup(grid: OccupancyGrid, pts_unit: jnp.ndarray) -> jnp.ndarray:
    """(..., 3) points in [0,1] -> (...,) bool, True = occupied cell."""
    idx = jnp.clip(
        (pts_unit * grid.resolution).astype(jnp.int32), 0, grid.resolution - 1
    )
    return grid.occ[idx[..., 0], idx[..., 1], idx[..., 2]] > 0.5


def sample_active_mask(
    grid: OccupancyGrid,
    rays_o: np.ndarray,  # (..., 3)
    rays_d: np.ndarray,  # (..., 3)
    rcfg,  # RenderConfig (deterministic eval sampling)
    margin: float = 0.0,
):
    """Host-side oracle for which samples the renderer may cull.

    Returns (active (..., S) bool, pts (..., S, 3)): a sample is active
    iff it lies inside the scene box AND in an occupied grid cell. This is
    the single source of truth shared by `cull_budget` and the renderer's
    `CullPlan` builder — the two must count identically or budgets
    silently under-cover.

    `margin > 0` (world units) computes the CONSERVATIVE mask used by
    warped pose-cache plans: the box test expands by `margin` and the
    occupancy dilates by `ceil(margin * resolution)` cells, so the
    returned mask is a superset of the exact (`margin=0`) mask of ANY ray
    set whose per-sample points deviate from these by at most `margin`
    in L-inf.
    """
    ro = np.asarray(rays_o, np.float32)
    rd = np.asarray(rays_d, np.float32)
    t = ray_t_samples(rcfg)
    pts = ro[..., None, :] + rd[..., None, :] * t[:, None]
    lo, hi = -0.5 - margin, 0.5 + margin
    inside = np.all((pts > lo) & (pts < hi), axis=-1)
    g = grid.resolution
    occ = grid.occ
    if margin > 0.0:
        occ = _dilate_max3(occ, int(np.ceil(margin * g)))
    cell = np.clip(((pts + 0.5) * g).astype(np.int64), 0, g - 1)
    occ_np = np.asarray(occ) > 0.5
    return inside & occ_np[cell[..., 0], cell[..., 1], cell[..., 2]], pts


def cull_budget(
    grid: Optional[OccupancyGrid],
    rays_o: np.ndarray,  # (N, 3) — ALL rays the budget must cover
    rays_d: np.ndarray,
    rcfg,  # RenderConfig
    chunk: int,
    slack: float = 1.15,
    align: int = 128,
) -> int:
    """Static per-chunk sample budget for the compacting renderer.

    Counts the occupied samples of every `chunk`-ray slice of the given
    rays (deterministic eval sampling), takes the max. The active mask is
    params-independent, so the count is EXACT for these rays; `slack`
    only buys headroom when the returned budget is reused for ray
    populations beyond the ones counted here (an overflow silently drops
    the overflowing samples). One-time host cost.
    """
    n_samples = rcfg.n_samples
    if grid is None:
        return chunk * n_samples
    ro = np.asarray(rays_o, np.float32).reshape(-1, 3)
    rd = np.asarray(rays_d, np.float32).reshape(-1, 3)
    worst = 0
    for s in range(0, ro.shape[0], chunk):
        active, _ = sample_active_mask(
            grid, ro[s : s + chunk], rd[s : s + chunk], rcfg
        )
        worst = max(worst, int(np.sum(active)))
    budget = int(np.ceil(worst * slack / align) * align)
    return int(np.clip(budget, align, chunk * n_samples))
