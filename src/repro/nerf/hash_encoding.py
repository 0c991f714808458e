"""Multi-resolution hash encoding (Instant NGP, Muller et al. 2022).

L levels of feature grids with geometrically increasing resolution
N_l = floor(N_min * b^l). Levels whose dense grid fits the table budget are
direct-indexed (no collisions); finer levels use the spatial hash

    h(x) = (x0 * pi0) xor (x1 * pi1) xor (x2 * pi2)  mod T

with pi = (1, 2654435761, 805459861), computed in uint32 (wrap-around is the
spec). Per-level quantization (the paper's contribution) fake-quantizes each
level's table independently with its assigned bit width.

TPU note: the gather here is XLA `take`, as is the serve path's
`repro.kernels.ops.hash_encode` (one gather over all levels' tables).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PRIMES = (1, 2654435761, 805459861)


@dataclasses.dataclass(frozen=True)
class HashEncodingConfig:
    n_levels: int = 16
    n_features: int = 2  # F: features per entry
    log2_table_size: int = 12  # T = 2^log2_table_size (max entries per level)
    base_resolution: int = 4  # N_min
    max_resolution: int = 128  # N_max

    @property
    def table_size(self) -> int:
        return 1 << self.log2_table_size

    def level_scale(self) -> float:
        """Growth factor b = exp((ln N_max - ln N_min) / (L - 1))."""
        if self.n_levels == 1:
            return 1.0
        return float(
            np.exp(
                (np.log(self.max_resolution) - np.log(self.base_resolution))
                / (self.n_levels - 1)
            )
        )

    def resolutions(self) -> List[int]:
        b = self.level_scale()
        return [
            int(np.floor(self.base_resolution * (b**l))) for l in range(self.n_levels)
        ]

    def level_entries(self, level: int) -> int:
        """Number of entries actually stored for a level (direct vs hashed)."""
        res = self.resolutions()[level]
        dense = (res + 1) ** 3
        return min(dense, self.table_size)

    def is_direct(self, level: int) -> bool:
        res = self.resolutions()[level]
        return (res + 1) ** 3 <= self.table_size

    @property
    def out_dim(self) -> int:
        return self.n_levels * self.n_features


def init_hash_tables(
    key: jax.Array, cfg: HashEncodingConfig, dtype=jnp.float32
) -> Dict[str, jnp.ndarray]:
    """Uniform init in [-1e-4, 1e-4] as in Instant NGP."""
    tables = {}
    for l in range(cfg.n_levels):
        key, sub = jax.random.split(key)
        n = cfg.level_entries(l)
        tables[f"level_{l}"] = jax.random.uniform(
            sub, (n, cfg.n_features), dtype=dtype, minval=-1e-4, maxval=1e-4
        )
    return tables


def _corner_indices(
    x0: jnp.ndarray, level: int, cfg: HashEncodingConfig
) -> jnp.ndarray:
    """Map integer corner coords (P, 8, 3) -> table indices (P, 8)."""
    n = cfg.level_entries(level)
    if cfg.is_direct(level):
        res = cfg.resolutions()[level]
        stride = res + 1
        x = x0.astype(jnp.uint32)
        idx = x[..., 0] + x[..., 1] * stride + x[..., 2] * stride * stride
        return idx.astype(jnp.int32)
    x = x0.astype(jnp.uint32)
    h = (
        x[..., 0] * jnp.uint32(PRIMES[0])
        ^ x[..., 1] * jnp.uint32(PRIMES[1])
        ^ x[..., 2] * jnp.uint32(PRIMES[2])
    )
    return (h % jnp.uint32(n)).astype(jnp.int32)


# The 8 binary corner offsets of a voxel, shape (8, 3).
_CORNERS = np.stack(
    [[(c >> d) & 1 for d in range(3)] for c in range(8)], axis=0
).astype(np.int32)


def level_corner_data(
    points: jnp.ndarray, level: int, cfg: HashEncodingConfig
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-level voxel-corner indices and trilinear weights.

    points: (P, 3) in [0, 1].  Returns (idx (P, 8) int32, w (P, 8) f32).
    Shared by `hash_encode` below and the serve path's
    `repro.kernels.ops.hash_encode`.
    """
    res = cfg.resolutions()[level]
    x = points * res
    x0 = jnp.floor(x)
    frac = x - x0
    x0 = jnp.clip(x0.astype(jnp.int32), 0, res)  # (P, 3)

    corners = x0[:, None, :] + jnp.asarray(_CORNERS)[None, :, :]  # (P, 8, 3)
    corners = jnp.clip(corners, 0, res)
    idx = _corner_indices(corners, level, cfg)  # (P, 8)

    c = jnp.asarray(_CORNERS, jnp.float32)[None]  # (1, 8, 3)
    w = jnp.prod(
        c * frac[:, None, :] + (1.0 - c) * (1.0 - frac[:, None, :]), axis=-1
    )  # (P, 8)
    return idx, w


def cell_corner_rows(res: int) -> np.ndarray:
    """Rows of a direct-indexed level's 8 corners for every cell origin:
    (r, 8) int32 with r = (res+1)^3, row c for the cell whose corner 0 has
    dense index c, corners in `_CORNERS` order and clipped at `res`
    exactly as `level_corner_data` clips them. A table gathered by these
    rows serves a point's 8 corners from its corner-0 index alone."""
    s = res + 1
    c = np.arange(s ** 3, dtype=np.int64)
    x0 = np.stack([c % s, (c // s) % s, c // (s * s)], axis=-1)  # (r, 3)
    corners = np.minimum(x0[:, None, :] + _CORNERS[None], res)  # (r, 8, 3)
    return (corners[..., 0] + corners[..., 1] * s
            + corners[..., 2] * s * s).astype(np.int32)


def hash_encode(
    tables: Dict[str, jnp.ndarray],
    points: jnp.ndarray,
    cfg: HashEncodingConfig,
    level_bits: Optional[jnp.ndarray] = None,
    paper_exact: bool = True,
) -> jnp.ndarray:
    """Encode points (P, 3) in [0,1] -> features (P, L*F).

    level_bits: optional (L,) float array of per-level bit widths; when given
    each level's table is fake-quantized (symmetric, Eq. 4-5) with an STE so
    the encode stays differentiable for QAT. Bit widths >= 16 disable
    quantization for that level (full precision sentinel).
    """
    from repro.quant.linear_quant import weight_qparams
    from repro.quant.qat import ste_fake_quant

    feats = []
    for l in range(cfg.n_levels):
        table = tables[f"level_{l}"]
        if level_bits is not None:
            bits = level_bits[l]
            lo, hi = jnp.min(table), jnp.max(table)
            qp = weight_qparams(lo, hi, bits, paper_exact=paper_exact)
            q = ste_fake_quant(table, qp, symmetric=True)
            # bits >= 16 sentinel: keep full precision.
            table = jnp.where(bits >= 16.0, table, q)
        idx, w = level_corner_data(points, l, cfg)
        vals = jnp.take(table, idx, axis=0)  # (P, 8, F)
        feats.append(jnp.sum(vals * w[..., None], axis=1))  # (P, F)
    return jnp.concatenate(feats, axis=-1)
