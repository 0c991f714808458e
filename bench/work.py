"""Work the algorithm requires, from the configuration and call shapes.

Every count here is what the computation needs, not what an
implementation happens to do: a gather moves its indices, the rows it
reads and its output, whichever route (one-hot matmul or XLA gather)
serves it; the one-hot kernel's P x T multiply-adds are not counted. So
a kernel share of its roofline cannot pass 100%, and a later kernel that
does the same work is read against the same count.

Also here: the benchmark's own count of occupancy-active samples (the
occupancy oracle of the served field), and the table of peaks.
"""
from __future__ import annotations

import functools
import json
from pathlib import Path
from typing import Dict

import numpy as np

PEAKS_FILE = Path(__file__).with_name("peaks.json")
F32 = 4
I32 = 4


def peaks(device_kind: str) -> Dict:
    """Peaks of one chip of `device_kind`; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it "
                       f"to {PEAKS_FILE.name} with its source")
    return table[device_kind]


# ---------------------------------------------------------------------------
# Field shapes
# ---------------------------------------------------------------------------
def linear_dims(model: Dict) -> Dict[str, tuple]:
    enc = model["n_levels"] * model["n_features"]
    h, c, g = model["hidden_dim"], model["color_hidden_dim"], model["geo_feat_dim"]
    sh = (model["sh_degree"] + 1) ** 2
    return {
        "sigma/0": (enc, h),
        "sigma/1": (h, 1 + g),
        "color/0": (g + sh, c),
        "color/1": (c, c),
        "color/2": (c, 3),
    }


def field_ops_per_sample(model: Dict) -> int:
    """Operations of one field query: trilinear interpolation (8 corners
    x F features, a multiply and an add each, per level) and the five
    linears (a multiply and an add per weight)."""
    interp = model["n_levels"] * 8 * model["n_features"] * 2
    mlp = sum(2 * i * o for i, o in linear_dims(model).values())
    return interp + mlp


# ---------------------------------------------------------------------------
# Kernel byte counts
# ---------------------------------------------------------------------------
def gather_bytes(lookups: int, n_features: int) -> int:
    """A table gather: its int32 indices in, one F-wide f32 row read per
    index, the F-wide f32 result out."""
    return lookups * (I32 + 2 * n_features * F32)


def ray_march_bytes(rays: int, samples: int, grid: int) -> int:
    """The occupancy march: ray origins and directions in, the grid read
    once, the (rays, samples) f32 mask out."""
    return rays * 6 * F32 + grid ** 3 * F32 + rays * samples * F32


# ---------------------------------------------------------------------------
# Occupancy-active samples (the benchmark's own oracle of the served field)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=4)
def _count_fn(near: float, far: float, n_samples: int):
    import jax
    import jax.numpy as jnp

    t = np.linspace(near, far, n_samples, dtype=np.float32)

    @jax.jit
    def counts(occ, ro, rd):
        g = occ.shape[0]
        pts = ro[..., None, :] + rd[..., None, :] * jnp.asarray(t)[:, None]
        inside = jnp.all((pts > -0.5) & (pts < 0.5), axis=-1)
        cell = jnp.clip(((pts + 0.5) * g).astype(jnp.int32), 0, g - 1)
        hit = occ[cell[..., 0], cell[..., 1], cell[..., 2]] > 0.5
        return jnp.sum(inside & hit, axis=-1).sum(axis=-1)

    return counts


def active_samples(occ, ro: np.ndarray, rd: np.ndarray, render: Dict,
                   block: int = 256) -> np.ndarray:
    """Occupancy-active samples per slot: `ro`, `rd` are (n_slots, R, 3);
    a sample is active where it lies strictly inside the scene box and in
    an occupied cell of `occ` (G, G, G). Returns (n_slots,) int64."""
    import jax.numpy as jnp

    fn = _count_fn(float(render["near"]), float(render["far"]),
                   int(render["n_samples"]))
    out = []
    for s in range(0, ro.shape[0], block):
        a, b = ro[s:s + block], rd[s:s + block]
        pad = block - a.shape[0]
        if pad:  # one shape per call: padding rays lie outside the box
            a = np.concatenate([a, np.full((pad,) + a.shape[1:], 10.0, np.float32)])
            b = np.concatenate([b, np.zeros((pad,) + b.shape[1:], np.float32)])
        c = np.asarray(fn(occ, jnp.asarray(a), jnp.asarray(b)))
        out.append(c[:block - pad])
    return np.concatenate(out).astype(np.int64) if out else np.zeros(0, np.int64)
