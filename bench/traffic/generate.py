"""The one traffic generator. A mix is a data file beside this one
(`<mix>.json`) that names a pose path and its parameters; every stream is
a deterministic function of the mix and the run's `--seed`.

Pose paths (cameras on the scene's camera sphere, looking at the scene's
center, pinhole rays as the scenes' own cameras cast them):

- `fresh_orbit`: independent poses drawn from the seed, never repeating:
  no two cameras of a stream lie within `min_separation` (L-inf, world
  units) of each other, so no two share a pose cell of the serve engine's
  pose cache (cells are `pos_cell` = 0.05 wide).
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent


def load_mix(name: str) -> Dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


def seed_words(seed: int, n: int, *salt: int) -> List[int]:
    """`n` uint32 words from any non-negative integer seed (and salt)."""
    ss = np.random.SeedSequence([int(seed)] + [int(s) for s in salt])
    return [int(w) for w in ss.generate_state(n, np.uint32)]


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed, 4, *salt))


# ---------------------------------------------------------------------------
# Cameras
# ---------------------------------------------------------------------------
def look_at(azimuth: float, elevation: float, radius: float) -> np.ndarray:
    """Camera-to-world (3, 4) [R|t] of a camera on the sphere of `radius`
    looking at the origin, y up (the scenes' own camera construction)."""
    eye = np.array([
        radius * math.cos(azimuth) * math.cos(elevation),
        radius * math.sin(elevation),
        radius * math.sin(azimuth) * math.cos(elevation),
    ])
    fwd = -eye / np.linalg.norm(eye)
    right = np.cross(fwd, np.array([0.0, 1.0, 0.0]))
    right /= np.linalg.norm(right)
    up = np.cross(right, fwd)
    c2w = np.stack([right, up, -fwd], axis=1)
    return np.concatenate([c2w, eye[:, None]], axis=1).astype(np.float32)


def camera_rays(c2w: np.ndarray, hw: int, focal: float):
    """Pinhole rays of one pose, row-major pixels: (hw*hw, 3) origins and
    unit directions, float32."""
    i, j = np.meshgrid(np.arange(hw, dtype=np.float32),
                       np.arange(hw, dtype=np.float32), indexing="xy")
    x = (i - hw / 2 + 0.5) / focal
    y = -(j - hw / 2 + 0.5) / focal
    d = np.stack([x, y, -np.ones_like(x)], axis=-1).reshape(-1, 3)
    d = d @ c2w[:, :3].T
    d = (d / np.linalg.norm(d, axis=-1, keepdims=True)).astype(np.float32)
    o = np.broadcast_to(c2w[:, 3], d.shape).astype(np.float32)
    return o, d


def center_out(hw: int) -> np.ndarray:
    """A frame's pixels (indices into the row-major frame) by distance
    from the image center, nearest first."""
    y, x = np.divmod(np.arange(hw * hw), hw)
    r2 = (x - (hw - 1) / 2.0) ** 2 + (y - (hw - 1) / 2.0) ** 2
    return np.argsort(r2, kind="stable")


# ---------------------------------------------------------------------------
# Pose paths
# ---------------------------------------------------------------------------
def fresh_orbit(p: Dict, n: int, rng: np.random.Generator) -> List[np.ndarray]:
    lo, hi = p["elevation"]
    sep = float(p["min_separation"])
    poses, eyes = [], []
    while len(poses) < n:
        c2w = look_at(rng.uniform(0.0, 2.0 * math.pi), rng.uniform(lo, hi),
                      p["radius"])
        eye = c2w[:, 3]
        if all(np.max(np.abs(eye - e)) >= sep for e in eyes):
            poses.append(c2w)
            eyes.append(eye)
    return poses


PATHS = {"fresh_orbit": fresh_orbit}


def client_poses(mix: Dict, seed: int, n: int) -> List[List[np.ndarray]]:
    """The first `n` poses of every client of a frame mix."""
    path = mix["poses"]
    fn = PATHS[path["path"]]
    return [fn(path, n, rng_for(seed, 1, c)) for c in range(mix["clients"])]

