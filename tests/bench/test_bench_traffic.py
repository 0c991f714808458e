"""The benchmark's traffic generator (`bench/traffic/generate.py`): each
stream is a deterministic function of its seed; fresh poses never share
a pose cell of the serve engine within a window's worth of frames;
cameras look at the scene; pixels go center first."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.traffic import generate  # noqa: E402

BIG_SEED = 2 ** 33 + 12345  # wider than 32 bits, as the driver's are


def test_streams_are_deterministic_per_seed():
    m = generate.load_mix("fresh_frames")
    a = generate.client_poses(m, BIG_SEED, 12)
    b = generate.client_poses(m, BIG_SEED, 12)
    c = generate.client_poses(m, BIG_SEED + 1, 12)
    assert len(a) == m["clients"]
    for pa, pb in zip(a, b):
        assert all(np.array_equal(x, y) for x, y in zip(pa, pb))
    assert not all(np.array_equal(x, y) for x, y in zip(a[0], c[0]))


def test_fresh_poses_never_share_a_pose_cell():
    from repro.nerf.pose_cache import pose_cell_key
    from repro.hero.scheduler import EngineConfig

    m = generate.load_mix("fresh_frames")
    ecfg = EngineConfig()
    for seed in (0, 7, BIG_SEED):
        poses = generate.client_poses(m, seed, m["frames"])[0]
        keys = set()
        for c2w in poses:
            ro, rd = generate.camera_rays(c2w, 32, 1.2 * 32)
            keys.add(pose_cell_key(ro, rd, ecfg.pose_pos_cell, ecfg.pose_dir_cell))
        assert len(keys) == len(poses)


def test_cameras_look_at_the_scene():
    m = generate.load_mix("fresh_frames")
    for c2w in generate.client_poses(m, 3, 8)[0]:
        ro, rd = generate.camera_rays(c2w, 16, 1.2 * 16)
        center = rd[8 * 16 + 8]  # a pixel next to the image center
        to_origin = -ro[0] / np.linalg.norm(ro[0])
        assert float(center @ to_origin) > 0.99
        assert abs(np.linalg.norm(ro[0]) - m["poses"]["radius"]) < 1e-5


@pytest.mark.parametrize("hw", [1, 4, 7, 800])
def test_pixels_go_center_first(hw):
    order = generate.center_out(hw)
    assert sorted(order.tolist()) == list(range(hw * hw))
    y, x = np.divmod(order, hw)
    r2 = (x - (hw - 1) / 2.0) ** 2 + (y - (hw - 1) / 2.0) ** 2
    assert np.all(np.diff(r2) >= 0)
