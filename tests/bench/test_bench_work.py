"""Work counts and peaks of the chip benchmark (`bench/work.py`,
`bench/peaks.json`), against hand-worked values for both configurations."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import work  # noqa: E402


def model(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())["model"]


@pytest.mark.parametrize("name", ["ngp-paper-t19", "ngp-paper-t14"])
def test_field_ops_per_sample_by_hand(name):
    # interpolation: 16 levels x 8 corners x 2 features x 2 = 512
    # linears (color/0 takes 15 geometry + 16 SH features):
    # 2 x (32*64 + 64*16 + 31*64 + 64*64 + 64*3) = 18688
    assert work.field_ops_per_sample(model(name)) == 512 + 18688


def test_gather_bytes_by_hand():
    # 8 corners x 1000 samples, F = 2: int32 index + row read + row out
    assert work.gather_bytes(8000, 2) == 8000 * (4 + 8 + 8)


def test_hash_roofline_count_does_not_change_with_the_gather_route():
    """The same calls read the same share whether the table routes its
    levels to the one-hot kernel (T=2^14) or to XLA's gather (T=2^19)."""
    from bench import common
    from bench import trace as tr

    red = tr.reduce_events([
        tr.Event("/host:CPU", "t", tr.WINDOW_SPAN, 0, 1e9),
        tr.Event("/device:TPU:0", tr.OP_LINE, "%hash_gather.2 = f32[..]", 0, 2e8),
        tr.Event("/device:TPU:0", tr.OP_LINE, "%hash_gather.3 = f32[..]", 3e8, 3e8),
    ])
    shares = set()
    for name in ("ngp-paper-t19", "ngp-paper-t14"):
        run = {"reduction": red, "budget": 16384, "model": model(name),
               "peaks": work.peaks("TPU v5 lite")}
        shares.add(common.read_metric("hash_gather_roofline", run))
    (share,) = shares
    # two calls of 8 * 16384 lookups, 20 bytes each, at 819 GB/s, in 0.5 s
    assert share == pytest.approx(100 * 2 * 8 * 16384 * 20 / 819e9 / 0.5)


def test_ray_march_bytes_by_hand():
    assert work.ray_march_bytes(512, 32, 32) == 512 * 24 + 32 ** 3 * 4 + 512 * 32 * 4


def test_peaks_known_and_unknown_kind():
    p = work.peaks("TPU v5 lite")
    assert p["int8_ops"] == 393e12 and p["hbm_bytes_per_s"] == 819e9
    assert "source" in p
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v99 imaginary")


def test_active_samples_counts_box_and_occupancy():
    occ = np.zeros((4, 4, 4), np.float32)
    occ[2, 2, 2] = 1.0  # the cell [0.0, 0.25)^3 of the centered box
    render = {"near": 0.0, "far": 2.0, "n_samples": 9}  # t = 0, 0.25, ..
    # One ray along +x through the occupied cell's center, one outside.
    ro = np.float32([[[-1.0, 0.125, 0.125], [-1.0, 0.9, 0.9]]])
    rd = np.float32([[[1.0, 0.0, 0.0], [1.0, 0.0, 0.0]]])
    # Samples at x = -1 + t: x in [0, 0.25) only for t = 1.0 -> one sample.
    assert work.active_samples(occ, ro, rd, render).tolist() == [1]
