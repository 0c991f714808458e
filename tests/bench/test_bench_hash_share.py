"""`hash.cell_lookup_share`, the reader of the program's hash-lookup
counters: on synthetic run contexts, and on tiny Instant-NGP and Nerfacto
frames cells run end to end on the CPU, whose shares follow from which
levels are direct-indexed."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import common  # noqa: E402

NAME = "hash.cell_lookup_share"


def reader():
    return common.load_module(ROOT / "bench" / "metrics" / f"{NAME}.py",
                              "test_metric_hash_cell_lookup_share")


def ctx(counters=None):
    return {"stats": {} if counters is None
            else {"trace": {"spans": {}, "counters": counters}}}


def test_reads_cell_lookups_over_lookups():
    r = reader()
    assert r.read(ctx({"hash.lookups": 2048, "hash.cell_lookups": 640})) == \
        pytest.approx(31.25)
    assert r.read(ctx({"hash.lookups": 2048})) == 0.0
    assert r.read(ctx({})) is None  # a program without the counters
    assert r.read(ctx()) is None


def tiny_ngp():
    """`ngp-paper-t19` at small widths: 16 levels over 512-row tables,
    resolutions 4, 4, 5, 6, 6, 7, 8, ... 32, so (res+1)^3 fits for the
    first 5 levels: 5 of 16 direct-indexed."""
    from bench.traffic import generate

    cfg = common.load_config("ngp-paper-t19")
    cfg["model"].update(log2_table_size=9, base_resolution=4,
                        max_resolution=32, hidden_dim=16, color_hidden_dim=16)
    cfg.update(image_hw=16, n_train_views=3, train_steps=10)
    return cfg, generate.load_mix("fresh_frames"), 31.25


def tiny_nerfacto():
    """`nerfacto` at small widths: main field 4 levels over 256 rows (only
    5^3 fits), proposals 2 levels over 64 rows (none fits), queried at 16,
    8 and 8 samples a ray: 8 x 8 of 16 x 16 + 8 x 16 + 8 x 32 lookups."""
    from bench.traffic import generate

    cfg = common.load_config("nerfacto")
    m = cfg["model"]
    m["field"].update(n_levels=4, log2_table_size=8, base_resolution=4,
                      max_resolution=32, hidden_dim=16, color_hidden_dim=16)
    for p, top in zip(m["proposals"], (16, 32)):
        p.update(n_levels=2, log2_table_size=6, base_resolution=4,
                 max_resolution=top)
    m.update(n_initial=16, n_resampled=[8, 8])
    cfg["policy"]["hash_bits"] = {"hash": [8, 6, 5, 4], "prop1": [8, 6],
                                  "prop2": [8, 6]}
    cfg.update(image_hw=16, n_train_views=3, train_steps=10, batch_rays=128)
    return cfg, generate.load_mix("fresh_frames_nerfacto"), 10.0


@pytest.mark.parametrize("cell,tiny", [("ngp19.fresh800", tiny_ngp),
                                       ("nerfacto.fresh800", tiny_nerfacto)])
def test_tiny_cell_reports_the_direct_levels_share(monkeypatch, tmp_path,
                                                    cell, tiny):
    from bench import run as bench_run

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(common, "OUT", tmp_path / "out")
    cfg, mix, share = tiny()
    mix.update(image_hw=32, frames=400, check_items=8)
    line, _ = bench_run.run_cell(cell, 2 ** 33 + 93, 0.5, True,
                                 require_tpu=False, cfg_override=cfg,
                                 mix_override=mix)
    assert line["correct"] is True, line["check"]
    assert line["metrics"][NAME] == {"value": share, "unit": "%"}
