"""The `nerfacto.fresh800` cell: its manifest entries resolve to their
files; the program agrees with the plain reference
(`bench/configs/nerfacto.py`) at a small size on seeded random weights,
in float and quantized, while the reference computed in a lower
precision or with a proposal pass left out does not; the cell driven
end to end on the CPU reads `correct` true, and false for each planted
fault; the new readers read what they should."""
import dataclasses
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import common  # noqa: E402

CELL = "nerfacto.fresh800"
SEED = 2 ** 33 + 15


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch, tmp_path):
    # With the variable set, the harness leaves JAX's cache as it is
    # (off in a test process), and writes nothing into the checkout.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def tiny_cfg():
    """The configuration at small widths of the same shape: main 4 levels
    T=2^8, proposals 2 levels T=2^6, 16-wide MLPs, 16 -> 8 -> 8 samples."""
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
    return cfg


def test_manifest_entries_resolve_to_their_files():
    from bench.traffic import generate

    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    conf = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    cfg = common.load_config(cell["config"])
    assert (ROOT / conf["file"]).is_file() and cfg["name"] == conf["name"]
    assert (ROOT / "bench" / "configs" / cfg["reference"]).is_file()
    mix = generate.load_mix(cell["traffic"])
    assert (ROOT / "bench" / f"{mix['kind']}.py").is_file()
    assert cell["chips"] == 1 and set(conf["reduced"]) <= set(cfg["published"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in m.get("workloads", [CELL]):
            assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {"device_idle.serve", "device_idle.engine",
                      "engine.host_ms", "render.proposal_ms",
                      "render.shade_ms", "mfu.nerfacto_serve"}
    ref_mix = generate.load_mix("fresh_frames")
    assert {k: v for k, v in mix.items() if k not in ("kind", "why")} == \
        {k: v for k, v in ref_mix.items() if k not in ("kind", "why")}


def test_model_block_is_the_published_nerfacto():
    from bench import serve_nerfacto
    from repro.configs.ngp import nerfacto

    cfg = common.load_config("nerfacto")
    n = cfg["n_train_views"]
    assert serve_nerfacto.model_config(cfg["model"], n) == nerfacto(n)


# ---------------------------------------------------------------------------
# Program against the plain reference, seeded random weights
# ---------------------------------------------------------------------------
def random_setup(seed):
    """(cfg, program config, random params, calibration rays, rays)."""
    import jax

    from bench import serve_nerfacto
    from repro.nerf import nerfacto as nf

    cfg = tiny_cfg()
    ncfg = serve_nerfacto.model_config(cfg["model"], cfg["n_train_views"])
    p = nf.init_nerfacto(jax.random.PRNGKey(seed), ncfg)
    key = jax.random.PRNGKey(seed + 1000)
    for top in [k for k in p if k.endswith("hash")]:
        for name, t in p[top].items():  # tables that vary over the scene
            key, sub = jax.random.split(key)
            p[top][name] = jax.random.uniform(sub, t.shape, minval=-1.0,
                                              maxval=1.0)
    rng = np.random.default_rng(seed)

    def rays(n):
        o = np.tile(np.float32([[0.3, 0.4, 1.2]]), (n, 1))
        d = -o + rng.normal(scale=0.3, size=(n, 3)).astype(np.float32)
        return o, (d / np.linalg.norm(d, axis=-1, keepdims=True)).astype(np.float32)

    return cfg, ncfg, p, rays(32), rays(64)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_float_field_matches_reference(seed):
    import jax.numpy as jnp

    from repro.nerf import nerfacto as nf

    cfg, ncfg, p, _, (o, d) = random_setup(seed)
    ref = common.reference_module(cfg)
    want = ref.render_float(p, cfg, o, d)
    got, _, _ = nf.render_rays(p, jnp.asarray(o), jnp.asarray(d), ncfg,
                               nf.serve_appearance(p))
    # Two float32 programs of the same equations: sample edges agree to
    # ulps, and colors (continuous in them) to a few ulps of [0, 1].
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


def served_and_reference(seed, **ref_kw):
    from bench import serve, serve_nerfacto
    from repro import hero
    from repro.hero import ServeConfig

    cfg, ncfg, p, (co, cd), (o, d) = random_setup(seed)
    art = serve_nerfacto.pack_artifact(cfg, ncfg, p, co, cd)
    got = hero.serve(art, ServeConfig(slots=2, slot_rays=32)).render(o, d)
    ref = common.reference_module(cfg)
    want = ref.Reference(p, cfg, co, cd).render(o, d)
    numbers = serve.gap_numbers(got, want)
    controls = {name: serve.gap_numbers(
        ref.Reference(p, cfg, co, cd, **kw).render(o, d), want)
        for name, kw in serve_nerfacto.CONTROLS.items()}
    return numbers, controls


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_quantized_served_colors_match_reference(seed):
    numbers, controls = served_and_reference(seed)
    # The served colors of every ray within 1e-4 (1/39 of an 8-bit level)
    # of the reference: the same codes, integer dot products scaled once.
    assert numbers["rays_off_share"] == 0.0, numbers
    # One bfloat16 pass over the gathered tables, and a proposal pass left
    # out, move the colors past it.
    assert controls["bfloat16"]["rays_off_share"] > 0.0, controls
    assert controls["no_proposal_2"]["rays_off_share"] > 0.0, controls


# ---------------------------------------------------------------------------
# The cell end to end
# ---------------------------------------------------------------------------
def run(fault=None):
    from bench import run as bench_run
    from bench.traffic import generate

    mix = generate.load_mix("fresh_frames_nerfacto")
    mix.update(image_hw=32, frames=400, check_items=8)
    line, out = bench_run.run_cell(CELL, SEED, 0.5, False, require_tpu=False,
                                   fault=fault, cfg_override=tiny_cfg(),
                                   mix_override=mix)
    return line, out


def test_sound_run_is_correct():
    line, out = run()
    assert line["correct"] is True, line["check"]
    assert set(line["metrics"]) == {"rays_per_s", "setup_s"}
    assert out["setup"]["window_compiles"] == 0
    assert out["setup"]["window_budget_retraces"] == 0
    counters = out["stats"]["trace"]["counters"]
    assert counters["render.proposal_samples"] == 24 * out["rays"]
    assert counters["render.shade_samples"] == 8 * out["rays"]


def half_left_out(engine):
    """Half of each slot's rays never rendered."""
    stepper = engine._stepper
    real = stepper.step_items

    def broken(scene, artifact, items, ro, rd):
        colors = np.array(real(scene, artifact, items, ro, rd))
        colors[:, colors.shape[1] // 2:] = 0.0
        return colors

    stepper.step_items = broken


def proposal_1_intervals(monkeypatch):
    """The shade program fed the intervals proposal 1 places (its second
    pass skipped)."""
    from repro.nerf import fast_render as fr

    real = fr._slot_propose_impl

    def first_pass_only(pack, ro, rd, *, cfg, use_pallas):
        one = dataclasses.replace(cfg, proposals=cfg.proposals[:1],
                                  n_resampled=cfg.n_resampled[:1])
        return real(pack, ro, rd, cfg=one, use_pallas=use_pallas)

    return lambda engine: monkeypatch.setattr(fr, "_slot_propose_impl",
                                              first_pass_only)


@pytest.mark.parametrize("how", ["half_left_out", "proposal_1_intervals"])
def test_planted_fault_is_not_correct(how, monkeypatch):
    fault = (half_left_out if how == "half_left_out"
             else proposal_1_intervals(monkeypatch))
    line, _ = run(fault)
    assert line["correct"] is False, (how, line["check"])


# ---------------------------------------------------------------------------
# Readers and work counts
# ---------------------------------------------------------------------------
def reader(name):
    return common.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                              "test_metric_" + name.replace(".", "_"))


def test_work_counts_by_hand():
    from bench import nerfacto_work

    m = common.load_config("nerfacto")["model"]
    # proposal: 5 levels x 8 corners x 2 features x 2 = 160, and
    # 2 x (10*16 + 16*1) = 352
    assert nerfacto_work.proposal_ops_per_sample(m) == 160 + 352
    # main: 16 x 8 x 2 x 2 = 512, and 2 x (32*64 + 64*16 + 63*64 + 64*64
    # + 64*3) = 22784
    assert nerfacto_work.shade_ops_per_sample(m) == 512 + 22784


def test_readers_on_a_synthetic_run():
    from bench import trace as tr

    E = tr.Event
    D = "/device:TPU:0"
    red = tr.reduce_events([
        E("/host:CPU", "t", tr.WINDOW_SPAN, 0, 1e9),
        E(D, tr.MODULE_LINE, "jit__slot_propose_impl(3)", 0, 3e6),
        E(D, tr.MODULE_LINE, "jit__slot_shade_impl(4)", 3e6, 1e6),
        E(D, tr.MODULE_LINE, "jit__slot_propose_impl(3)", 4e6, 3e6),
        E(D, tr.MODULE_LINE, "jit__slot_shade_impl(4)", 7e6, 1e6),
    ])
    m = common.load_config("nerfacto")["model"]
    run = {"reduction": red, "slots": 2, "window_s": 2.0, "model": m,
           "peaks": {"int8_ops": 1e12},
           "stats": {"trace": {"counters": {
               "render.proposal_samples": 1024 * 352,
               "render.shade_samples": 1024 * 48}}}}
    assert reader("render.proposal_ms").read(run) == pytest.approx(3.0)
    assert reader("render.shade_ms").read(run) == pytest.approx(1.0)
    ops = 1024 * (352 * 512 + 48 * 23296)
    assert reader("mfu.nerfacto_serve").read(run) == pytest.approx(
        100.0 * ops / 2.0 / 1e12)
    # A program without the Nerfacto path reads nothing, and raises not.
    empty = dict(run, reduction=tr.reduce_events([
        E("/host:CPU", "t", tr.WINDOW_SPAN, 0, 1e9),
        E(D, tr.MODULE_LINE, "jit__slot_march_impl(1)", 0, 1e6)]),
        stats={"trace": {"counters": {}}})
    for name in ("render.proposal_ms", "render.shade_ms", "mfu.nerfacto_serve"):
        assert reader(name).read(empty) is None
