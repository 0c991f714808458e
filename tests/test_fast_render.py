"""Fused render engine: parity against the `render_rays` fake-quant oracle
across quant specs, occupancy-culling correctness, early-termination
equivalence, and the device-resident PSNR path vs the host-loop original."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.nerf.dataset import make_dataset
from repro.nerf.fast_render import (
    FastRenderEngine,
    build_cull_plan,
    build_fused_pack,
    fast_render_rays,
    fused_ngp_apply,
)
from repro.nerf.hash_encoding import HashEncodingConfig
from repro.nerf.ngp import (
    NGPConfig,
    NGPQuantSpec,
    init_ngp,
    ngp_apply,
    no_quant_spec,
    uniform_quant_spec,
)
from repro.nerf.occupancy import (
    OccupancyGrid,
    bake_occupancy,
    cull_budget,
    occupancy_lookup,
    sample_active_mask,
)
from repro.nerf.render import RenderConfig, render_rays
from repro.nerf.train import TrainConfig, evaluate_psnr, psnr, train_ngp

CFG = NGPConfig(
    hash=HashEncodingConfig(n_levels=4, log2_table_size=9, base_resolution=4,
                            max_resolution=32),
    hidden_dim=16, color_hidden_dim=16, geo_feat_dim=7, sh_degree=2,
)
RCFG = RenderConfig(n_samples=16, stratified=False)


@pytest.fixture(scope="module")
def params():
    p = init_ngp(jax.random.PRNGKey(0), CFG)
    # Freshly-initialized tables sit at +-1e-4 (pure quantization noise);
    # scale to trained-model magnitude so bit widths measure signal.
    p["hash"] = {k: v * 1e3 for k, v in p["hash"].items()}
    return p


@pytest.fixture(scope="module")
def rays():
    key = jax.random.PRNGKey(1)
    n = 24
    ro = jnp.asarray([0.0, 0.0, -1.2]) + 0.05 * jax.random.normal(key, (n, 3))
    rd = jnp.asarray([[0.0, 0.0, 1.0]]) + 0.3 * jax.random.normal(key, (n, 3))
    rd = rd / jnp.linalg.norm(rd, axis=-1, keepdims=True)
    return ro, rd


def _calibrated_spec(params, bits_w, bits_a, bits_h):
    """Spec with activation ranges calibrated from a real forward pass."""
    key = jax.random.PRNGKey(2)
    pts = jax.random.uniform(key, (256, 3))
    dirs = jnp.tile(jnp.asarray([[0.0, 0.0, 1.0]]), (256, 1))
    _, _, taps = ngp_apply(params, pts, dirs, CFG, None, return_taps=True)
    from repro.nerf.ngp import ngp_linear_names

    ranges = jnp.asarray(
        [[float(jnp.min(taps[n])), float(jnp.max(taps[n]))]
         for n in ngp_linear_names(CFG)]
    )
    return NGPQuantSpec(
        hash_bits=jnp.asarray(bits_h, jnp.float32),
        weight_bits=jnp.asarray(bits_w, jnp.float32),
        act_bits=jnp.asarray(bits_a, jnp.float32),
        act_ranges=ranges,
    )


SPECS = {
    "full_precision": lambda p: None,
    "uniform8": lambda p: _calibrated_spec(p, [8] * 5, [8] * 5, [8] * 4),
    "mixed": lambda p: _calibrated_spec(
        p, [8, 4, 32, 6, 8], [6, 8, 8, 32, 4], [8, 6, 4, 32]
    ),
}


@pytest.mark.parametrize("spec_name", sorted(SPECS))
def test_reference_mode_matches_render_rays(params, rays, spec_name):
    """fast_render (reference mode, no culling) == render_rays oracle."""
    spec = SPECS[spec_name](params)
    ro, rd = rays
    want, _ = render_rays(params, ro, rd, CFG, RCFG, spec, None)
    got, _ = fast_render_rays(params, ro, rd, CFG, RCFG, spec, mode="reference")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("spec_name", sorted(SPECS))
def test_fused_mode_matches_fake_quant_oracle(params, rays, spec_name):
    """Integer lowering == fake-quant reference for every spec shape
    (full-precision sentinel, uniform int8, mixed incl. the >=16 band)."""
    spec = SPECS[spec_name](params)
    ro, rd = rays
    want, _ = render_rays(params, ro, rd, CFG, RCFG, spec, None)
    got, _ = fast_render_rays(params, ro, rd, CFG, RCFG, spec, mode="fused")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_fused_int_kernel_path_exact(params):
    """Force the REAL integer kernels (use_pallas=True -> interpret-mode
    Pallas off-TPU): int8 codes + int32 accumulation reproduce the float
    carrier to roundoff."""
    spec = SPECS["uniform8"](params)
    pack = build_fused_pack(params, CFG, spec)
    key = jax.random.PRNGKey(3)
    pts = jax.random.uniform(key, (64, 3))
    dirs = jnp.tile(jnp.asarray([[0.0, 0.0, 1.0]]), (64, 1))
    s_int, rgb_int = fused_ngp_apply(pack, pts, dirs, CFG, use_pallas=True)
    s_ref, rgb_ref = ngp_apply(params, pts, dirs, CFG, spec)
    # Tolerance: the paper-exact 8-bit grid's -129 level clamps to the
    # int8 MXU range (one LSB on the most negative weight codes).
    np.testing.assert_allclose(np.asarray(rgb_int), np.asarray(rgb_ref),
                               atol=1e-2)
    np.testing.assert_allclose(np.asarray(s_int), np.asarray(s_ref),
                               rtol=2e-2, atol=1e-2)


def _masked_oracle(params, ro, rd, grid):
    """Dense render with sigma zeroed in culled cells — the culling spec."""
    n, s = ro.shape[0], RCFG.n_samples
    t = jnp.broadcast_to(jnp.linspace(RCFG.near, RCFG.far, s), (n, s))
    pts = ro[:, None, :] + rd[:, None, :] * t[..., None]
    pts_unit = jnp.clip(pts + 0.5, 0.0, 1.0)
    sigma, rgb = ngp_apply(
        params, pts_unit.reshape(-1, 3),
        jnp.broadcast_to(rd[:, None, :], pts.shape).reshape(-1, 3), CFG, None,
    )
    inside = jnp.all((pts > -0.5) & (pts < 0.5), axis=-1)
    active = inside & occupancy_lookup(grid, pts_unit)
    sigma = jnp.where(active, sigma.reshape(n, s), 0.0)
    from repro.nerf.render import composite

    color, _, _ = composite(sigma, rgb.reshape(n, s, 3), t, RCFG.white_bg)
    return color


def test_culling_matches_masked_oracle(params, rays):
    """Culled samples contribute exactly zero weight: the compacting
    renderer (both the dynamic path and the precomputed CullPlan) equals
    a dense render whose sigma is masked by the same grid."""
    ro, rd = rays
    rng = np.random.RandomState(0)
    occ = OccupancyGrid(
        occ=jnp.asarray((rng.rand(8, 8, 8) < 0.4).astype(np.float32)),
        resolution=8, threshold=0.0, occupied_fraction=0.4,
    )
    want = _masked_oracle(params, ro, rd, occ)

    budget = cull_budget(occ, np.asarray(ro), np.asarray(rd), RCFG,
                         chunk=ro.shape[0])
    got_dyn, _ = fast_render_rays(
        params, ro, rd, CFG, RCFG, None, occ=occ, mode="reference",
        budget=budget,
    )
    np.testing.assert_allclose(np.asarray(got_dyn), np.asarray(want), atol=2e-5)

    plan = build_cull_plan(
        occ, np.asarray(ro)[None], np.asarray(rd)[None], None, RCFG, CFG
    )
    assert plan.budget <= ro.shape[0] * RCFG.n_samples
    got_plan, _ = fast_render_rays(
        params, ro, rd, CFG, RCFG, None, occ=occ, mode="reference", plan=plan,
    )
    np.testing.assert_allclose(np.asarray(got_plan), np.asarray(want), atol=2e-5)


def test_plan_compaction_byte_identical_to_cumsum_fallback(params, rays):
    """The pure-gather CullPlan is a host-precomputed transcript of
    exactly what the dynamic compaction does: over one flattened sample
    population, the staged buffers, validity mask, and masked gather
    reconstruction are byte-identical (assert_array_equal, no
    tolerance). Every path stages its sample depths from the one
    host-side `ray_t_samples` source (the old np-vs-jnp linspace ulp is
    gone), so END-TO-END COLORS are byte-equal too: between the plan
    path and the dynamic path in the fused integer mode the engine
    serves (activation quantization rounds away the one remaining
    divergence — XLA fuses the in-graph `ro + rd*t` into FMAs the host
    baker cannot reproduce, a 1-ulp float residue pinned by the
    reference-mode allclose below)."""
    ro, rd = rays
    rng = np.random.RandomState(7)
    occ = OccupancyGrid(
        occ=jnp.asarray((rng.rand(8, 8, 8) < 0.4).astype(np.float32)),
        resolution=8, threshold=0.0, occupied_fraction=0.4,
    )
    plan = build_cull_plan(
        occ, np.asarray(ro)[None], np.asarray(rd)[None], None, RCFG, CFG
    )
    B = plan.budget

    # The dynamic compaction's buffers (the occ branch of the chunk
    # renderer), replayed as an independent cumsum+scatter over the same
    # host-staged samples the plan was built from.
    active, pts = sample_active_mask(occ, np.asarray(ro), np.asarray(rd),
                                     RCFG)
    flat_active = jnp.asarray(active.reshape(-1))
    flat_pts = jnp.asarray(
        np.clip(pts + 0.5, 0.0, 1.0).reshape(-1, 3).astype(np.float32)
    )
    flat_dirs = jnp.asarray(np.broadcast_to(
        np.asarray(rd, np.float32)[:, None, :], pts.shape
    ).reshape(-1, 3))
    rank = jnp.cumsum(flat_active) - 1
    valid = flat_active & (rank < B)
    pos = jnp.where(valid, rank, B)
    buf_pts = jnp.zeros((B, 3)).at[pos].set(flat_pts, mode="drop")
    buf_dirs = jnp.zeros((B, 3)).at[pos].set(flat_dirs, mode="drop")
    take = jnp.clip(rank, 0, B - 1)

    np.testing.assert_array_equal(np.asarray(plan.buf_pts[0]),
                                  np.asarray(buf_pts))
    np.testing.assert_array_equal(np.asarray(plan.buf_dirs[0]),
                                  np.asarray(buf_dirs))
    np.testing.assert_array_equal(np.asarray(plan.valid[0]),
                                  np.asarray(valid))
    # take differs only on invalid slots (plan parks them at 0, the
    # fallback at the clipped rank) — the masked reconstruction both
    # paths actually use must agree bit-for-bit.
    vals = jax.random.normal(jax.random.PRNGKey(5), (B,))
    rec_plan = jnp.where(plan.valid[0], vals[plan.take[0]], 0.0)
    rec_dyn = jnp.where(valid, vals[take], 0.0)
    np.testing.assert_array_equal(np.asarray(rec_plan), np.asarray(rec_dyn))

    # End-to-end, reference mode: the host-baked plan is 1-ulp off the
    # dynamic path (in-graph FMA), pinned at float roundoff.
    from repro.nerf.fast_render import _frame_colors_impl

    def dyn(pack=None, spec=None, mode="reference"):
        return np.asarray(_frame_colors_impl(
            params, pack, spec, occ, jnp.asarray(ro)[None],
            jnp.asarray(rd)[None], cfg=CFG, rcfg=RCFG, mode=mode,
            budget=B, use_pallas="auto", early_stop=True,
        )[0])

    want_ref, _ = fast_render_rays(
        params, ro, rd, CFG, RCFG, None, occ=occ, mode="reference", plan=plan,
    )
    np.testing.assert_allclose(dyn(), np.asarray(want_ref), atol=1e-6)

    # End-to-end, fused integer mode (what the serve engine runs): the
    # quantizer absorbs the FMA ulp -> plan == dynamic, bitwise.
    spec = SPECS["uniform8"](params)
    pack = build_fused_pack(params, CFG, spec)
    want_fused, _ = fast_render_rays(
        params, ro, rd, CFG, RCFG, spec, occ=occ, mode="fused", pack=pack,
        plan=plan,
    )
    got = dyn(pack=pack, spec=spec, mode="fused")
    np.testing.assert_array_equal(got, np.asarray(want_fused))


def test_empty_grid_renders_background(params, rays):
    """A fully-empty grid culls everything -> pure white background."""
    ro, rd = rays
    empty = OccupancyGrid(occ=jnp.zeros((8, 8, 8)), resolution=8,
                          threshold=0.0, occupied_fraction=0.0)
    color, acc = fast_render_rays(
        params, ro, rd, CFG, RCFG, None, occ=empty, mode="reference",
        budget=128,
    )
    np.testing.assert_allclose(np.asarray(color), 1.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(acc), 0.0, atol=1e-6)


def test_budget_overflow_degrades_gracefully(params, rays):
    """A too-small dynamic budget drops samples but stays finite and keeps
    weights normalized."""
    ro, rd = rays
    dense = OccupancyGrid(occ=jnp.ones((8, 8, 8)), resolution=8,
                          threshold=0.0, occupied_fraction=1.0)
    color, acc = fast_render_rays(
        params, ro, rd, CFG, RCFG, None, occ=dense, mode="reference",
        budget=64,  # << active count
    )
    assert np.all(np.isfinite(np.asarray(color)))
    assert float(jnp.max(acc)) <= 1.0 + 1e-5


def test_early_termination_equivalence():
    """alpha_composite(early_stop=True) == dense scan on saturated rays:
    chunks behind an opaque wall are skipped, numerics unchanged."""
    from repro.kernels import ref
    from repro.kernels.alpha_composite import alpha_composite

    key = jax.random.PRNGKey(4)
    r, s = 20, 64
    sigma = jax.random.uniform(key, (r, s)) * 2.0
    sigma = sigma.at[:, 2].set(1e4)  # opaque wall early on every ray
    rgb = jax.random.uniform(jax.random.PRNGKey(5), (r, s, 3))
    delta = jnp.full((r, s), 0.05)
    c_ref, a_ref = ref.alpha_composite_ref(sigma, rgb, delta)
    # bs=8 -> 8 sample-chunks; all but the first are skippable.
    c_es, a_es = alpha_composite(sigma, rgb, delta, br=8, bs=8,
                                 early_stop=True, interpret=True)
    np.testing.assert_allclose(np.asarray(c_es), np.asarray(c_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(a_es), np.asarray(a_ref), atol=1e-5)
    # Unsaturated random rays: early_stop must be a pure no-op.
    sigma2 = jax.random.uniform(key, (r, s))
    c1, a1 = alpha_composite(sigma2, rgb, delta, br=8, bs=8, early_stop=True,
                             interpret=True)
    c2, a2 = alpha_composite(sigma2, rgb, delta, br=8, bs=8, early_stop=False,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2), atol=1e-6)


# ---------------------------------------------------------------------------
# Trained-scene end-to-end: occupancy bake + full acceptance band.
# Marked slow: these train a scene and render full frames — they run in
# tier-1 (`pytest -q`) but are excluded from the CI fast lane
# (`pytest -q -m "not slow"`).
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def trained():
    ds = make_dataset(SceneConfig_tiny())
    tcfg = TrainConfig(steps=80, batch_rays=256, lr=5e-3)
    params, _ = train_ngp(ds, CFG, RCFG, tcfg)
    return params, ds


def SceneConfig_tiny():
    from repro.nerf.scenes import SceneConfig

    return SceneConfig(name="lego", image_hw=16, n_train_views=4,
                       n_test_views=2)


@pytest.mark.slow
def test_occupancy_bake_shapes_and_monotonicity(trained):
    params, _ = trained
    occ = bake_occupancy(params, CFG, resolution=16, supersample=2, dilate=1)
    assert occ.occ.shape == (16, 16, 16)
    assert 0.0 <= occ.occupied_fraction <= 1.0
    # Dilation can only grow the occupied set.
    raw = bake_occupancy(params, CFG, resolution=16, supersample=2, dilate=0)
    assert occ.occupied_fraction >= raw.occupied_fraction
    # A stricter threshold can only shrink it.
    strict = bake_occupancy(params, CFG, resolution=16, supersample=2,
                            threshold=1e3)
    assert strict.occupied_fraction <= raw.occupied_fraction


@pytest.mark.slow
def test_evaluate_psnr_device_path_matches_host_loop(trained):
    """The device-resident SE accumulation reproduces the old per-chunk
    host-sync loop (satellite: one scalar per view, same numbers)."""
    params, ds = trained
    spec = no_quant_spec(CFG)
    total_se, total_px = 0.0, 0
    for v in range(ds.test_rays_o.shape[0]):
        color, _ = render_rays(
            params, jnp.asarray(ds.test_rays_o[v]),
            jnp.asarray(ds.test_rays_d[v]), CFG, RCFG, spec, None,
        )
        total_se += float(((np.asarray(color) - ds.test_rgb[v]) ** 2).sum())
        total_px += ds.test_rgb[v].size
    want = psnr(total_se / total_px)
    got = evaluate_psnr(params, ds, CFG, RCFG, spec, mode="reference")
    assert abs(got - want) < 1e-2, (got, want)


@pytest.mark.slow
def test_trained_psnr_parity_within_acceptance_band(trained):
    """Fused full-frame PSNR within 0.1 dB of the reference renderer, with
    occupancy culling active (acceptance criterion)."""
    params, ds = trained
    occ = bake_occupancy(params, CFG, resolution=32)
    for bits in (None, 8):
        spec = uniform_quant_spec(CFG, bits) if bits else None
        ref_psnr = evaluate_psnr(params, ds, CFG, RCFG, spec, mode="reference")
        fused = evaluate_psnr(params, ds, CFG, RCFG, spec, occ=occ,
                              mode="fused")
        assert abs(fused - ref_psnr) < 0.1, (bits, fused, ref_psnr)


@pytest.mark.slow
def test_engine_render_frame_matches_render_rays(trained):
    params, ds = trained
    eng = FastRenderEngine(params, CFG, RCFG, mode="reference")
    got = eng.render_frame(ds.test_rays_o[0], ds.test_rays_d[0])
    want, _ = render_rays(
        params, jnp.asarray(ds.test_rays_o[0]), jnp.asarray(ds.test_rays_d[0]),
        CFG, RCFG, None, None,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
