"""Nerfacto (`repro.nerf.nerfacto`): the samplers against a NumPy
transcription of nerfstudio's `PDFSampler`, the contraction, the unit
walk at the published widths, the fused serve programs against the float
forward, and a packed artifact that round-trips through disk and serves
through `hero.serve` exactly what its programs compute."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.ngp import nerfacto as published
from repro.configs.ngp import paper
from repro.nerf import fast_render as fr
from repro.nerf import nerfacto as nf
from repro.nerf.hash_encoding import HashEncodingConfig
from repro.nerf.ngp import NGPConfig
from repro.quant.policy import QuantPolicy, UnitKind

# Small widths of the same shape: main 4 levels T=2^8, proposals 2
# levels T=2^6, 16-wide MLPs, samples 16 -> 8 -> 8.
CFG = nf.NerfactoConfig(
    field=NGPConfig(hash=HashEncodingConfig(n_levels=4, log2_table_size=8,
                                            base_resolution=4,
                                            max_resolution=32),
                    hidden_dim=16, color_hidden_dim=16, geo_feat_dim=15,
                    sh_degree=3),
    proposals=(HashEncodingConfig(n_levels=2, log2_table_size=6,
                                  base_resolution=4, max_resolution=16),
               HashEncodingConfig(n_levels=2, log2_table_size=6,
                                  base_resolution=4, max_resolution=32)),
    proposal_hidden=16, appearance_dim=32, n_images=3, n_initial=16,
    n_resampled=(8, 8))


def random_params(seed, cfg=CFG):
    """Seeded random weights; tables uniform in [-1, 1] so that every
    field varies over the scene (the init's +-1e-4 tables would not)."""
    p = nf.init_nerfacto(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 1000)
    for top in [k for k in p if k.endswith("hash")]:
        for name, t in p[top].items():
            key, sub = jax.random.split(key)
            p[top][name] = jax.random.uniform(sub, t.shape, minval=-1.0,
                                              maxval=1.0)
    return p


def rays(n=64, seed=0):
    """Rays from a camera on the repository's camera sphere toward the
    scene, spread over a cone."""
    rng = np.random.default_rng(seed)
    o = np.tile(np.float32([[0.3, 0.4, 1.2]]), (n, 1))
    d = -o + rng.normal(scale=0.3, size=(n, 3)).astype(np.float32)
    d /= np.linalg.norm(d, axis=-1, keepdims=True)
    return jnp.asarray(o), jnp.asarray(d)


# ---------------------------------------------------------------------------
# Samplers and contraction
# ---------------------------------------------------------------------------
def pdf_sampler_numpy(bins, weights, n, padding):
    """nerfstudio's `PDFSampler.generate_ray_samples` in eval with
    include_original False, transcribed into NumPy (float64)."""
    w = weights + padding
    wsum = w.sum(-1, keepdims=True)
    pad = np.maximum(1e-5 - wsum, 0.0)
    w = w + pad / w.shape[-1]
    wsum = wsum + pad
    cdf = np.minimum(1.0, np.cumsum(w / wsum, -1))
    cdf = np.concatenate([np.zeros_like(cdf[:, :1]), cdf], -1)
    u = np.linspace(0.0, 1.0 - 1.0 / (n + 1), n + 1) + 1.0 / (2 * (n + 1))
    out = np.empty((bins.shape[0], n + 1))
    for r in range(bins.shape[0]):
        inds = np.searchsorted(cdf[r], u, side="right")
        below = np.clip(inds - 1, 0, bins.shape[-1] - 1)
        above = np.clip(inds, 0, bins.shape[-1] - 1)
        c0, c1 = cdf[r][below], cdf[r][above]
        with np.errstate(divide="ignore", invalid="ignore"):
            t = np.clip(np.nan_to_num((u - c0) / (c1 - c0), nan=0.0), 0, 1)
        out[r] = bins[r][below] + t * (bins[r][above] - bins[r][below])
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pdf_resample_matches_numpy_pdf_sampler(seed):
    rng = np.random.default_rng(seed)
    m = 17
    bins = np.sort(rng.uniform(0, 1, (32, m + 1)), -1).astype(np.float32)
    w = (rng.uniform(0, 1, (32, m)) ** 4).astype(np.float32)
    got = np.asarray(nf.pdf_resample(jnp.asarray(bins), jnp.asarray(w), 9, 0.01))
    want = pdf_sampler_numpy(bins.astype(np.float64), w.astype(np.float64), 9,
                             0.01)
    # float32 against float64: ulps of the CDF, over the CDF's rise
    # inside the bin, times the bin's width; steep weights (w ** 4) make
    # that rise small, so a few ulps of [0, 1] become ~3e-6.
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_pdf_resample_zero_weights_give_uniform_samples():
    """All-zero weights with no histogram padding: the 1e-5 padding makes
    the pdf uniform, so the new edges are the u's themselves on [0, 1]."""
    bins = np.linspace(0, 1, 11, dtype=np.float32)[None]
    got = np.asarray(nf.pdf_resample(jnp.asarray(bins),
                                     jnp.zeros((1, 10)), 7, 0.0))[0]
    u = (np.arange(8) + 0.5) / 8
    np.testing.assert_allclose(got, u, atol=1e-6)  # float32 rounding


def test_pdf_resample_one_hot_weight_puts_every_sample_in_its_bin():
    bins = np.linspace(0, 1, 11, dtype=np.float32)[None]
    w = np.zeros((1, 10), np.float32)
    w[0, 6] = 1.0
    got = np.asarray(nf.pdf_resample(jnp.asarray(bins), jnp.asarray(w), 12,
                                     0.0))[0]
    assert np.all((got >= 0.6) & (got <= 0.7)), got
    assert np.all(np.diff(got) > 0)


def test_contraction_identity_inside_bounded_and_continuous():
    x = np.random.default_rng(0).uniform(-1, 1, (1000, 3)).astype(np.float32)
    u, sel = nf.field_coords(jnp.asarray(x))
    # c(x) = x exactly: the coordinates are (x + 2)/4 to the last bit.
    np.testing.assert_array_equal(np.asarray(u), (x + np.float32(2)) / 4)
    assert bool(jnp.all(sel))
    far = np.random.default_rng(1).normal(size=(1000, 3)).astype(np.float32) * 1e4
    u, sel = nf.field_coords(jnp.asarray(far))
    c = np.asarray(u) * 4.0 - 2.0
    assert np.abs(c).max() <= 2.0 and bool(jnp.all(sel))
    # Continuous across |x|_inf = 1 (to float rounding of the step).
    d = np.float32([0.3, -0.7, 1.0])
    lo, hi = d * np.float32(1.0 - 1e-6), d * np.float32(1.0 + 1e-6)
    a, b = (np.asarray(nf.field_coords(jnp.asarray(v[None]))[0])[0]
            for v in (lo, hi))
    np.testing.assert_allclose(a, b, atol=1e-6)


def test_ray_weights_and_losses_by_hand():
    sigma = jnp.asarray([[1.0, 2.0]])
    delta = jnp.asarray([[0.5, 0.25]])
    w = np.asarray(nf.ray_weights(sigma, delta))[0]
    np.testing.assert_allclose(w, [1 - np.exp(-0.5),
                                   (1 - np.exp(-0.5)) * np.exp(-0.5)],
                               rtol=1e-6)
    # One interval of weight 1 and length 0.5: only the intra term,
    # w^2 * len / 3.
    d = nf.distortion_loss(jnp.asarray([[0.25, 0.75]]), jnp.asarray([[1.0]]))
    assert float(d) == pytest.approx(0.5 / 3)
    # Proposal weights that cover the main field's give no interlevel loss;
    # dropping them gives the main weights' sum.
    b = jnp.asarray([[0.0, 0.5, 1.0]])
    wm = jnp.asarray([[0.3, 0.6]])
    assert float(nf.interlevel_loss([b, b], [wm, wm])) == pytest.approx(0.0)
    assert float(nf.interlevel_loss([b, b], [wm * 0, wm])) == pytest.approx(
        0.45, rel=1e-5)


# ---------------------------------------------------------------------------
# Published config and unit walk
# ---------------------------------------------------------------------------
def test_published_config_and_unit_walk():
    cfg = published(n_images=8)
    assert cfg.field == paper() and cfg.field.sh_dim == 16
    # floor(16 b^l): the finest level of proposal 1 floors 127.99.. to 127.
    assert [h.resolutions() for h in cfg.proposals] == [
        [16, 26, 45, 76, 127], [16, 32, 64, 128, 256]]
    assert [[h.is_direct(l) for l in range(5)] for h in cfg.proposals] == [
        [True, True, True, False, False], [True, True, False, False, False]]
    assert cfg.proposal_samples_per_ray == 352
    assert cfg.shade_samples_per_ray == 48
    units = nf.make_quant_units(cfg)
    hashes = [u.name for u in units if u.kind == UnitKind.HASH_LEVEL]
    assert len(hashes) == 26
    assert hashes[15:17] == ["hash/level_15", "prop1/hash/level_0"]
    assert hashes[-1] == "prop2/hash/level_4"
    linears = [u.name[:-2] for u in units if u.kind == UnitKind.WEIGHT]
    assert linears == ["sigma/0", "sigma/1", "color/0", "color/1", "color/2",
                       "prop1/0", "prop1/1", "prop2/0", "prop2/1"]
    dims = nf.linear_dims(cfg)
    assert dims["color/0"] == (63, 64)  # 16 SH + 15 geometry + 32 appearance
    assert dims["prop1/0"] == (10, 16) and dims["prop2/1"] == (16, 1)
    assert [u.index for u in units] == list(range(len(units)))


# ---------------------------------------------------------------------------
# Fused serve programs
# ---------------------------------------------------------------------------
def programs(pack, o, d, cfg=CFG):
    edges = fr._slot_propose_impl(pack, o, d, cfg=cfg, use_pallas="auto")
    return edges, fr._slot_shade_impl(pack, o, d, edges, cfg=cfg,
                                      use_pallas="auto", early_stop=True)


def float_pack(params, cfg=CFG):
    units = nf.make_quant_units(cfg)
    ranges = jnp.tile(jnp.asarray([[0.0, 1.0]]), (9, 1))
    spec = nf.spec_from_policy(cfg, QuantPolicy.uniform(units, 32), ranges)
    return fr.build_nerfacto_pack(params, cfg, spec)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_full_precision_programs_match_float_forward(seed):
    p = random_params(seed)
    o, d = rays(seed=seed)
    want, bins, _ = nf.render_rays(p, o, d, CFG, nf.serve_appearance(p))
    edges, got = programs(float_pack(p), o, d)
    # The same float32 operations in two programs: fusion moves the last
    # bits, and far edges (up to t = 1000) carry ulps of 6e-5.
    np.testing.assert_allclose(np.asarray(edges),
                               np.asarray(nf.spacing_to_euclidean(bins[-1], CFG)),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def quantized_artifact(seed):
    from repro.hero import QuantArtifact

    p = random_params(seed)
    units = nf.make_quant_units(CFG)
    bits = [8, 6, 5, 4, 8, 6, 8, 5] + [8, 6, 8, 8, 6, 5, 6, 4, 8, 8] + [8] * 8
    ranges = jnp.asarray([[-2.0, 2.0]] * 9)
    spec = nf.spec_from_policy(
        CFG, QuantPolicy.uniform(units, 8).with_bits(bits), ranges)
    spec = nf.NerfactoQuantSpec(
        main=dataclasses.replace(spec.main, paper_exact=False),
        proposals=tuple(dataclasses.replace(s, paper_exact=False)
                        for s in spec.proposals))
    return QuantArtifact(
        scene="chair", bits=bits, cfg=CFG, rcfg=None, scene_cfg={}, params=p,
        act_ranges=ranges, pack=fr.build_nerfacto_pack(p, CFG, spec),
        occ=None, hardware={}, metrics={})


def test_artifact_round_trip_keeps_codes_and_serves_exactly(tmp_path):
    from repro import hero
    from repro.hero import QuantArtifact, ServeConfig
    from repro.quant.packing import PackedTensor

    art = quantized_artifact(3)
    back = QuantArtifact.load(art.save(tmp_path / "a"))
    assert back.cfg == CFG and back.occ is None and back.proposal_sampled
    assert back.stored_model_bytes() == art.stored_model_bytes()
    for (f, a), (g, b) in zip(art.pack.fields(), back.pack.fields()):
        assert f == g and a.modes == b.modes
        for name, t in a.hash_tables.items():
            u = b.hash_tables[name]
            assert isinstance(u, PackedTensor) and u.bits == t.bits
            np.testing.assert_array_equal(np.asarray(u.words),
                                          np.asarray(t.words))
        for name, lyr in a.layers.items():
            np.testing.assert_array_equal(np.asarray(b.layers[name]["wq"].words),
                                          np.asarray(lyr["wq"].words))
    np.testing.assert_array_equal(np.asarray(back.pack.appearance),
                                  np.asarray(art.pack.appearance))
    # Only the main field has a direct-indexed level (res 4 at T=2^8):
    # its cell-major table is staged again at load.
    assert [("cells_cat" in p.compute) for _, p in back.pack.fields()] == \
        [True, False, False]
    np.testing.assert_array_equal(
        np.asarray(back.pack.main.compute["cells_cat"]),
        np.asarray(art.pack.main.compute["cells_cat"]))

    o, d = rays(n=700, seed=3)
    _, want = programs(art.pack, o, d)
    svc = hero.serve(back, ServeConfig(slots=2, slot_rays=128))
    got = svc.render(np.asarray(o), np.asarray(d))
    # Bit for bit, up to the slot split: XLA:CPU may round a row's float
    # arithmetic differently where it sits in another vector lane.
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-6)
    s = svc.stats()
    slot_points = 6 * 128  # 700 rays in 6 slots of 128, 3 steps of 2
    assert s["trace"]["counters"] == {
        "render.proposal_samples": 700 * 24, "render.shade_samples": 700 * 8,
        "hash.lookups": slot_points * (16 * 16 + 8 * 16 + 8 * 32),
        "hash.cell_lookups": slot_points * 8 * 8, "render.fetches": 3}
    spans = s["trace"]["spans"]
    assert "pose.key" not in spans
    assert spans["render.dispatch"]["count"] == 2 * spans["render.slot"]["count"]
    assert s["budget_retraces"] == 0 and s["sample_budget"] is None


def test_engine_counts_hash_lookups_per_proposal_slot(monkeypatch):
    """Zero-render: with both slot programs stubbed, a proposal slot
    counts each field's corner lookups at the points it is queried at
    (every ray of the slot x the field's samples), 8 a level, and the
    part the main field's one direct-indexed level serves from cell-major
    rows; the proposal fields have none."""
    from repro import hero
    from repro.hero import ServeConfig

    art = quantized_artifact(0)
    assert [sum(h.is_direct(l) for l in range(h.n_levels))
            for h in (CFG.field.hash,) + CFG.proposals] == [1, 0, 0]
    monkeypatch.setattr(fr, "_slot_propose_impl", lambda pack, o, d, **kw: o)
    monkeypatch.setattr(fr, "_slot_shade_impl",
                        lambda pack, o, d, edges, **kw: jnp.zeros_like(o))
    svc = hero.serve(art, ServeConfig(slots=2, slot_rays=64))
    svc.render(*map(np.asarray, rays(n=150)))  # 3 slots
    c = svc.stats()["trace"]["counters"]
    per_slot = 64 * (16 * 2 * 8 + 8 * 2 * 8 + 8 * 4 * 8)
    assert c["hash.lookups"] == 3 * per_slot
    assert c["hash.cell_lookups"] == 3 * 64 * 8 * 1 * 8


def test_engine_dispatches_every_proposal_slot_before_one_fetch(monkeypatch):
    """Zero-render: with both slot programs stubbed to record each
    dispatch and the fetch wrapped to record each fetch, every step
    dispatches both programs of each of its slots before its one fetch,
    and `render.fetches` counts one a step."""
    from repro import hero
    from repro.hero import ServeConfig

    log = []

    def propose(pack, o, d, **kw):
        log.append("propose")
        return o

    def shade(pack, o, d, edges, **kw):
        log.append("shade")
        return jnp.zeros_like(o)

    monkeypatch.setattr(fr, "_slot_propose_impl", propose)
    monkeypatch.setattr(fr, "_slot_shade_impl", shade)
    svc = hero.serve(quantized_artifact(0), ServeConfig(slots=2, slot_rays=64))
    stepper = svc.engine._stepper
    fetch = stepper._fetch

    def logged_fetch(outputs):
        log.append("fetch")
        return fetch(outputs)

    monkeypatch.setattr(stepper, "_fetch", logged_fetch)
    log.clear()  # `hero.serve` warms the engine up
    svc.render(*map(np.asarray, rays(n=150)))  # 3 slots: steps of 2 and 1
    P, S, F = "propose", "shade", "fetch"
    assert log == [P, S, P, S, F, P, S, F]
    s = svc.engine.stats()
    assert s["trace"]["counters"]["render.fetches"] == s["device_steps"] == 2
