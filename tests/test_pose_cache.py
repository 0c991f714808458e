"""Pose-grid plan cache: quantization, conservativeness, LRU policy, and
the serve engine's hit/warp/march tier progression.

The load-bearing property (pinned here both host-side and end-to-end):
a plan built with coverage margin `m` never culls a sample that the
exact plan of ANY rays within `m` L-inf deviation would keep — so the
warp tier's colors are byte-identical to the march tier's, and every
tier sits inside the 1e-3 dB PSNR band of an engine without the cache.
"""
import numpy as np
import pytest

from _hypothesis_shim import given, settings, st
from repro.nerf.hash_encoding import HashEncodingConfig
from repro.nerf.ngp import NGPConfig
from repro.nerf.occupancy import OccupancyGrid, sample_active_mask
from repro.nerf.pose_cache import (
    PoseGridConfig,
    PosePlanCache,
    build_warp_plan,
    pose_cell_key,
    ray_fingerprint,
    warp_deviation,
)
from repro.nerf.render import RenderConfig

RCFG = RenderConfig(n_samples=8, stratified=False)


def _occ(g=8, frac=0.4, seed=7):
    rng = np.random.RandomState(seed)
    import jax.numpy as jnp

    return OccupancyGrid(
        occ=jnp.asarray((rng.rand(g, g, g) < frac).astype(np.float32)),
        resolution=g, threshold=0.0, occupied_fraction=frac,
    )


def _rays(n=8, seed=0):
    rng = np.random.RandomState(seed)
    ro = rng.uniform(-0.35, 0.35, size=(n, 3)).astype(np.float32)
    rd = rng.normal(size=(n, 3)).astype(np.float32)
    rd /= np.linalg.norm(rd, axis=-1, keepdims=True)
    return ro, rd


# ---------------------------------------------------------------------------
# Pose-cell quantization + fingerprints + deviation bound
# ---------------------------------------------------------------------------
def test_pose_cell_key_deterministic_and_shift_sensitive():
    ro, rd = _rays()
    k1 = pose_cell_key(ro, rd, 0.05, 0.05)
    k2 = pose_cell_key(ro.copy(), rd.copy(), 0.05, 0.05)
    assert k1 == k2 and len(k1) == 9
    assert all(isinstance(v, int) for v in k1)
    # A full-cell translation always changes the position part.
    k3 = pose_cell_key(ro + np.float32(0.05), rd, 0.05, 0.05)
    assert k3[:3] != k1[:3] and k3[3:] == k1[3:]
    # Reshaped (H, W, 3) bundles key identically to flat (N, 3).
    k4 = pose_cell_key(ro.reshape(2, 4, 3), rd.reshape(2, 4, 3), 0.05, 0.05)
    assert k4 == k1


def test_ray_fingerprint_content_hash():
    ro, rd = _rays()
    assert ray_fingerprint(ro, rd) == ray_fingerprint(ro.copy(), rd.copy())
    ro2 = ro.copy()
    ro2[3, 1] += np.float32(1e-6)
    assert ray_fingerprint(ro2, rd) != ray_fingerprint(ro, rd)


def test_warp_deviation_bound_and_shape_mismatch():
    ro, rd = _rays()
    assert warp_deviation(ro, rd, ro, rd, RCFG) == 0.0
    got = warp_deviation(ro + np.float32(0.01), rd, ro, rd, RCFG)
    assert abs(got - 0.01) < 1e-6
    # Direction deviation scales by t_far = max(|near|, |far|).
    rd2 = rd.copy()
    rd2[0, 0] += np.float32(0.002)
    got = warp_deviation(ro, rd2, ro, rd, RCFG)
    assert abs(got - 0.002 * max(abs(RCFG.near), abs(RCFG.far))) < 1e-6
    assert warp_deviation(ro[:4], rd[:4], ro, rd, RCFG) == float("inf")


# ---------------------------------------------------------------------------
# Conservativeness: the margin-m mask covers the exact mask of any rays
# within m L-inf — the property that makes warped plans safe to reuse.
# ---------------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=10_000),
    frac=st.floats(min_value=0.05, max_value=0.95),
)
def test_warp_margin_mask_is_superset_of_jittered_exact(seed, frac):
    rng = np.random.RandomState(seed)
    occ = _occ(g=8, frac=frac, seed=seed)
    ro, rd = _rays(n=8, seed=seed + 1)
    margin = PoseGridConfig().margin(occ)  # 1 occ cell in world units

    cons, _ = sample_active_mask(occ, ro, rd, RCFG, margin=margin)
    t_far = max(abs(RCFG.near), abs(RCFG.far))
    # Split the deviation budget between origin and direction jitter so
    # d_o + t_far * d_d <= margin (the warp_deviation admission test).
    d_o = margin * 0.5
    d_d = (margin * 0.5) / t_far
    ro_j = ro + rng.uniform(-d_o, d_o, ro.shape).astype(np.float32)
    rd_j = rd + rng.uniform(-d_d, d_d, rd.shape).astype(np.float32)
    assert warp_deviation(ro_j, rd_j, ro, rd, RCFG) <= margin + 1e-6

    exact_j, _ = sample_active_mask(occ, ro_j, rd_j, RCFG)
    assert np.all(cons | ~exact_j), (
        "conservative mask culled a sample the jittered exact mask keeps"
    )


def test_build_warp_plan_invariants():
    cfg = NGPConfig(
        hash=HashEncodingConfig(n_levels=4, log2_table_size=9,
                                base_resolution=4, max_resolution=32),
        hidden_dim=16, color_hidden_dim=16, geo_feat_dim=7, sh_degree=2,
    )
    occ = _occ()
    ro, rd = _rays(n=16, seed=3)
    margin = 1.0 / occ.resolution
    plan = build_warp_plan(occ, ro, rd, RCFG, cfg, margin)

    P = ro.shape[0] * RCFG.n_samples
    cons = np.asarray(plan.valid_cons)
    exact = np.asarray(plan.plan_row[3])
    assert cons.shape == exact.shape == (P,)
    assert np.all(cons | ~exact)  # conservative superset of exact
    # take/inv_take round-trip on every conservative-active sample.
    take = np.asarray(plan.take)
    inv = np.asarray(plan.inv_take)
    idx = np.nonzero(cons)[0]
    assert plan.budget % 128 == 0 and plan.budget >= idx.size
    np.testing.assert_array_equal(inv[take[idx]], idx)
    assert plan.fp == ray_fingerprint(ro, rd)
    assert plan.nbytes > 0
    L = cfg.hash.n_levels
    assert np.asarray(plan.plan_row[4]).shape == (L, plan.budget, 8)


# ---------------------------------------------------------------------------
# PosePlanCache policy: LRU, pin-aware eviction, drop_scene, stats
# ---------------------------------------------------------------------------
def test_pose_cache_lru_and_use_counts():
    c = PosePlanCache(max_entries=2)
    a, b, d = ("s", 1), ("s", 2), ("s", 3)
    assert c.note_use(a).uses == 1
    assert c.note_use(a).uses == 2
    c.note_use(b)
    c.note_use(a)  # a is MRU
    c.note_use(d)  # capacity 2 -> b (LRU) evicted
    assert c.get(b) is None and c.get(a) is not None and c.get(d) is not None
    assert c.stats()["evictions"] == 1
    assert len(c) == 2


def test_pose_cache_never_evicts_pinned():
    c = PosePlanCache(max_entries=1)
    a, b, d = ("s", 1), ("s", 2), ("s", 3)
    c.note_use(a)
    c.pin(a)
    c.note_use(b)  # a pinned: cache runs over capacity, b evicts nothing
    assert c.get(a) is not None
    c.note_use(d)  # b unpinned and LRU -> evicted
    assert c.get(b) is None and c.get(a) is not None
    c.pin(a)  # pins are counted
    c.unpin(a)
    assert c.pinned(a)
    c.unpin(a)
    assert not c.pinned(a)
    c.note_use(("s", 4))
    c.note_use(("s", 5))
    assert c.get(a) is None  # unpinned: evictable again


def test_pose_cache_drop_scene_removes_even_pinned():
    c = PosePlanCache(max_entries=8)
    c.note_use(("a", 1))
    c.note_use(("a", 2))
    c.note_use(("b", 1))
    c.pin(("a", 1))
    assert c.drop_scene("a") == 2
    assert c.get(("a", 1)) is None and c.get(("b", 1)) is not None
    assert c.stats()["cells"] == 1


def test_pose_cache_stats_shape():
    c = PosePlanCache(max_entries=4)
    got = c.stats()
    assert set(got) == {"cells", "bytes", "hits", "warps", "misses",
                        "builds", "evictions"}
    assert all(v == 0 for v in got.values())


# ---------------------------------------------------------------------------
# Engine integration: the real tiers on a real (tiny) quantized scene
# ---------------------------------------------------------------------------
from repro.core import SceneScale, build_scene_env  # noqa: E402

TINY = SceneScale.tiny()
HW = 12  # 144 rays/request -> 3 items at slot_rays=64


@pytest.fixture(scope="module")
def tiny_artifact():
    import repro.hero as hero

    env = build_scene_env("chair", TINY, seed=0)
    rng = np.random.RandomState(3)
    bits = rng.randint(4, 9, size=env.n_units).tolist()
    return hero.compile(env, bits)


def _orbit(theta, height, hw=HW):
    import jax.numpy as jnp

    from repro.nerf.scenes import camera_rays

    c, s = np.cos(theta), np.sin(theta)
    c2w = np.asarray(
        [[c, 0.0, -s, 2.0 * s], [0.0, 1.0, 0.0, height],
         [s, 0.0, c, 2.0 * c]], np.float32,
    )
    ro, rd = camera_rays(jnp.asarray(c2w), hw, hw * 1.2)
    return np.asarray(ro).reshape(-1, 3), np.asarray(rd).reshape(-1, 3)


def _engine(artifact, **over):
    from repro.hero.engine import ServeEngine
    from repro.hero.scheduler import EngineConfig

    cfg = EngineConfig(slots=4, slot_rays=64, **over)
    return ServeEngine({artifact.scene: artifact}, cfg)


def _psnr(a, b):
    se = float(((a - b) ** 2).mean())
    return float(-10.0 * np.log10(max(se, 1e-12)))


def test_engine_tier_progression_and_parity(tiny_artifact):
    """One pose revisited: miss -> miss+build -> hit; in-cell jitter ->
    warp. Every tier's colors are byte-identical to an engine without the
    pose cache (its every slot marches, with no plans) on the same rays,
    PSNR deltas pinned 0."""
    scene = tiny_artifact.scene
    eng = _engine(tiny_artifact)
    eng_plain = _engine(tiny_artifact, pose_cache=False)
    stepper = eng._stepper
    # Height 0.11 sits mid-cell (pos_cell 0.05): jitter can't straddle.
    ro, rd = _orbit(0.3, 0.11)

    march = eng.render(ro, rd, scene=scene)  # visit 1: miss, no build
    s1 = dict(stepper.pose_stats())
    assert s1["misses"] == 3 and s1["builds"] == 0 and s1["cells"] == 1

    ref = eng_plain.render(ro, rd, scene=scene)
    np.testing.assert_array_equal(march, ref)

    again = eng.render(ro, rd, scene=scene)  # visit 2: miss + build
    s2 = dict(stepper.pose_stats())
    assert s2["builds"] == 3 and s2["hits"] == 0 and s2["bytes"] > 0
    np.testing.assert_array_equal(again, march)

    hit = eng.render(ro, rd, scene=scene)  # visit 3: every item hits
    s3 = dict(stepper.pose_stats())
    assert s3["hits"] == 3 and s3["builds"] == 3
    np.testing.assert_array_equal(hit, march)

    # Warp: jitter within the cell AND the coverage margin. Retry signs
    # and scales — a pose component can sit on a quantization boundary.
    key0 = stepper.pose_key(scene, ro, rd)
    warped = None
    for eps in (1e-4, -1e-4, 5e-5, -5e-5):
        ro_j = ro + np.float32(eps)
        if stepper.pose_key(scene, ro_j, rd) != key0:
            continue
        before = stepper.pose_stats()["warps"]
        got = eng.render(ro_j, rd, scene=scene)
        if stepper.pose_stats()["warps"] == before:
            continue
        warped = (ro_j, got)
        break
    assert warped is not None, "no jitter landed in the warp tier"
    ro_j, warp = warped
    ref_j = eng_plain.render(ro_j, rd, scene=scene)
    np.testing.assert_array_equal(warp, ref_j)
    assert abs(_psnr(warp, ref) - _psnr(ref_j, ref)) <= 1e-3  # dB band


def test_engine_plan_bytes_charged_to_resident(tiny_artifact):
    scene = tiny_artifact.scene
    eng = _engine(tiny_artifact)
    ro, rd = _orbit(1.1, 0.16)
    base = eng.stats()["cache"]["resident_bytes"]
    eng.render(ro, rd, scene=scene)
    eng.render(ro, rd, scene=scene)  # second visit bakes plans
    st = eng.stats()
    plan_bytes = st["pose_cache"]["bytes"]
    assert plan_bytes > 0
    assert st["cache"]["resident_bytes"] == base + plan_bytes


def test_engine_pose_cache_off_disables_tiers(tiny_artifact):
    ro, rd = _orbit(2.0, 0.21)
    eng = _engine(tiny_artifact, pose_cache=False)
    eng.render(ro, rd, scene=tiny_artifact.scene)
    assert eng.stats()["pose_cache"] is None


def test_engine_fresh_poses_build_nothing(tiny_artifact):
    """Never-revisited poses stay in the march tier: zero plan builds,
    zero bytes — the fresh-stream fast path costs no baking."""
    scene = tiny_artifact.scene
    eng = _engine(tiny_artifact)
    for theta in (0.4, 1.3, 2.2, 3.1):
        eng.render(*_orbit(theta, 0.13), scene=scene)
    st = eng.stats()["pose_cache"]
    assert st["builds"] == 0 and st["bytes"] == 0 and st["hits"] == 0
    assert st["cells"] == 4 and st["misses"] == 12


def test_engine_tier_counters_and_spans(tiny_artifact):
    """The engine's spans name each slot's work: two visits march (the
    second bakes plans), the third hits; each visit is one step of 3
    slots, which fetches once; only march slots count their active
    samples."""
    scene = tiny_artifact.scene
    eng = _engine(tiny_artifact)
    ro, rd = _orbit(0.7, 0.12)
    eng.reset_stats()
    for _ in range(3):
        eng.render(ro, rd, scene=scene)
    tr = eng.stats()["trace"]
    c, sp = tr["counters"], tr["spans"]
    pc = eng.stats()["pose_cache"]
    assert (pc["misses"], pc["hits"], pc["warps"]) == (6, 3, 0)
    assert sp["pose.build"]["count"] == 3 and sp["pose.key"]["count"] == 3
    assert sp["render.slot"]["count"] == 9
    assert (sp["render.wait"]["count"] == c["render.fetches"]
            == eng.stats()["device_steps"] == 3)
    assert c["render.budget_samples"] == 6 * eng.budget
    assert c["render.active_samples"] <= c["render.budget_samples"]


def test_engine_without_budget_counts_no_samples(tiny_artifact):
    """With no sample budget the march slots fetch only their colors, all
    3 slots of the one step in one fetch, and the fill counters, which
    need a budget, stay empty."""
    scene = tiny_artifact.scene
    eng = _engine(tiny_artifact, budget=None, pose_cache=False)
    ro, rd = _orbit(0.7, 0.12)
    eng.reset_stats()
    eng.render(ro, rd, scene=scene)
    tr = eng.stats()["trace"]
    assert eng.budget is None
    assert tr["spans"]["render.wait"]["count"] == 1
    assert set(tr["counters"]) == {"hash.lookups", "hash.cell_lookups",
                                   "render.fetches"}
    assert tr["counters"]["render.fetches"] == 1


@pytest.mark.parametrize("budget", ["auto", None])
def test_engine_counts_hash_lookups_per_march_slot(tiny_artifact, monkeypatch,
                                                   budget):
    """Zero-render: with the march program stubbed, each march slot counts
    the corner lookups of its field query, 8 a level at each point the
    field is queried at (the sample budget, or every sample without one),
    and the part its one direct-indexed level serves from cell-major
    rows."""
    import jax.numpy as jnp

    from repro.nerf import fast_render as fr

    monkeypatch.setattr(
        fr, "_slot_march_impl",
        lambda params, pack, spec, occ, ro, rd, **kw: (
            jnp.zeros_like(ro), jnp.int32(0)))
    eng = _engine(tiny_artifact, budget=budget, pose_cache=False)
    eng.reset_stats()
    eng.render(*_orbit(0.7, 0.12), scene=tiny_artifact.scene)  # 3 slots
    c = eng.stats()["trace"]["counters"]
    hcfg = tiny_artifact.cfg.hash
    assert [hcfg.is_direct(l) for l in range(4)] == [True, False, False, False]
    points = eng.budget or 64 * tiny_artifact.rcfg.n_samples
    assert c["hash.lookups"] == 3 * points * 4 * 8
    assert c["hash.cell_lookups"] == 3 * points * 1 * 8


def _visit(eng, ro, rd):
    """One request's colors through `eng`, and the pose-cache tier counts
    it moved."""
    before = dict(eng._stepper.pose_stats())
    got = eng.render(ro, rd, scene=eng.scenes[0])
    after = eng._stepper.pose_stats()
    return got, {k: after[k] - before[k] for k in ("misses", "hits", "warps")}


def _slot_alone(stepper, art, tier, ro, rd, plan=None):
    """One slot rendered by itself through its tier's program and fetched
    at once."""
    import jax.numpy as jnp

    from repro.nerf import fast_render as fr

    st = stepper._state[art.scene]
    kw = dict(cfg=art.cfg, rcfg=st["rcfg"], mode="fused",
              use_pallas=stepper.cfg.use_pallas,
              early_stop=stepper.cfg.early_stop)
    args = (art.params, art.pack, st["spec"], art.occ,
            jnp.asarray(ro), jnp.asarray(rd))
    if tier == "march":
        return np.asarray(
            fr._slot_march_impl(*args, budget=st["budget"], **kw)[0])
    if tier == "hit":
        return np.asarray(fr._slot_plan_impl(*args, plan.plan_row, **kw))
    return np.asarray(fr._slot_warp_impl(
        *args, plan.inv_take, plan.take, plan.valid_cons, **kw))


@pytest.mark.parametrize("tier", ["march", "hit", "warp"])
def test_engine_full_bucket_matches_slots_rendered_alone(tiny_artifact,
                                                         tier):
    """A full bucket of 4 slots of one tier, dispatched together and
    fetched once, serves byte for byte what each slot serves rendered by
    itself through its tier's program and fetched at once. The pose and
    the tier progression are `test_engine_tier_progression_and_parity`'s:
    the first visit marches, the second bakes plans, the third hits, an
    in-cell jitter warps."""
    scene = tiny_artifact.scene
    eng = _engine(tiny_artifact)
    stepper = eng._stepper
    ro, rd = _orbit(0.3, 0.11, hw=16)  # 256 rays: 4 full slots, one step
    if tier != "march":
        _visit(eng, ro, rd)
        _visit(eng, ro, rd)
    got = None
    if tier == "warp":
        key0 = stepper.pose_key(scene, ro, rd)
        for eps in (1e-4, -1e-4, 5e-5, -5e-5):
            ro_j = ro + np.float32(eps)
            if stepper.pose_key(scene, ro_j, rd) != key0:
                continue
            got, moved = _visit(eng, ro_j, rd)
            if moved["warps"] == 4:
                ro = ro_j
                break
            got = None
        assert got is not None, "no jitter put every slot in the warp tier"
    else:
        got, moved = _visit(eng, ro, rd)
        assert moved[{"march": "misses", "hit": "hits"}[tier]] == 4
    plans = (stepper._pose_cache.get(stepper.pose_key(scene, ro, rd)).plans
             if tier != "march" else {})
    for k in range(4):
        s = slice(64 * k, 64 * (k + 1))
        alone = _slot_alone(stepper, tiny_artifact, tier, ro[s], rd[s],
                            plans.get(k))
        np.testing.assert_array_equal(got[s], alone)


def _bucket_rays(rng):
    """Four 64-ray slots that start inside the scene box. In each slot
    the share `corner` of the rays leaves a corner of the box along its
    diagonal (about 4.4 of a ray's 8 samples active); the rest leave its
    center in random directions (about 1.8 active)."""
    ro, rd = [], []
    for corner in (0.25, 1.0, 0.0, 0.5):
        k = int(64 * corner)
        o = np.zeros((64, 3))
        d = rng.normal(size=(64, 3))
        o[:k] = -0.45 + rng.uniform(-0.03, 0.03, (k, 3))
        d[:k] = 1.0 + rng.uniform(-0.2, 0.2, (k, 3))
        ro.append(o)
        rd.append(d / np.linalg.norm(d, axis=1, keepdims=True))
    return (np.concatenate(ro).astype(np.float32),
            np.concatenate(rd).astype(np.float32))


def test_engine_overflowing_slots_of_one_step_regrow_and_rerender(
        tiny_artifact):
    """An undersized budget (128 samples) on one step whose slots need
    more: every slot over the budget it ran at re-renders with a fetch of
    its own, the budget grows by the engine's rule only where a slot
    overflows what an earlier slot of the step grew it to (each growth
    one retrace), and the colors equal those of an engine with no
    budget."""
    import jax.numpy as jnp

    from repro.nerf.fast_render import _slot_march_impl

    art, scene = tiny_artifact, tiny_artifact.scene
    ro, rd = _bucket_rays(np.random.RandomState(0))
    eng = _engine(art, budget=128, pose_cache=False)
    plain = _engine(art, budget=None, pose_cache=False)
    st = eng._stepper._scene_state(scene, art)
    needs = [int(_slot_march_impl(
        art.params, art.pack, st["spec"], art.occ,
        jnp.asarray(ro[64 * k:64 * (k + 1)]), jnp.asarray(rd[64 * k:64 * (k + 1)]),
        cfg=art.cfg, rcfg=st["rcfg"], mode="fused", budget=None,
        use_pallas=eng.cfg.use_pallas, early_stop=eng.cfg.early_stop)[1])
        for k in range(4)]
    budget, growths = 128, 0
    for n in needs:  # the growth rule, slot by slot
        if n > budget:
            grown = np.ceil(max(n * eng.cfg.budget_headroom, n) / 128) * 128
            budget, growths = min(int(grown), 64 * st["rcfg"].n_samples), growths + 1
    overflows = sum(n > 128 for n in needs)
    assert overflows >= 3 and growths >= 2 and overflows > growths

    eng.reset_stats()
    got = eng.render(ro, rd, scene=scene)
    np.testing.assert_array_equal(got, plain.render(ro, rd, scene=scene))
    s = eng.stats()
    c = s["trace"]["counters"]
    assert s["budget_retraces"] == growths and eng.budget == budget
    assert (c["render.fetches"] == s["trace"]["spans"]["render.wait"]["count"]
            == s["device_steps"] + overflows)
    assert c["render.active_samples"] == sum(needs)
    assert c["render.active_samples"] <= c["render.budget_samples"]


def test_engine_dispatches_every_slot_before_one_fetch(tiny_artifact,
                                                       monkeypatch):
    """Zero-render: with the march program stubbed to record each dispatch
    and the fetch wrapped to record each fetch, every step dispatches all
    its slots before its one fetch. The 2nd and 3rd slots of the first
    step overflow the budget they ran at: each then re-renders and
    fetches on its own, and the budget grows once, since the 3rd fits
    what the 2nd grew it to."""
    import jax.numpy as jnp

    from repro.nerf import fast_render as fr

    log, calls = [], []

    def march(params, pack, spec, occ, ro, rd, *, budget, **kw):
        log.append("dispatch")
        calls.append(budget)
        need = budget + 1 if len(calls) in (2, 3) else 0
        return jnp.zeros_like(ro), jnp.int32(need)

    monkeypatch.setattr(fr, "_slot_march_impl", march)
    eng = _engine(tiny_artifact, budget=128, pose_cache=False)
    stepper = eng._stepper
    fetch = stepper._fetch

    def logged_fetch(outputs):
        log.append("fetch")
        return fetch(outputs)

    monkeypatch.setattr(stepper, "_fetch", logged_fetch)
    scene = tiny_artifact.scene
    eng.submit(*_orbit(0.7, 0.12, hw=16), scene=scene)  # 4 slots
    eng.submit(*_orbit(0.7, 0.12), scene=scene)  # 3 slots
    eng.reset_stats()
    eng.drain()
    D, F = "dispatch", "fetch"
    assert log == [D, D, D, D, F, D, F, D, F, D, D, D, F]
    assert calls == [128] * 4 + [256] * 5
    s = eng.stats()
    assert s["device_steps"] == 2 and s["budget_retraces"] == 1
    assert s["trace"]["counters"]["render.fetches"] == 2 + 2
