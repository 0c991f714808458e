"""Public `repro.hero` API: hardware-target plugins, deployable
QuantArtifacts (round-trip parity), and the batched render service.

The headline acceptance pin: `hero.compile` -> save -> load -> serve
produces the IDENTICAL PSNR (0.0000 dB at the reported precision) as the
in-process fused render path on the quick/tiny scene.
"""
import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

import repro.hero as hero
from repro.core import SceneScale, build_scene_env
from repro.core.closed_loop import ClosedLoopConfig, HeroSearchRun
from repro.hero.service import ServeConfig
from repro.hero.targets import NeuRexTarget, RooflineTarget
from repro.hwsim import HWConfig, build_trace
from repro.nerf.hash_encoding import HashEncodingConfig
from repro.nerf.ngp import NGPConfig
from repro.nerf.render import RenderConfig

TINY = SceneScale.tiny()


@pytest.fixture(scope="module")
def tiny_env():
    """One tiny trained scene env shared by the artifact/service tests."""
    return build_scene_env("chair", TINY, seed=0)


@pytest.fixture(scope="module")
def tiny_artifact(tiny_env):
    rng = np.random.RandomState(3)
    bits = rng.randint(4, 9, size=tiny_env.n_units).tolist()
    return hero.compile(tiny_env, bits)


# ---------------------------------------------------------------------------
# Hardware-target protocol + registry
# ---------------------------------------------------------------------------
def _tiny_trace():
    cfg = NGPConfig(
        hash=HashEncodingConfig(n_levels=4, log2_table_size=9,
                                base_resolution=4, max_resolution=32),
        hidden_dim=16, color_hidden_dim=16, geo_feat_dim=7, sh_degree=2,
    )
    rcfg = RenderConfig(n_samples=8, stratified=False)
    rng = np.random.RandomState(0)
    ro = rng.uniform(-0.4, 0.4, size=(32, 3)).astype(np.float32)
    rd = rng.normal(size=(32, 3)).astype(np.float32)
    rd /= np.linalg.norm(rd, axis=-1, keepdims=True)
    return cfg, rcfg, ro, rd


def test_registry_lists_builtin_targets():
    names = hero.list_targets()
    for want in ("neurex", "neurex-edge", "neurex-cloud", "roofline-edge"):
        assert want in names
    for name in names:
        t = hero.make_target(name, coarse_levels=2)
        assert isinstance(t, hero.HardwareTarget)
        assert t.describe()["name"] == name
    with pytest.raises(KeyError):
        hero.make_target("warp-drive")
    # Typo'd overrides must raise, not silently configure defaults —
    # only the documented cross-family knob (coarse_levels) is ignored
    # by families that lack the concept.
    with pytest.raises(TypeError):
        hero.make_target("roofline-edge", mac_lanez=999)
    with pytest.raises(TypeError):
        hero.make_target("neurex", grid_cache_kbb=1)


def test_register_custom_target_roundtrips():
    # The natural third-party factory: takes NO cross-family knobs.
    hero.register_target(
        "test-custom", lambda: RooflineTarget(name="test-custom"),
        "test-only",
    )
    try:
        t = hero.resolve_target("test-custom")
        assert t.name == "test-custom"
        # An instance resolves to itself (overrides ignored).
        assert hero.resolve_target(t) is t
        # The generic scene-builder path pushes coarse_levels at every
        # target; make_target strips it for factories lacking the knob...
        assert hero.make_target("test-custom", coarse_levels=2).name == \
            "test-custom"
        # ... but a genuine typo still raises.
        with pytest.raises(TypeError):
            hero.make_target("test-custom", coarse_levelz=2)
    finally:
        from repro.hero.targets import _TARGET_REGISTRY
        _TARGET_REGISTRY.pop("test-custom")


@pytest.mark.parametrize("name", ["neurex-edge", "neurex-cloud", "roofline-edge"])
def test_targets_simulate_and_batch_consistently(name):
    """Every built-in target: scalar == batched numbers, monotone in bits,
    and edge hardware slower than cloud on the same workload."""
    cfg, rcfg, ro, rd = _tiny_trace()
    t = hero.make_target(name, coarse_levels=2)
    trace = t.build_workload(cfg, rcfg, ro, rd)
    kw = dict(n_features=cfg.hash.n_features, resolutions=cfg.hash.resolutions())

    eight = t.baseline(trace, 8, **kw)
    four = t.baseline(trace, 4, **kw)
    assert four.total_cycles < eight.total_cycles
    assert four.model_bytes < eight.model_bytes

    bsim = t.batched(trace, **kw)
    L, M = cfg.hash.n_levels, 5
    hb = np.stack([np.full(L, 8.0), np.full(L, 4.0)])
    wb = np.stack([np.full(M, 8.0), np.full(M, 4.0)])
    out = bsim.simulate_batch(hb, wb, wb)
    assert out["total_cycles"][0] == pytest.approx(eight.total_cycles, rel=1e-4)
    assert out["total_cycles"][1] == pytest.approx(four.total_cycles, rel=1e-4)
    assert out["model_bytes"][0] == pytest.approx(eight.model_bytes, rel=1e-5)

    vfn = bsim.vmappable()
    if vfn is not None:  # shard-safe form must agree with the batched one
        one = {k: float(v) for k, v in vfn(hb[0], wb[0], wb[0]).items()}
        assert one["total_cycles"] == pytest.approx(eight.total_cycles, rel=1e-4)


def test_edge_slower_than_cloud():
    cfg, rcfg, ro, rd = _tiny_trace()
    kw = dict(n_features=cfg.hash.n_features, resolutions=cfg.hash.resolutions())
    edge = hero.make_target("neurex-edge", coarse_levels=2)
    cloud = hero.make_target("neurex-cloud", coarse_levels=2)
    trace = edge.build_workload(cfg, rcfg, ro, rd)
    assert (
        edge.baseline(trace, 8, **kw).total_cycles
        > cloud.baseline(trace, 8, **kw).total_cycles
    )


def test_env_layer_has_no_direct_neurex_construction():
    """Acceptance pin: the search stack takes hardware by injection only."""
    root = Path(__file__).resolve().parent.parent / "src" / "repro" / "core"
    for fname in ("env.py", "batched_env.py", "closed_loop.py"):
        source = (root / fname).read_text()
        assert "NeuRexSimulator(" not in source, (
            f"core/{fname} constructs NeuRexSimulator directly; inject a "
            "HardwareTarget instead"
        )


def test_env_rejects_target_and_hw_cfg_together(tiny_env):
    with pytest.raises(ValueError, match="not both"):
        from repro.core import EnvConfig, NGPQuantEnv

        NGPQuantEnv(
            tiny_env.params, tiny_env.dataset, tiny_env.cfg, tiny_env.rcfg,
            tiny_env.tcfg, EnvConfig(), hw_cfg=HWConfig(),
            target=NeuRexTarget(),
        )


def test_env_sim_alias_for_neurex_family(tiny_env):
    # Legacy alias resolves for the NeuRex default ...
    assert tiny_env.sim is tiny_env.target.sim


# ---------------------------------------------------------------------------
# Non-NeuRex target through the full closed loop
# ---------------------------------------------------------------------------
def test_roofline_target_runs_full_closed_loop(tmp_path):
    cfg = ClosedLoopConfig(
        scenes=("chair",),
        budget_fracs=(1.0, 0.9),
        seed=0,
        scale=TINY,
        n_iterations=2,
        population=4,
        sharded=False,
        checkpoint_path=str(tmp_path / "ckpt.json"),
        verbose=False,
        hardware="roofline-edge",
    )
    result = HeroSearchRun(cfg).run()
    assert len(result.cells) == 2
    assert result.policies_evaluated > 0
    assert len(result.frontier) >= 1
    # The target actually used is the roofline (no NeuRex scalar sim).
    run = HeroSearchRun(cfg)
    env = run.bundle("chair").env
    assert isinstance(env.target, RooflineTarget)
    with pytest.raises(AttributeError, match="no scalar"):
        env.sim
    # The checkpoint fingerprint records the hardware name.
    state = json.loads((tmp_path / "ckpt.json").read_text())
    assert state["config"]["hardware"] == "roofline-edge"


def test_injected_target_instances_fingerprint_by_config():
    """Two differently-configured injected instances must not share a
    checkpoint identity (their latency axes are incomparable), and an
    instance never fingerprints like the by-name default."""
    cfg = ClosedLoopConfig(scale=TINY, verbose=False)
    by_name = HeroSearchRun(cfg)._fingerprint()
    slow = HeroSearchRun(
        cfg, target=NeuRexTarget(HWConfig(dram_peak_gbps=1.0))
    )._fingerprint()
    fast = HeroSearchRun(
        cfg, target=NeuRexTarget(HWConfig(dram_peak_gbps=100.0))
    )._fingerprint()
    assert slow != fast
    assert slow != by_name
    # Same config -> same identity (resume works for equal instances).
    slow2 = HeroSearchRun(
        cfg, target=NeuRexTarget(HWConfig(dram_peak_gbps=1.0))
    )._fingerprint()
    assert slow == slow2


# ---------------------------------------------------------------------------
# set_latency_target deprecation shim
# ---------------------------------------------------------------------------
def test_set_latency_target_deprecated_but_functional(tiny_env):
    before = tiny_env.ecfg.latency_target
    try:
        with pytest.warns(DeprecationWarning, match="set_latency_target"):
            tiny_env.set_latency_target(1e9)
        assert tiny_env.ecfg.latency_target == 1e9
        # The deprecated env default still feeds the enforcement path...
        bits_env = tiny_env.enforce_latency_target([8] * tiny_env.n_units)
        # ... and the call-state route gives the same answer.
        bits_call = tiny_env.enforce_latency_target(
            [8] * tiny_env.n_units, target=1e9
        )
        assert bits_env == bits_call
    finally:
        tiny_env.ecfg = dataclasses.replace(
            tiny_env.ecfg, latency_target=before
        )


# ---------------------------------------------------------------------------
# QuantArtifact: compile -> save -> load -> serve parity
# ---------------------------------------------------------------------------
def test_artifact_roundtrip_identical_psnr(tiny_env, tiny_artifact, tmp_path):
    """save -> load reproduces the in-process fused PSNR EXACTLY."""
    ds = tiny_env.dataset
    psnr_inproc = tiny_artifact.engine().evaluate_psnr(ds)
    # compile recorded the same number (same engine path).
    assert psnr_inproc == pytest.approx(tiny_artifact.metrics["psnr"], abs=1e-9)

    tiny_artifact.save(tmp_path / "art")
    loaded = hero.QuantArtifact.load(tmp_path / "art")
    assert loaded.bits == tiny_artifact.bits
    assert loaded.scene == tiny_artifact.scene
    assert loaded.cfg == tiny_artifact.cfg
    assert loaded.hardware == tiny_artifact.hardware
    # Packed integer code words survive bit-for-bit (weights AND tables).
    def assert_same(v, got):
        from repro.quant.packing import PackedTensor

        if isinstance(v, PackedTensor):
            assert isinstance(got, PackedTensor)
            assert (v.bits, v.shape) == (got.bits, got.shape)
            for f in ("words", "scale", "offset"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(v, f)), np.asarray(getattr(got, f))
                )
        else:
            np.testing.assert_array_equal(np.asarray(v), np.asarray(got))

    for name, lyr in tiny_artifact.pack.layers.items():
        for k, v in lyr.items():
            assert_same(v, loaded.pack.layers[name][k])
    for name, t in tiny_artifact.pack.hash_tables.items():
        assert_same(t, loaded.pack.hash_tables[name])
    assert loaded.pack.modes == tiny_artifact.pack.modes

    psnr_loaded = loaded.engine().evaluate_psnr(ds)
    assert psnr_loaded == psnr_inproc  # 0.0000 dB delta, exactly


def test_tile_repack_invisible_on_disk(tiny_artifact, tmp_path):
    """Tentpole storage pin: the tile-native compute layout NEVER reaches
    disk. A tile-layout load re-saves byte-identical arrays (same sha256
    set, same npz contents) — storage stays schema-v2 planar."""
    p1 = tiny_artifact.save(tmp_path / "a")
    loaded = hero.QuantArtifact.load(p1)  # default: tile-native compute
    assert loaded.pack.layout.startswith("tile:")
    assert loaded.pack.compute  # staged tile words / dequant carriers
    # Derived compute is resident cost, not storage truth.
    lean = dataclasses.replace(
        loaded, pack=dataclasses.replace(loaded.pack, compute={})
    )
    assert loaded.resident_bytes() > lean.resident_bytes()
    assert loaded.stored_model_bytes() == tiny_artifact.stored_model_bytes()

    p2 = loaded.save(tmp_path / "b")
    m1 = json.loads((p1 / "manifest.json").read_text())["arrays"]
    m2 = json.loads((p2 / "manifest.json").read_text())["arrays"]
    assert {k: v["sha256"] for k, v in m1.items()} == \
           {k: v["sha256"] for k, v in m2.items()}
    with np.load(p1 / "arrays.npz") as z1, np.load(p2 / "arrays.npz") as z2:
        assert sorted(z1.files) == sorted(z2.files)
        for k in z1.files:
            np.testing.assert_array_equal(z1[k], z2[k])


def test_load_restages_the_cell_major_table(tiny_artifact, tmp_path):
    """The cell-major table of the direct-indexed levels is staged at
    load as at compile, from the stored tables and the manifest's config,
    and never reaches disk: the stored bytes are unchanged."""
    from repro.nerf.fast_render import fused_pack_stored_bytes

    hcfg = tiny_artifact.cfg.hash
    assert [hcfg.is_direct(l) for l in range(4)] == [True, False, False, False]
    loaded = hero.QuantArtifact.load(tiny_artifact.save(tmp_path / "a"))
    assert loaded.pack.hash_cfg == hcfg
    want = tiny_artifact.pack.compute["cells_cat"]
    assert want.shape == (5 ** 3, 8 * 2)
    np.testing.assert_array_equal(np.asarray(loaded.pack.compute["cells_cat"]),
                                  np.asarray(want))
    assert fused_pack_stored_bytes(loaded.pack) == \
        fused_pack_stored_bytes(tiny_artifact.pack)


def test_load_stages_every_compute_form_and_serves_identically(
        tiny_env, tiny_artifact, tmp_path):
    """A loaded artifact holds every compute form the fused field query
    reads — the concatenated table, the cell-major table where a level
    is direct-indexed, and the tile-native words and float carrier of
    each packed layer — equal to the compiled artifact's, and evaluates
    the same PSNR."""
    from repro.kernels.repack import DEFAULT_TILE_BK
    from repro.quant.packing import PackedTensor

    loaded = hero.QuantArtifact.load(tiny_artifact.save(tmp_path / "art"))
    pack, hcfg = loaded.pack, loaded.cfg.hash
    assert pack.layout == f"tile:{DEFAULT_TILE_BK}"
    want = {"table_cat"}
    if any(hcfg.is_direct(l) for l in range(hcfg.n_levels)):
        want.add("cells_cat")
    packed = [n for n, lyr in pack.layers.items() if "wq" in lyr]
    assert packed
    for name in packed:
        want |= {f"{name}::wq_tile", f"{name}::w_f32"}
    assert set(pack.compute) == want
    for k, v in tiny_artifact.pack.compute.items():
        got = pack.compute[k]
        if isinstance(v, PackedTensor):
            assert got.layout == v.layout
            v, got = v.words, got.words
        np.testing.assert_array_equal(np.asarray(got), np.asarray(v))
    ds = tiny_env.dataset
    assert loaded.engine().evaluate_psnr(ds) == \
        tiny_artifact.engine().evaluate_psnr(ds)


def test_artifact_integrity_check_fails_loudly(tiny_artifact, tmp_path):
    path = tiny_artifact.save(tmp_path / "art")
    manifest = json.loads((path / "manifest.json").read_text())
    some_key = next(iter(manifest["arrays"]))
    manifest["arrays"][some_key]["sha256"] = "0" * 16
    (path / "manifest.json").write_text(json.dumps(manifest))
    with pytest.raises(ValueError, match="integrity"):
        hero.QuantArtifact.load(path)

    manifest["schema_version"] = 99
    (path / "manifest.json").write_text(json.dumps(manifest))
    with pytest.raises(ValueError, match="schema_version"):
        hero.QuantArtifact.load(path)


def test_serve_matches_in_process_fused_path(tiny_env, tiny_artifact, tmp_path):
    """The acceptance pin: compile -> save -> load -> serve == the
    in-process fused render path, 0.0000 dB PSNR delta."""
    ds = tiny_env.dataset
    psnr_inproc = tiny_artifact.engine().evaluate_psnr(ds)

    tiny_artifact.save(tmp_path / "art")
    svc = hero.serve(
        hero.QuantArtifact.load(tmp_path / "art"),
        ServeConfig(slots=2, slot_rays=64),
    )
    se, px = 0.0, 0
    rids = [
        svc.submit(ds.test_rays_o[v], ds.test_rays_d[v])
        for v in range(ds.test_rays_o.shape[0])
    ]
    svc.drain()
    for v, rid in enumerate(rids):
        colors = svc.result(rid)
        gt = ds.test_rgb[v].reshape(-1, 3)
        se += float(((colors - gt) ** 2).sum())
        px += gt.size
    psnr_serve = -10.0 * np.log10(max(se / px, 1e-12))
    assert round(psnr_serve, 4) == round(psnr_inproc, 4)  # 0.0000 dB delta

    stats = svc.stats()
    assert stats["requests_completed"] == len(rids)
    assert stats["rays_rendered"] == px // 3
    assert stats["latency_ms"]["p95"] >= stats["latency_ms"]["p50"]


def test_service_slot_recycling_and_budget_growth(tiny_artifact):
    """Requests larger than one slot split into items, the queue drains
    across steps, and an underestimated budget grows instead of dropping
    samples."""
    ds_rays = 40
    rng = np.random.RandomState(7)
    ro = rng.uniform(-0.3, 0.3, size=(ds_rays, 3)).astype(np.float32)
    rd = rng.normal(size=(ds_rays, 3)).astype(np.float32)
    rd /= np.linalg.norm(rd, axis=-1, keepdims=True)

    svc = hero.serve(
        tiny_artifact, ServeConfig(slots=2, slot_rays=16, budget=128),
        warmup=False,
    )
    rid = svc.submit(ro, rd)
    assert svc.pending == 3  # ceil(40 / 16) work items
    svc.drain()
    out = svc.result(rid)
    assert out.shape == (ds_rays, 3)
    assert np.all(np.isfinite(out))

    # Same rays through the exact (uncapped) path must agree: the budget
    # either sufficed or grew — never silently dropped samples.
    exact = hero.serve(
        tiny_artifact, ServeConfig(slots=2, slot_rays=16, budget=None),
        warmup=False,
    ).render(ro, rd)
    np.testing.assert_allclose(out, exact, atol=1e-6)

    with pytest.raises(ValueError, match="not complete"):
        svc.submit(ro, rd)
        svc.result(rid + 1)


def test_service_budget_grows_instead_of_dropping(tiny_artifact):
    """A deliberately undersized budget must retrace to a bigger one, not
    silently drop in-box samples."""
    n = 64
    # Axis-aligned rays whose early samples all sit inside the scene box:
    # the active count per slot deterministically exceeds the tiny budget.
    ro = np.tile(np.asarray([[-0.4, 0.0, 0.0]], np.float32), (n, 1))
    rd = np.tile(np.asarray([[1.0, 0.0, 0.0]], np.float32), (n, 1))

    svc = hero.serve(
        tiny_artifact, ServeConfig(slots=1, slot_rays=n, budget=128),
        warmup=False,
    )
    out = svc.render(ro, rd)
    assert svc.retraces >= 1
    assert svc.budget > 128

    exact = hero.serve(
        tiny_artifact, ServeConfig(slots=1, slot_rays=n, budget=None),
        warmup=False,
    ).render(ro, rd)
    np.testing.assert_allclose(out, exact, atol=1e-6)


def test_service_result_frees_request_state(tiny_artifact):
    """The `_requests` leak regression: a long-lived service must not
    retain completed requests after retrieval. `result()` frees the
    buffer (second call raises), while throughput stats keep counting
    through the bounded completed ring."""
    svc = hero.serve(
        tiny_artifact,
        ServeConfig(slots=1, slot_rays=16, completed_ring=8),
        warmup=False,
    )
    rng = np.random.RandomState(13)
    for i in range(12):
        ro = rng.uniform(-0.3, 0.3, size=(4, 3)).astype(np.float32)
        rd = rng.normal(size=(4, 3)).astype(np.float32)
        rd /= np.linalg.norm(rd, axis=-1, keepdims=True)
        rid = svc.submit(ro, rd)
        svc.drain()
        assert svc.result(rid).shape == (4, 3)
        with pytest.raises(KeyError, match="already retrieved"):
            svc.result(rid)
    assert len(svc.engine._requests) == 0  # nothing retained
    assert len(svc.engine._ring) == 8  # ring bounded at completed_ring
    stats = svc.stats()
    assert stats["requests_completed"] == 12  # counters saw every request
    assert stats["requests_pending"] == 0
    assert stats["latency_ms"]["p95"] is not None


# ---------------------------------------------------------------------------
# Multi-scene engine: mixed-stream parity with the synchronous service
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_artifact_lego():
    """A second tiny scene so the engine tests mix two artifacts."""
    env = build_scene_env("lego", TINY, seed=1)
    rng = np.random.RandomState(5)
    bits = rng.randint(4, 9, size=env.n_units).tolist()
    return hero.compile(env, bits)


def test_engine_mixed_scene_stream_byte_identical_to_sync_service(
    tiny_artifact, tiny_artifact_lego
):
    """Acceptance pin: an interleaved 2-scene request stream through the
    multi-scene engine produces BYTE-IDENTICAL colors (0.0000 dB) to
    draining each scene through its own synchronous RenderService.

    Both paths use the same explicit static budget (`None` = uncapped,
    retrace-free), so the device computation is the same jitted function
    over the same per-slot inputs — co-batching across requests and
    scenes must not change a single bit of any request's output.
    """
    arts = {a.scene: a for a in (tiny_artifact, tiny_artifact_lego)}
    cfg = ServeConfig(slots=2, slot_rays=32, budget=None)
    eng = hero.serve(arts, cfg)  # -> multi-scene ServeEngine
    assert sorted(eng.resident_scenes) == ["chair", "lego"]

    rng = np.random.RandomState(11)
    reqs = []
    for i in range(6):  # chair/lego interleaved, ragged sizes
        scene = ("chair", "lego")[i % 2]
        n = (40, 17, 64)[i % 3]
        ro = rng.uniform(-0.4, 0.4, size=(n, 3)).astype(np.float32)
        rd = rng.normal(size=(n, 3)).astype(np.float32)
        rd /= np.linalg.norm(rd, axis=-1, keepdims=True)
        reqs.append((eng.submit(ro, rd, scene=scene), scene, ro, rd))
    eng.drain()

    sync = {s: hero.serve(a, cfg, warmup=False) for s, a in arts.items()}
    for rid, scene, ro, rd in reqs:
        got = eng.result(rid)
        want = sync[scene].render(ro, rd)
        np.testing.assert_array_equal(got, want)  # byte-identical

    stats = eng.stats()
    assert stats["requests_completed"] == len(reqs)
    assert sorted(stats["scenes"]) == ["chair", "lego"]
    assert stats["cache"]["resident"] and stats["cache"]["evictions"] == 0


def test_engine_lru_cache_serves_from_loader(tiny_artifact, tmp_path):
    """`hero.serve` with no resident artifacts + a loader: requests for a
    non-resident scene load on miss and render correctly end to end."""
    path = tiny_artifact.save(tmp_path / "art")
    loads = []

    def loader(scene):
        assert scene == tiny_artifact.scene
        loads.append(scene)
        return hero.QuantArtifact.load(path)

    eng = hero.serve(
        {}, ServeConfig(slots=2, slot_rays=32, budget=None),
        loader=loader, warmup=False,
    )
    rng = np.random.RandomState(17)
    ro = rng.uniform(-0.4, 0.4, size=(20, 3)).astype(np.float32)
    rd = rng.normal(size=(20, 3)).astype(np.float32)
    rd /= np.linalg.norm(rd, axis=-1, keepdims=True)
    got = eng.render(ro, rd, scene=tiny_artifact.scene)
    assert loads == [tiny_artifact.scene]  # loaded exactly once
    want = hero.serve(
        tiny_artifact, ServeConfig(slots=2, slot_rays=32, budget=None),
        warmup=False,
    ).render(ro, rd)
    np.testing.assert_array_equal(got, want)
    assert eng.stats()["cache"]["loads"] == 1
    assert eng.stats()["cache"]["resident_bytes"] > 0  # real payload size


# ---------------------------------------------------------------------------
# model_bytes exactness: frontier objective == stored payload == disk bytes
# ---------------------------------------------------------------------------
def test_model_bytes_exact_from_search_to_disk(tiny_env, tmp_path):
    """Acceptance pin: for a mixed 4-bit-MLP / 6-bit-hash policy, the
    simulator's model_bytes (the frontier objective), the compiled
    artifact's metric, the in-memory pack payload, and the bytes actually
    sitting in arrays.npz are ONE number."""
    from repro.hero.artifact import _SEP
    from repro.quant.policy import QuantPolicy

    bits = [6 if u.name.startswith("hash/") else 4 for u in tiny_env.units]
    art = hero.compile(tiny_env, bits)

    policy = QuantPolicy.uniform(tiny_env.units, 8).with_bits(bits)
    lat = tiny_env.simulate_policy(policy)
    assert art.metrics["model_bytes"] == lat.model_bytes
    assert art.metrics["model_bytes"] == art.stored_model_bytes()

    # The batched evaluator (what the closed loop's frontier consumes)
    # lands on the same number.
    from repro.core.batched_env import BatchedQuantEnv

    benv = BatchedQuantEnv(tiny_env)
    sim = benv.simulate_batch(np.asarray([bits], np.int32))
    assert float(sim["model_bytes"][0]) == art.metrics["model_bytes"]

    # And the number is what the directory physically holds.
    path = art.save(tmp_path / "art")
    disk = 0
    with np.load(path / "arrays.npz") as z:
        for k in z.files:
            parts = k.split(_SEP)
            if parts[-2:] == ["pt", "words"]:
                disk += z[k].nbytes  # packed weight/table words
            elif parts[0] == "pack" and parts[-1] == "w":
                disk += z[k].nbytes  # f32 weight carrier (>8-bit units)
            elif parts[0] == "packtab" and "pt" not in parts:
                disk += z[k].nbytes  # f32 table carrier (>8-bit levels)
    assert disk == art.stored_model_bytes()

    # Sub-byte is real: the payload beats one-byte-per-code int8 storage
    # (4/6-bit codes pack to 0.5x/0.75x of an int8 store).
    from repro.quant.packing import PackedTensor

    int8_store = sum(
        int(np.prod(v.shape))
        for lyr in art.pack.layers.values()
        for v in lyr.values()
        if isinstance(v, PackedTensor)
    ) + sum(
        int(np.prod(t.shape))
        for t in art.pack.hash_tables.values()
        if isinstance(t, PackedTensor)
    )
    assert disk < 0.8 * int8_store


# ---------------------------------------------------------------------------
# Schema v1 -> v2 auto-upgrade
# ---------------------------------------------------------------------------
def _write_v1_dir(artifact, path):
    """Materialize the legacy schema-1 layout (int8 weight codes + f32
    w_deq carrier + float-carrier hash tables) from a v2 artifact, with a
    valid v1 manifest — the format PR 4 shipped."""
    from repro.hero.artifact import _SEP, _sha
    from repro.quant.packing import PackedTensor

    arrays = {"act_ranges": np.asarray(artifact.act_ranges)}
    for top, sub in artifact.params.items():
        for k, v in sub.items():
            arrays[f"params{_SEP}{top}{_SEP}{k}"] = np.asarray(v)
    for name, lyr in artifact.pack.layers.items():
        for k, v in lyr.items():
            if isinstance(v, PackedTensor):
                arrays[f"pack{_SEP}{name}{_SEP}w_codes"] = np.clip(
                    np.asarray(v.codes()), -128, 127
                ).astype(np.int8)
                arrays[f"pack{_SEP}{name}{_SEP}w_deq"] = np.asarray(
                    v.dequantize()
                )
                arrays[f"pack{_SEP}{name}{_SEP}sw"] = np.asarray(v.scale)
            else:
                arrays[f"pack{_SEP}{name}{_SEP}{k}"] = np.asarray(v)
    for name, t in artifact.pack.hash_tables.items():
        tt = t.dequantize() if isinstance(t, PackedTensor) else t
        arrays[f"packtab{_SEP}{name}"] = np.asarray(tt)
    arrays["occ"] = np.asarray(artifact.occ.occ)

    manifest = {
        "schema_version": 1,
        "scene": artifact.scene,
        "bits": [int(b) for b in artifact.bits],
        "cfg": dataclasses.asdict(artifact.cfg),
        "rcfg": dataclasses.asdict(artifact.rcfg),
        "scene_cfg": artifact.scene_cfg,
        "pack_modes": list(artifact.pack.modes),
        "occ": {
            "resolution": artifact.occ.resolution,
            "threshold": artifact.occ.threshold,
            "occupied_fraction": artifact.occ.occupied_fraction,
        },
        "hardware": artifact.hardware,
        "metrics": artifact.metrics,
        "arrays": {
            k: {"shape": list(v.shape), "dtype": str(v.dtype),
                "sha256": _sha(v)}
            for k, v in arrays.items()
        },
    }
    path = Path(path)
    path.mkdir(parents=True, exist_ok=True)
    with open(path / "arrays.npz", "wb") as f:
        np.savez(f, **arrays)
    (path / "manifest.json").write_text(json.dumps(manifest, indent=2))
    return path


def test_v1_artifact_auto_upgrades_and_serves_identically(
    tiny_env, tiny_artifact, tmp_path
):
    """Loading a v1 directory re-packs through the deterministic
    `build_fused_pack` path: identical PSNR to the v2 compile of the same
    params, measured model_bytes, and re-saving writes schema v2."""
    _write_v1_dir(tiny_artifact, tmp_path / "v1")
    loaded = hero.QuantArtifact.load(tmp_path / "v1")
    assert loaded.schema_version == 2
    assert loaded.metrics["model_bytes"] == loaded.stored_model_bytes()

    ds = tiny_env.dataset
    psnr_v1 = loaded.engine().evaluate_psnr(ds)
    psnr_v2 = tiny_artifact.engine().evaluate_psnr(ds)
    assert psnr_v1 == psnr_v2  # 0.0000 dB, exactly

    loaded.save(tmp_path / "resaved")
    manifest = json.loads((tmp_path / "resaved" / "manifest.json").read_text())
    assert manifest["schema_version"] == 2
    again = hero.QuantArtifact.load(tmp_path / "resaved")
    assert again.engine().evaluate_psnr(ds) == psnr_v2


def test_v1_artifact_corrupted_sha_still_refuses(tiny_artifact, tmp_path):
    """Integrity runs BEFORE the v1 upgrade path: a corrupted array fails
    loudly, never silently re-packs."""
    path = _write_v1_dir(tiny_artifact, tmp_path / "v1")
    manifest = json.loads((path / "manifest.json").read_text())
    some_key = next(k for k in manifest["arrays"] if k.startswith("params"))
    manifest["arrays"][some_key]["sha256"] = "f" * 16
    (path / "manifest.json").write_text(json.dumps(manifest))
    with pytest.raises(ValueError, match="integrity"):
        hero.QuantArtifact.load(path)


def test_facade_best_bits_and_compile_accepts_bundle(tiny_env):
    from repro.core.closed_loop import CellResult, ClosedLoopResult
    from repro.core.pareto import ParetoFrontier

    cells = [
        CellResult("chair", 1.0, 1e9, 0.5, [8] * tiny_env.n_units, 4, 1, 1.0),
        CellResult("chair", 0.85, 9e8, 0.9, [6] * tiny_env.n_units, 4, 1, 1.0),
    ]
    result = ClosedLoopResult(
        frontier=ParetoFrontier(), scene_frontiers={}, cells=cells,
        policies_evaluated=8, search_seconds=2.0, wall_seconds=3.0,
        resumed_cells=0, seconds_to_fixed_bit=None, fixed_bit_reference=6,
    )
    scene, bits = hero.best_bits(result)
    assert scene == "chair" and bits == [6] * tiny_env.n_units
    with pytest.raises(ValueError):
        hero.best_bits(result, scene="lego")
