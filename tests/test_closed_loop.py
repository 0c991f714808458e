"""Closed-loop search: batched-vs-scalar parity oracle, seeded
determinism + checkpoint/resume, shared occupancy bake, and the sharded
population evaluator (single-device parity here; a forced two-device
subprocess pins the multi-device path)."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BatchedEnvConfig,
    BatchedQuantEnv,
    ClosedLoopConfig,
    EnvConfig,
    HeroSearchRun,
    NGPQuantEnv,
    SceneScale,
    build_scene_bundle,
)
from repro.core.reward import hero_reward
from repro.hwsim import HWConfig, NeuRexSimulator
from repro.nerf.fast_render import fast_render_rays
from repro.nerf.ngp import NGPQuantSpec
from repro.nerf.occupancy import (
    bake_occupancy_cached,
    occupancy_registry_size,
)
from repro.quant.policy import QuantPolicy

TINY = SceneScale.tiny()


@pytest.fixture(scope="module")
def bundles():
    """Two tiny scene bundles shared by every test in this module (and by
    every HeroSearchRun below — envs are never mutated by a run)."""
    return {
        "chair": build_scene_bundle("chair", TINY, seed=0),
        "lego": build_scene_bundle("lego", TINY, seed=1),
    }


# ---------------------------------------------------------------------------
# Parity: batched population rewards vs K sequential scalar evaluations
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scene", ["chair", "lego"])
def test_batched_rewards_match_sequential_scalar_oracle(bundles, scene):
    """`evaluate_population` == K independent scalar evaluations (float64
    numpy simulator + one-policy-at-a-time proxy render + Eq. 8), so the
    sharded path always has a sequential oracle to fall back on."""
    bundle = bundles[scene]
    env, benv = bundle.env, bundle.benv
    K = 5
    rng = np.random.RandomState(11)
    bits = rng.randint(env.ecfg.b_min, env.ecfg.b_max + 1,
                       size=(K, env.n_units))

    ev = benv.evaluate_population(bits)

    oracle_sim = NeuRexSimulator(env.sim.cfg, backend="numpy")
    hb, wb, ab = benv.bits_to_arrays(bits)
    rcfg = dataclasses.replace(env.rcfg, stratified=False)
    ro, rd, gt = benv._proxy_rays
    for i in range(K):
        ref = oracle_sim.simulate(
            env.trace, hb[i], wb[i], ab[i],
            n_features=env.cfg.hash.n_features,
            resolutions=env.cfg.hash.resolutions(),
        )
        assert ev.latency_cycles[i] == pytest.approx(
            ref.total_cycles, rel=1e-3
        )
        assert ev.model_bytes[i] == pytest.approx(ref.model_bytes, rel=1e-3)

        # Scalar (non-vmapped) proxy render of the same fixed ray subset.
        spec = NGPQuantSpec(
            hash_bits=jnp.asarray(hb[i]), weight_bits=jnp.asarray(wb[i]),
            act_bits=jnp.asarray(ab[i]), act_ranges=env.act_ranges,
        )
        color, _ = fast_render_rays(
            env.params, ro, rd, env.cfg, rcfg, spec, occ=env.occ,
            mode="reference", plan=benv._proxy_plan,
        )
        mse = max(float(jnp.mean((color - gt) ** 2)), 1e-12)
        psnr_i = -10.0 * np.log10(mse)
        assert ev.psnr[i] == pytest.approx(psnr_i, abs=1e-3)

        want_reward = hero_reward(
            psnr_i, benv.psnr_org_proxy, float(ev.latency_cycles[i]),
            env.original_cost, lam=env.ecfg.lam,
        )
        assert ev.reward[i] == pytest.approx(want_reward, abs=1e-3)


def test_budget_as_call_state_across_two_budgets(bundles):
    """The same env scores under two hardware budgets without mutation:
    enforcement honors the per-call target and the batched feasibility
    mask agrees with the scalar simulator."""
    env = bundles["chair"].env
    benv = bundles["chair"].benv
    before = env.ecfg
    bits0 = [8] * env.n_units
    for frac in (0.9, 0.7):
        target = env.original_cost * frac
        enforced = env.enforce_latency_target(list(bits0), target=target)
        lat = env.simulate_policy(
            QuantPolicy.uniform(env.units, 8).with_bits(enforced)
        ).total_cycles
        assert lat <= target * (1 + 1e-6)
        ev = benv.evaluate_population([enforced], latency_target=target)
        assert ev.feasible is not None and bool(ev.feasible[0])
    assert env.ecfg is before  # env untouched by per-call budgets


# ---------------------------------------------------------------------------
# Seeded determinism + checkpoint/resume
# ---------------------------------------------------------------------------
def _cl_cfg(**kw):
    base = dict(
        scenes=("chair", "lego"), budget_fracs=(1.0, 0.8), seed=7,
        scale=TINY, n_iterations=2, population=6, verbose=False,
    )
    base.update(kw)
    return ClosedLoopConfig(**base)


def test_closed_loop_deterministic_given_seed(bundles):
    cfg = _cl_cfg()
    res_a = HeroSearchRun(cfg, bundles).run()
    res_b = HeroSearchRun(cfg, bundles).run()
    assert res_a.frontier.objective_set() == res_b.frontier.objective_set()
    for scene in cfg.scenes:
        assert (
            res_a.scene_frontiers[scene].objective_set()
            == res_b.scene_frontiers[scene].objective_set()
        )
    assert [c.best_bits for c in res_a.cells] == [
        c.best_bits for c in res_b.cells
    ]
    assert res_a.policies_evaluated == res_b.policies_evaluated


@pytest.mark.parametrize("stop_after", [1, 2])
def test_checkpoint_resume_reproduces_uninterrupted_run(
    bundles, tmp_path, stop_after
):
    """Resume from a scene-boundary interrupt (2) AND a mid-scene one (1,
    where the scene's 8-bit anchor is already checkpointed — it must not
    be re-inserted as a duplicate tie). Frontier sizes are compared, not
    just objective sets, to catch silent duplicates."""
    cfg = _cl_cfg()
    full = HeroSearchRun(cfg, bundles).run()

    ck = tmp_path / "ckpt.json"
    cfg_ck = dataclasses.replace(cfg, checkpoint_path=str(ck))
    partial = HeroSearchRun(cfg_ck, bundles).run(stop_after_cells=stop_after)
    assert len(partial.cells) == stop_after and ck.exists()
    state = json.loads(ck.read_text())
    assert len(state["completed"]) == stop_after

    resumed = HeroSearchRun(cfg_ck, bundles).run()
    assert resumed.resumed_cells == stop_after
    assert len(resumed.cells) == len(full.cells)
    assert resumed.frontier.objective_set() == full.frontier.objective_set()
    assert len(resumed.frontier) == len(full.frontier)
    for scene in cfg.scenes:
        assert (
            resumed.scene_frontiers[scene].objective_set()
            == full.scene_frontiers[scene].objective_set()
        )
        assert len(resumed.scene_frontiers[scene]) == len(
            full.scene_frontiers[scene]
        )
    assert [c.best_bits for c in resumed.cells] == [
        c.best_bits for c in full.cells
    ]
    assert resumed.policies_evaluated == full.policies_evaluated


def test_checkpoint_config_mismatch_refused(bundles, tmp_path):
    ck = tmp_path / "ckpt.json"
    cfg = _cl_cfg(checkpoint_path=str(ck))
    HeroSearchRun(cfg, bundles).run(stop_after_cells=1)
    other = dataclasses.replace(cfg, seed=cfg.seed + 1)
    with pytest.raises(ValueError, match="different closed-loop config"):
        HeroSearchRun(other, bundles).run()


def test_frontier_valid_vs_8bit_baseline(bundles):
    """Acceptance shape: non-empty joint frontier, nothing dominated by
    the fixed-8-bit anchor, and the anchor present or strictly beaten."""
    from repro.core.closed_loop import bench_report
    from repro.core.pareto import ParetoPoint

    cfg = _cl_cfg()
    res = HeroSearchRun(cfg, bundles).run()
    assert len(res.frontier) > 0
    anchor = ParetoPoint(latency=1.0, psnr=0.0, model_bytes=1.0)
    for p in res.frontier:
        assert not anchor.dominates(p)
    report = bench_report(res, cfg)
    assert report["frontier_valid_vs_8bit"]
    assert report["frontier_hypervolume"] >= 0.0
    assert report["policies_per_sec"] > 0.0


# ---------------------------------------------------------------------------
# Orchestrated sweeps: sequential identity, chaos recovery, quarantine
# ---------------------------------------------------------------------------
def _assert_results_identical(a, b):
    """Full result identity: joint + per-scene frontiers (sets AND sizes,
    to catch silent duplicate ties), exact hypervolume, per-cell winners."""
    assert a.frontier.objective_set() == b.frontier.objective_set()
    assert len(a.frontier) == len(b.frontier)
    assert a.hypervolume() == b.hypervolume()
    assert set(a.scene_frontiers) == set(b.scene_frontiers)
    for scene in a.scene_frontiers:
        assert (
            a.scene_frontiers[scene].objective_set()
            == b.scene_frontiers[scene].objective_set()
        )
        assert len(a.scene_frontiers[scene]) == len(b.scene_frontiers[scene])
    assert [c.best_bits for c in a.cells] == [c.best_bits for c in b.cells]
    assert a.policies_evaluated == b.policies_evaluated


def test_orchestrator_workers1_identical_to_sequential(bundles):
    """The acceptance baseline: one inline worker, chaos off — the
    orchestrator IS the sequential `HeroSearchRun.run()`, result-for-
    result (frontier points and exact hypervolume)."""
    from repro.distributed.orchestrator import (
        ElasticOrchestrator,
        OrchestratorConfig,
        SearchCellProgram,
    )

    cfg = _cl_cfg()
    seq = HeroSearchRun(cfg, bundles).run()
    orch = ElasticOrchestrator(
        SearchCellProgram(HeroSearchRun(cfg, bundles)),
        OrchestratorConfig(workers=1, worker_kind="inline"),
    )
    res = orch.run()
    _assert_results_identical(res, seq)
    assert res.resumed_cells == 0
    assert [e for e in orch.events if e[0] == "done"] == [
        ("done", s.name, 0, "inline-0")
        for s in HeroSearchRun(cfg, bundles).cell_specs()
    ]


def test_orchestrator_thread_pool_identical_to_sequential(bundles):
    """Two thread workers complete cells out of canonical order; the
    replay-at-finalize merge still reproduces the sequential result."""
    from repro.distributed.orchestrator import (
        ElasticOrchestrator,
        OrchestratorConfig,
        SearchCellProgram,
    )

    cfg = _cl_cfg()
    seq = HeroSearchRun(cfg, bundles).run()
    res = ElasticOrchestrator(
        SearchCellProgram(HeroSearchRun(cfg, bundles)),
        OrchestratorConfig(workers=2, worker_kind="thread"),
    ).run()
    _assert_results_identical(res, seq)


def test_chaos_sweep_recovers_to_identical_frontier(bundles, tmp_path):
    """THE acceptance drill: a 2-scene x 2-budget sweep takes a worker
    kill on its first cell AND a torn checkpoint write (the orchestrator
    dies mid-write); the relaunched sweep quarantines the torn file,
    restarts clean, and lands on the EXACT uninterrupted joint frontier
    (points and hypervolume pinned)."""
    from repro.distributed.chaos import ChaosInterrupt, Fault, FaultPlan
    from repro.distributed.orchestrator import (
        ElasticOrchestrator,
        OrchestratorConfig,
        SearchCellProgram,
    )

    cfg = _cl_cfg()
    clean = HeroSearchRun(cfg, bundles).run()

    ck = tmp_path / "sweep.json"
    cfg_ck = dataclasses.replace(cfg, checkpoint_path=str(ck))
    names = [s.name for s in HeroSearchRun(cfg_ck, bundles).cell_specs()]
    plan = FaultPlan([
        Fault("crash", names[0]),  # worker killed on the first lease
        Fault("torn_checkpoint", names[2]),  # host killed mid-write later
    ])
    orch = ElasticOrchestrator(
        SearchCellProgram(HeroSearchRun(cfg_ck, bundles)),
        OrchestratorConfig(
            workers=2, worker_kind="inline",
            backoff_base=1e-4, poll_interval=1e-4,
        ),
        chaos=plan,
    )
    with pytest.raises(ChaosInterrupt):
        orch.run()
    ev_kinds = [e[0] for e in orch.events]
    assert "crash" in ev_kinds and "rescale" in ev_kinds  # kill recovered
    assert ev_kinds.count("torn") == 1
    assert ck.exists()
    with pytest.raises(json.JSONDecodeError):
        json.loads(ck.read_text())  # the write really was torn

    # Relaunch. The torn file is quarantined (warned, moved aside) and the
    # sweep restarts clean — NOT from a silently half-trusted checkpoint.
    with pytest.warns(RuntimeWarning, match="quarantined"):
        resumed = ElasticOrchestrator(
            SearchCellProgram(HeroSearchRun(cfg_ck, bundles)),
            OrchestratorConfig(workers=2, worker_kind="inline"),
        ).run()
    assert (tmp_path / "sweep.json.corrupt").exists()
    _assert_results_identical(resumed, clean)
    # And the checkpoint left behind by the relaunch is whole again.
    state = json.loads(ck.read_text())
    assert sorted(state["completed"]) == sorted(names)


def test_truncated_checkpoint_quarantined_and_restarted(bundles, tmp_path):
    """Satellite regression: a truncated checkpoint file is moved to
    `<path>.corrupt`, a RuntimeWarning names it, and the sequential run
    restarts cleanly to the full result."""
    from repro.distributed.chaos import tear_checkpoint

    cfg = _cl_cfg()
    full = HeroSearchRun(cfg, bundles).run()

    ck = tmp_path / "ckpt.json"
    cfg_ck = dataclasses.replace(cfg, checkpoint_path=str(ck))
    HeroSearchRun(cfg_ck, bundles).run(stop_after_cells=2)
    tear_checkpoint(str(ck))
    with pytest.warns(RuntimeWarning, match="quarantined"):
        res = HeroSearchRun(cfg_ck, bundles).run()
    assert res.resumed_cells == 0  # nothing was trusted from the torn file
    assert (tmp_path / "ckpt.json.corrupt").exists()
    _assert_results_identical(res, full)


# ---------------------------------------------------------------------------
# Shared occupancy bake (registry)
# ---------------------------------------------------------------------------
def test_two_envs_same_scene_share_one_occupancy_grid(bundles):
    env1 = bundles["chair"].env
    env2 = NGPQuantEnv(
        env1.params, env1.dataset, env1.cfg, env1.rcfg, env1.tcfg,
        EnvConfig(finetune_steps=1, trace_rays=16, calib_points=64),
        HWConfig(coarse_levels=min(8, env1.cfg.hash.n_levels // 2)),
        seed=3,
    )
    assert env2.occ is env1.occ  # same bake object, not a re-bake


def test_bake_registry_keys_on_weights_and_knobs(bundles):
    env = bundles["lego"].env
    n0 = occupancy_registry_size()
    same = bake_occupancy_cached(
        env.params, env.cfg, resolution=env.ecfg.occ_resolution,
        threshold=env.ecfg.occ_threshold,
    )
    assert same is env.occ and occupancy_registry_size() == n0
    other = bake_occupancy_cached(
        env.params, env.cfg, resolution=env.ecfg.occ_resolution,
        threshold=env.ecfg.occ_threshold * 2,
    )
    assert other is not env.occ and occupancy_registry_size() == n0 + 1


# ---------------------------------------------------------------------------
# Sharded population evaluation
# ---------------------------------------------------------------------------
def test_sharded_flag_matches_default_path(bundles):
    """`sharded=True` routes latency through the fused on-device model
    (and on a 1-device host collapses to plain vmap): metrics must be
    identical to the memoized host path either way."""
    env = bundles["chair"].env
    benv_ref = bundles["chair"].benv
    benv_sh = BatchedQuantEnv(
        env, BatchedEnvConfig(proxy_rays=TINY.proxy_rays, seed=0),
        sharded=True,
    )
    rng = np.random.RandomState(5)
    bits = rng.randint(1, 9, size=(6, env.n_units))
    a = benv_ref.evaluate_population(bits)
    b = benv_sh.evaluate_population(bits)
    np.testing.assert_allclose(b.latency_cycles, a.latency_cycles, rtol=1e-5)
    np.testing.assert_allclose(b.model_bytes, a.model_bytes, rtol=1e-5)
    np.testing.assert_allclose(b.psnr, a.psnr, atol=1e-4)
    np.testing.assert_allclose(b.reward, a.reward, atol=1e-3)


_SHARDED_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=2 "
        + os.environ.get("XLA_FLAGS", "")
    )
    import jax
    import jax.numpy as jnp
    import numpy as np

    assert len(jax.devices()) == 2, jax.devices()

    from repro.distributed.population import pad_population, shard_population
    from repro.hwsim import (
        BatchedNeuRexSimulator, HWConfig, build_trace,
        build_trace_constants, policy_latency,
    )
    from repro.nerf.hash_encoding import HashEncodingConfig
    from repro.nerf.ngp import NGPConfig
    from repro.nerf.render import RenderConfig

    CFG = NGPConfig(
        hash=HashEncodingConfig(n_levels=4, log2_table_size=9,
                                base_resolution=4, max_resolution=32),
        hidden_dim=16, color_hidden_dim=16, geo_feat_dim=7, sh_degree=2,
    )
    HW = HWConfig(coarse_levels=2)
    rng = np.random.RandomState(0)
    ro = rng.randn(32, 3).astype(np.float32) * 0.1
    rd = rng.randn(32, 3).astype(np.float32)
    rd /= np.linalg.norm(rd, axis=1, keepdims=True)
    trace = build_trace(CFG, RenderConfig(n_samples=8), ro, rd)
    tc = build_trace_constants(trace, HW, CFG.hash.n_features)

    K = 5  # odd on purpose: exercises the pad-to-device-multiple path
    n_mlp = len(tc.mlp_dims)
    hb = rng.randint(1, 9, size=(K, tc.n_levels)).astype(np.float32)
    wb = rng.randint(1, 9, size=(K, n_mlp)).astype(np.float32)
    ab = rng.randint(1, 9, size=(K, n_mlp)).astype(np.float32)

    padded, k0 = pad_population(hb, 2)
    assert padded.shape[0] == 6 and k0 == K

    call = shard_population(
        jax.vmap(lambda h, w, a: policy_latency(h, w, a, tc, HW, 0.5))
    )
    assert call.n_shards == 2
    out = call(jnp.asarray(hb), jnp.asarray(wb), jnp.asarray(ab))
    assert out["total_cycles"].shape == (K,)

    ref = BatchedNeuRexSimulator(
        trace, HW, n_features=CFG.hash.n_features
    ).simulate_batch(hb, wb, ab)
    np.testing.assert_allclose(
        out["total_cycles"], ref["total_cycles"], rtol=1e-5
    )
    np.testing.assert_array_equal(out["grid_misses"], ref["grid_misses"])
    np.testing.assert_array_equal(out["grid_hits"], ref["grid_hits"])
    print("SHARDED_OK")
""")


def test_sharded_two_device_subprocess_parity():
    """Force 2 host devices in a fresh process (conftest forbids touching
    device state in-process) and pin sharded == memoized-host metrics,
    including the K % n_devices != 0 padding path."""
    env = dict(os.environ)
    src = str(Path(__file__).resolve().parent.parent / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_SUBPROCESS],
        capture_output=True, text=True, timeout=240, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHARDED_OK" in proc.stdout


# ---------------------------------------------------------------------------
# Scales: the paper widths, and fingerprints that older checkpoints match
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name,sha", [
    ("quick",
     "5a7bf468582b71d06070dd54177ae0ab43e54545c993daa8a54bfb4c39609b42"),
    ("standard",
     "f3da6bec8c03608ae0ddb46f51903fc1e94f131eecaf4b94d2fd00a0abb66c75"),
    ("tiny",
     "a233b8c95a6df679fd34b8320a2bde9abb9aa3d81d70ed4176f15f64a65aa54d"),
])
def test_scene_scale_keeps_checkpoint_fingerprint(name, sha):
    """Fields added to SceneScale (base_res, sh_degree) stay out of the
    run fingerprint at their defaults: every existing scale keeps the
    fingerprint its checkpoints were written with."""
    import hashlib

    fp = ClosedLoopConfig(scale=getattr(SceneScale, name)()).fingerprint()
    got = hashlib.sha256(json.dumps(fp, sort_keys=True).encode()).hexdigest()
    assert got == sha
    assert "base_res" not in fp["scale"] and "sh_degree" not in fp["scale"]


def test_paper_scale_builds_the_paper_widths():
    from repro.configs.ngp import paper

    scale = SceneScale.paper()
    assert scale.ngp_config() == paper()
    fp = ClosedLoopConfig(scale=scale).fingerprint()["scale"]
    # 16 SH coefficients (bands 0-3) is the default degree, so it stays
    # out of the fingerprint; the base resolution does not.
    assert scale.sh_degree == 3 and paper().sh_dim == 16
    assert fp["base_res"] == 16 and "sh_degree" not in fp
