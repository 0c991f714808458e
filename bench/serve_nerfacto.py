"""Frame serving of a Nerfacto field: the configuration's field trained,
calibrated and packed, served through the program's `ServeEngine` to the
same closed-loop clients as `bench/serve.py`'s cells.

Set-up (all of it counted in `setup_s`): train the field from the seed
with the program's `train_nerfacto` (RGB, interlevel and distortion
losses); calibrate each linear's activation range on the float render of
seeded training rays; pack the policy into a `QuantArtifact` (three
packed fields, no occupancy grid); stand the engine up with `hero.serve`,
which compiles the proposal and shading programs. Every ray is the
configuration's fixed samples, so no budget is settled.

Window and comparison as in `bench/serve.py` (its `Window`, `Stream` and
ray-by-ray comparison are used as they are): a sample of the work items
served in the window, drawn from the seed, is rendered again by the
configuration's plain reference (`bench/configs/nerfacto.py`).
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List

import numpy as np

from bench import common, work
from bench.serve import Stream, Window, gap_numbers, round_range, sampled_rays
from bench.traffic import generate
from repro.nerf import nerfacto

# Controls of the comparison (calibration only): the reference computed
# another way and put in the program's place.
CONTROLS = {
    "high": {"precision": "high"},
    "bfloat16": {"precision": "bfloat16"},
    "no_proposal_2": {"skip_pass": 1},
}


def model_config(m: Dict, n_images: int) -> nerfacto.NerfactoConfig:
    """The configuration's `model` block as the program's config."""
    from repro.nerf.hash_encoding import HashEncodingConfig
    from repro.nerf.ngp import NGPConfig

    f = m["field"]
    grid = ("n_levels", "n_features", "log2_table_size", "base_resolution",
            "max_resolution")
    return nerfacto.NerfactoConfig(
        field=NGPConfig(
            hash=HashEncodingConfig(**{k: f[k] for k in grid}),
            hidden_dim=f["hidden_dim"], geo_feat_dim=f["geo_feat_dim"],
            color_hidden_dim=f["color_hidden_dim"], sh_degree=f["sh_degree"]),
        proposals=tuple(HashEncodingConfig(**{k: p[k] for k in grid})
                        for p in m["proposals"]),
        proposal_hidden=m["proposals"][0]["hidden_dim"],
        appearance_dim=m["appearance_dim"], n_images=n_images,
        n_initial=m["n_initial"], n_resampled=tuple(m["n_resampled"]),
        near=m["near"], far=m["far"],
        histogram_padding=m["histogram_padding"],
    )


def policy_bits(cfg: Dict, ncfg: nerfacto.NerfactoConfig) -> List[int]:
    """The configuration's policy in the program's unit walk order: main
    hash levels, each proposal's, then each linear's activation and
    weight."""
    pol = cfg["policy"]
    bits = list(pol["hash_bits"]["hash"])
    for k in range(len(ncfg.proposals)):
        bits += pol["hash_bits"][f"prop{k + 1}"]
    for name in nerfacto.linear_names(ncfg):
        bits += [pol["linears"][name]["act"], pol["linears"][name]["weight"]]
    return bits


def build_field(cfg: Dict, seed: int, phases: Dict[str, float]):
    """Train, then calibrate and pack (`pack_artifact`), as the
    configuration states; seconds of each step go into `phases`. Returns
    (float params, artifact, calibration ray origins, directions)."""
    import jax

    from repro.nerf.dataset import make_dataset
    from repro.nerf.scenes import SceneConfig

    t = time.perf_counter()

    def lap(name):
        nonlocal t
        now = time.perf_counter()
        phases[name] = now - t
        t = now

    ncfg = model_config(cfg["model"], cfg["n_train_views"])
    ds = make_dataset(SceneConfig(name=cfg["scene"], image_hw=cfg["image_hw"],
                                  n_train_views=cfg["n_train_views"],
                                  n_test_views=1))
    lap("dataset")
    train_seed, calib_seed = generate.seed_words(seed, 2, 0)
    params, _ = nerfacto.train_nerfacto(ds, ncfg, nerfacto.NerfactoTrainConfig(
        steps=cfg["train_steps"], batch_rays=cfg["batch_rays"], lr=cfg["lr"],
        seed=train_seed))
    jax.block_until_ready(params)
    lap("train")

    rng = np.random.default_rng(calib_seed)
    idx = rng.integers(0, ds.train_rays_o.shape[0], size=cfg["calib_rays"])
    calib_o = np.ascontiguousarray(ds.train_rays_o[idx], np.float32)
    calib_d = np.ascontiguousarray(ds.train_rays_d[idx], np.float32)
    art = pack_artifact(cfg, ncfg, params, calib_o, calib_d)
    lap("pack")
    return params, art, calib_o, calib_d


def pack_artifact(cfg: Dict, ncfg: nerfacto.NerfactoConfig, params: Dict,
                  calib_o: np.ndarray, calib_d: np.ndarray):
    """Calibrate each linear's activation range on the float render of
    the calibration rays (at the served appearance) and pack the
    configuration's policy into a `QuantArtifact`."""
    import jax
    import jax.numpy as jnp

    from repro.hero import QuantArtifact
    from repro.nerf.fast_render import build_nerfacto_pack
    from repro.quant.policy import QuantPolicy

    def taps(p, o, d):
        t = {}
        nerfacto.render_rays(p, o, d, ncfg, nerfacto.serve_appearance(p), t)
        return {n: (jnp.min(v), jnp.max(v)) for n, v in t.items()}

    with jax.default_matmul_precision("highest"):
        got = jax.jit(taps)(params, jnp.asarray(calib_o), jnp.asarray(calib_d))
    act_ranges = jnp.asarray(
        [round_range(float(got[n][0]), float(got[n][1]),
                     cfg["act_range_sig_bits"])
         for n in nerfacto.linear_names(ncfg)], jnp.float32)
    bits = policy_bits(cfg, ncfg)
    units = nerfacto.make_quant_units(ncfg)
    spec = nerfacto.spec_from_policy(
        ncfg, QuantPolicy.uniform(units, 8).with_bits(bits), act_ranges)
    pe = cfg["policy"]["paper_exact"]
    spec = nerfacto.NerfactoQuantSpec(
        main=dataclasses.replace(spec.main, paper_exact=pe),
        proposals=tuple(dataclasses.replace(s, paper_exact=pe)
                        for s in spec.proposals))
    pack = build_nerfacto_pack(params, ncfg, spec, layout="tile:128")
    jax.block_until_ready(pack)
    return QuantArtifact(
        scene=cfg["scene"], bits=bits, cfg=ncfg, rcfg=None, scene_cfg={},
        params=params, act_ranges=act_ranges, pack=pack, occ=None,
        hardware={}, metrics={})


def run(cell: Dict, cfg: Dict, mix: Dict, seed: int, seconds: float,
        trace: bool, clock: common.CompileClock, t_start: float,
        fault=None, controls=()) -> Dict:
    """One run of the cell. `fault` (tests only) breaks the timed path
    underneath: it is called with the engine before the window.
    `controls` (calibration only) name entries of `CONTROLS`."""
    import jax

    from repro import hero
    from repro.hero import ServeConfig
    from repro.kernels import ops

    spans = common.Spans()
    phases: Dict[str, float] = {}
    params, art, calib_o, calib_d = build_field(cfg, seed, phases)
    t = time.perf_counter()
    hw, focal = mix["image_hw"], mix["focal_mult"] * mix["image_hw"]
    poses = generate.client_poses(mix, seed, mix["frames"])
    streams = [Stream(p, hw, focal) for p in poses]
    svc = hero.serve(art, ServeConfig(slots=mix["slots"],
                                      slot_rays=mix["slot_rays"]))
    engine = svc.engine
    if fault is not None:
        fault(engine)
    engine.reset_stats()
    phases["serve_warm"] = time.perf_counter() - t

    win = Window(engine, art.scene, streams, mix, spans)
    before = clock.snapshot()
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    prof_dir = common.OUT / "trace" / cell["name"]
    if trace:
        import shutil

        shutil.rmtree(prof_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(prof_dir), profiler_options=opts)
    try:
        with spans("bench.window"):
            window_s = win.run(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    after = clock.snapshot()
    stats = engine.stats()
    device = common.device_info(cell["chips"])

    out = {
        "setup": {
            "setup_s": setup_s, "phases_s": phases, "setup_compile": before,
            "window_compiles": after["compiles"] - before["compiles"],
            "window_budget_retraces": stats["budget_retraces"],
            "auto_resolved": {f"{a}:{b}": n
                              for (a, b), n in sorted(ops.AUTO_RESOLVED.items())},
            "spans_s": spans.seconds,
        },
        "window_s": window_s,
        "rays": stats["rays_rendered"],
        "attempted": stats["requests_submitted"],
        "failed": stats["requests_expired"] + stats["requests_rejected"],
        "device": device,
        "stats": stats,
    }

    run_ctx = None
    if trace:
        import glob

        from bench import trace as tr

        files = sorted(glob.glob(str(prof_dir / "plugins/profile/*/*.xplane.pb")))
        run_ctx = {
            "reduction": tr.reduce_events(tr.read_xplane(files[-1])),
            "stats": stats, "window_s": window_s, "slots": len(win.rendered),
            "model": cfg["model"],
            "peaks": (work.peaks(device["kind"])
                      if device["platform"] == "tpu" else None),
        }

    # The reference runs after the program's serve state is dropped.
    rng = np.random.default_rng(generate.seed_words(seed, 4, 5))
    n = min(mix["check_items"], len(win.rendered))
    pick = sorted(rng.choice(len(win.rendered), size=n, replace=False))
    items = [win.rendered[j] for j in pick]
    served = {it: win.served(it) for it in items}
    win.served = lambda it: served[it]
    win.engine = engine = svc = art = None
    gc.collect()
    t = time.perf_counter()
    ref_mod = common.reference_module(cfg)
    ro, rd, got = sampled_rays(win, items)
    want = ref_mod.Reference(params, cfg, calib_o, calib_d).render(ro, rd)
    out["check"] = {"rays_compared": int(got.shape[0]),
                    "numbers": gap_numbers(got, want),
                    "seconds": time.perf_counter() - t}
    out["controls"] = {
        c: gap_numbers(ref_mod.Reference(params, cfg, calib_o, calib_d,
                                         **CONTROLS[c]).render(ro, rd), want)
        for c in controls}
    out["trace_ctx"] = run_ctx
    return out
