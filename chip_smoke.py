#!/usr/bin/env python3
"""Smoke run of HERO's main path on a TPU, at the paper's Instant-NGP
widths (16 hash levels, F=2, T=2^19, resolutions 16..2048, 64-wide MLPs,
16 SH coefficients), through the public `repro.hero` API.

    python chip_smoke.py             # one chip: search -> compile -> serve
    python chip_smoke.py --chips 4   # four chips: sharded population scoring

One chip: the closed-loop population search trains the field on the
device and scores a few iterations against the `neurex` target; the best
policy compiles to a `QuantArtifact`, which is saved, loaded back
tile-native and served through `ServeEngine`: full test-view frames and
one fresh pose that takes the `march` tier. Checks: served PSNR equals
the compile-time fused PSNR (1e-3 dB), the fused integer path agrees with
the fake-quant float reference (0.1 dB), the fresh pose served through
the march tier agrees with the same rays rendered in one chunk by the
artifact's fused `FastRenderEngine`, the march kernel's mask on those
rays equals `ref.ray_march_ref`, and one `quant_matmul_packed` call
equals its reference bit for bit.

Four chips: the same scene bundle's population is scored through
`shard_population` on a 4-device ("pop",) mesh and on a 1-device mesh;
cache misses must be equal, cycles within 1e-3 relative, proxy quality
within 1e-4 dB, and the latency model's output under that mesh must sit
on all four devices.

This proves that the system starts and computes correctly on the chip; it
is not a benchmark. The seconds it prints are smoke timings: compile
(lowering and backend compilation, from JAX's monitoring events) and the
rest of each phase's wall time. The last line of standard output
is one JSON object, printed only when every check passed:
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
Without a TPU, or without the repository's `src/` beside this file, it
exits non-zero before that line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SCENE = "chair"
BUDGET_FRAC = 0.85  # one latency budget: 85% of the all-8-bit latency
SEED = 0
ITERATIONS, POPULATION = 2, 8  # search depth cuts
SLOTS, SLOT_RAYS = 4, 1024
SERVE_BAND_DB = 1e-3  # served vs compile-time fused PSNR
REFERENCE_BAND_DB = 0.1  # fused integer path vs fake-quant reference
# March tier vs the one-chunk fused render, per color channel in [0, 1]:
# float rounding between two compiled programs (8 ulp at 1.0), far below
# one 8-bit color level; a sample the march mask dropped or added moves a
# pixel by that sample's whole contribution.
MARCH_VS_FUSED_ATOL = 1e-6
SHARD_CYCLES_RTOL = 1e-3
SHARD_QUALITY_DB = 1e-4


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"[smoke] check ok: {what}", flush=True)


class Phase:
    """Wall and compile seconds of one phase; `clock` is a
    `repro.obs.CompileClock`."""

    def __init__(self, name: str, clock, report: dict):
        self.name, self.clock, self.report = name, clock, report

    def __enter__(self):
        print(f"[smoke] === phase {self.name} ===", flush=True)
        self.t0 = time.perf_counter()
        self.c0 = self.clock.seconds
        self.h0, self.m0 = self.clock.hits, self.clock.misses
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        wall = time.perf_counter() - self.t0
        comp = self.clock.seconds - self.c0
        row = {
            "compile_s": comp, "run_s": wall - comp, "wall_s": wall,
            "cache_hits": self.clock.hits - self.h0,
            "cache_misses": self.clock.misses - self.m0,
        }
        self.report[self.name] = row
        print(f"[smoke] phase {self.name} done (smoke timing, not a "
              f"benchmark): compile {comp:.1f} s, run {wall - comp:.1f} s, "
              f"wall {wall:.1f} s; persistent-cache hits {row['cache_hits']}"
              f", misses {row['cache_misses']}", flush=True)
        return False


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------
def print_config(scale) -> None:
    from repro.core.closed_loop import PAPER_DEPTH_CUTS

    h = scale.ngp_config().hash
    print(f"[smoke] model: Instant-NGP at paper widths: {h.n_levels} hash "
          f"levels, F={h.n_features}, T=2^{h.log2_table_size}, resolutions "
          f"{h.resolutions()[0]}..{h.resolutions()[-1]}, MLP width "
          f"{scale.hidden}, geo_feat 15, SH degree {scale.sh_degree}")
    print(f"[smoke] scene {SCENE!r}, one latency budget "
          f"{BUDGET_FRAC:g} x all-8-bit latency, target neurex")
    print("[smoke] depth cuts (value here | published):")
    for key, published in PAPER_DEPTH_CUTS.items():
        print(f"[smoke]   {key:15s} {getattr(scale, key):>6} | {published}")
    print(f"[smoke]   {'iterations':15s} {ITERATIONS:>6} | search "
          f"iterations per cell (HERO runs its RL search to convergence)")
    print(f"[smoke]   {'population':15s} {POPULATION:>6} | policies "
          "per iteration")


def phase_search(scale):
    import numpy as np

    from repro import hero

    result = hero.search(
        scenes=(SCENE,), budget_fracs=(BUDGET_FRAC,), hardware="neurex",
        scale=scale, n_iterations=ITERATIONS,
        population=POPULATION, seed=SEED, checkpoint_path=None,
        verbose=True,
    )
    check(len(result.cells) == 1, "one search cell completed")
    check(result.policies_evaluated >= ITERATIONS * POPULATION,
          f"{result.policies_evaluated} policies scored")
    pts = result.scene_frontiers[SCENE].points
    vals = np.asarray([[p.latency, p.psnr, p.model_bytes] for p in pts])
    check(len(pts) > 0 and bool(np.isfinite(vals).all()),
          f"{len(pts)}-point frontier, all objectives finite")
    scene, bits = hero.best_bits(result, SCENE)
    check(all(1 <= b <= 8 for b in bits), f"best policy bits {bits}")
    print(f"[smoke] search: best reward {result.cells[0].best_reward:.4f}, "
          f"{result.policies_evaluated} policies, frontier {len(pts)}")
    return bits


def phase_compile(scale, bits, workdir: Path):
    from repro import hero
    from repro.hero import QuantArtifact

    art = hero.compile_scene(SCENE, bits, scale=scale, seed=SEED * 1000)
    path = art.save(workdir / SCENE)
    loaded = QuantArtifact.load(path)
    check(loaded.pack.layout == "tile:128", "artifact loaded tile-native")
    check(loaded.stored_model_bytes() == art.metrics["model_bytes"]
          == loaded.metrics["model_bytes"],
          f"stored_model_bytes() == model_bytes == "
          f"{int(art.metrics['model_bytes'])}")
    print(f"[smoke] compile: PSNR {art.metrics['psnr']:.4f} dB (in-process "
          f"fused), latency {art.metrics['latency_cycles']:.0f} cycles")
    return loaded


def phase_serve(loaded, parity: dict):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import hero
    from repro.hero import ServeConfig
    from repro.kernels import ops, ref
    from repro.nerf.dataset import make_dataset
    from repro.nerf.fast_render import _use_kernels
    from repro.nerf.occupancy import ray_t_samples
    from repro.nerf.scenes import SceneConfig, camera_poses, camera_rays

    check(_use_kernels("auto"), "fused renderer takes the kernel path")
    sc = dict(loaded.scene_cfg)
    sc["light_dir"] = tuple(sc["light_dir"])
    ds = make_dataset(SceneConfig(**sc))

    svc = hero.serve(loaded, ServeConfig(slots=SLOTS, slot_rays=SLOT_RAYS))
    se, px = 0.0, 0
    for v in range(ds.test_rays_o.shape[0]):
        colors = svc.render(ds.test_rays_o[v], ds.test_rays_d[v])
        check(colors.shape == (ds.test_rays_o.shape[1], 3)
              and bool(np.isfinite(colors).all()),
              f"test view {v}: full frame served, finite")
        se += float(((colors - ds.test_rgb[v]) ** 2).sum())
        px += ds.test_rgb[v].size
    psnr_serve = -10.0 * math.log10(max(se / px, 1e-12))
    psnr_compile = float(loaded.metrics["psnr"])
    parity["serve_vs_compile_db"] = abs(psnr_serve - psnr_compile)
    print(f"[smoke] parity: served {psnr_serve:.6f} dB vs compile-time "
          f"fused {psnr_compile:.6f} dB")
    check(parity["serve_vs_compile_db"] <= SERVE_BAND_DB,
          f"served vs compile-time PSNR within {SERVE_BAND_DB} dB "
          f"(delta {parity['serve_vs_compile_db']:.2e})")

    fused = loaded.engine().evaluate_psnr(ds)
    reference = loaded.engine(mode="reference").evaluate_psnr(ds)
    parity["fused_vs_reference_db"] = abs(fused - reference)
    print(f"[smoke] parity: fused integer {fused:.6f} dB vs fake-quant "
          f"reference {reference:.6f} dB")
    check(parity["fused_vs_reference_db"] <= REFERENCE_BAND_DB,
          f"fused vs reference PSNR within {REFERENCE_BAND_DB} dB "
          f"(delta {parity['fused_vs_reference_db']:.2e})")

    # A fresh pose: a camera on no train or test ring -> the march tier.
    fresh_cfg = dataclasses.replace(ds.cfg, n_test_views=1,
                                    cam_elevation=0.8)
    pose = camera_poses(fresh_cfg)[1][0]
    ro, rd = camera_rays(jnp.asarray(pose), ds.cfg.image_hw,
                         ds.cfg.focal_mult * ds.cfg.image_hw)
    ro, rd = np.asarray(ro), np.asarray(rd)
    before = svc.stats()["pose_cache"]
    colors = svc.render(ro, rd)
    after = svc.stats()["pose_cache"]
    n_items = -(-ro.shape[0] // SLOT_RAYS)
    check(after["misses"] - before["misses"] == n_items
          and after["hits"] == before["hits"]
          and after["warps"] == before["warps"],
          f"fresh pose served through the march tier ({n_items} slots)")
    check(bool(np.isfinite(colors).all()), "fresh-pose frame finite")

    # The served march tier against the fused render of the same rays as
    # one chunk, under the exact budget of those rays: a fault in the
    # slot assembly or the budget growth moves the served colors.
    want = np.asarray(loaded.engine(mode="fused").render_rays(ro, rd))
    diff = np.abs(colors - want)
    parity["march_vs_fused_max_abs"] = float(diff.max())
    parity["march_vs_fused_byte_equal"] = bool(np.array_equal(colors, want))
    print(f"[smoke] fresh pose: march tier vs one-chunk fused render max "
          f"|diff| {diff.max():.3e}, byte-equal "
          f"{parity['march_vs_fused_byte_equal']}")
    check(diff.max() <= MARCH_VS_FUSED_ATOL,
          f"served march tier == one-chunk fused render within "
          f"{MARCH_VS_FUSED_ATOL} per channel")
    ref_colors = np.asarray(
        loaded.engine(mode="reference").render_rays(ro, rd)
    )
    mse = float(np.mean((colors - ref_colors) ** 2))
    print(f"[smoke] fresh pose: served vs fake-quant reference render "
          f"{-10.0 * math.log10(max(mse, 1e-12)):.2f} dB PSNR")

    t1 = jnp.asarray(ray_t_samples(
        dataclasses.replace(loaded.rcfg, stratified=False)))
    mismatched, active = 0, 0
    for s0 in range(0, ro.shape[0], SLOT_RAYS):
        o = jnp.asarray(ro[s0:s0 + SLOT_RAYS])
        d = jnp.asarray(rd[s0:s0 + SLOT_RAYS])
        got = np.asarray(ops.ray_march(loaded.occ.occ, o, d, t1,
                                       early_stop=True))
        want = np.asarray(ref.ray_march_ref(loaded.occ.occ, o, d, t1))
        mismatched += int((got != want).sum())
        active += int(want.sum())
    parity["march_mask_mismatches"] = mismatched
    check(mismatched == 0, f"march kernel mask on the served slots == "
          f"ref.ray_march_ref ({active} active samples of "
          f"{ro.shape[0] * t1.shape[0]})")

    lyr = loaded.pack.layers["sigma/0"]
    wq, wt = lyr["wq"], loaded.pack.compute["sigma/0::wq_tile"]
    x = jax.random.randint(jax.random.PRNGKey(SEED),
                           (4096, wq.shape[0]), -128, 128, jnp.int8)
    got = np.asarray(ops.quant_matmul_packed(x, wt, 0.05, wq.scale, 3))
    want = np.asarray(ref.quant_matmul_packed_ref(x, wq, 0.05, wq.scale, 3))
    parity["packed_matmul_mismatches"] = int((got != want).sum())
    check(parity["packed_matmul_mismatches"] == 0,
          f"quant_matmul_packed ({wq.bits}-bit, {wt.layout}) == "
          "ref.quant_matmul_packed_ref bit for bit")


def phase_population(scale, n_dev: int, parity: dict):
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core.batched_env import BatchedEnvConfig, BatchedQuantEnv
    from repro.core.closed_loop import build_scene_bundle
    from repro.distributed.population import (
        POP_AXIS, pad_population, population_mesh,
    )

    bundle = build_scene_bundle(SCENE, scale, seed=SEED * 1000,
                                sharded=True)
    env, sharded = bundle.env, bundle.benv
    check(sharded.sharded, f"population sharded over {n_dev} devices")
    single = BatchedQuantEnv(
        env, BatchedEnvConfig(proxy_rays=scale.proxy_rays,
                              seed=SEED * 1000),
        sharded=True, mesh=population_mesh(1),
    )
    rng = np.random.RandomState(SEED)
    k = 2 * n_dev + 2  # not a device multiple: the pad path runs too
    bits = rng.randint(2, 9, size=(k, env.n_units))
    bits[0] = 8
    s4 = sharded.simulate_batch(bits)
    q4 = sharded.proxy_quality(env.params, bits)
    s1 = single.simulate_batch(bits)
    q1 = single.proxy_quality(env.params, bits)

    # Where the shards land: the env's latency model under the shard_map
    # that `shard_population` builds on the n_dev mesh, on the same
    # padded population; its rows must equal the sharded env's.
    mesh = population_mesh(n_dev)
    lat = jax.jit(jax.shard_map(
        jax.vmap(sharded.bsim.vmappable()), mesh=mesh,
        in_specs=(P(POP_AXIS),) * 3, out_specs=P(POP_AXIS), check_vma=False,
    ))
    cycles = lat(*sharded.bits_to_arrays(pad_population(bits, n_dev)[0]))[
        "total_cycles"]
    devices = sorted(s.device.id for s in cycles.addressable_shards)
    print(f"[smoke] sharded latency output devices: {devices}")
    check(devices == sorted(d.id for d in mesh.devices.flat)
          and len(set(devices)) == n_dev,
          f"sharded output spans all {n_dev} devices")
    check(np.array_equal(np.asarray(cycles)[:k], s4["total_cycles"]),
          "placement probe reproduces the sharded env's cycles")
    miss_eq = bool(np.array_equal(s4["grid_misses"], s1["grid_misses"]))
    cyc = np.max(np.abs(s4["total_cycles"] - s1["total_cycles"])
                 / np.abs(s1["total_cycles"]))
    dq = float(np.max(np.abs(q4 - q1)))
    parity.update(cycles_rel=float(cyc), quality_db=dq,
                  misses_equal=miss_eq, policies=k)
    print(f"[smoke] {k} policies: grid misses equal {miss_eq}, cycles max "
          f"rel diff {cyc:.2e}, proxy quality max diff {dq:.2e} dB")
    check(miss_eq, "cache misses equal, sharded vs 1-device")
    check(cyc <= SHARD_CYCLES_RTOL,
          f"cycles within {SHARD_CYCLES_RTOL} relative")
    check(dq <= SHARD_QUALITY_DB, f"proxy quality within {SHARD_QUALITY_DB} dB")
    check(bool(np.isfinite(q4).all()), "proxy quality finite")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: search -> compile -> serve; 4: the sharded "
                         "population phase and its 1-device comparison")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro" / "hero").is_dir():
        print("[smoke] FAIL: the repository's src/repro package is not "
              f"beside {Path(__file__).name}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels.backend import enable_compile_cache
    from repro.obs import CompileClock

    cache_dir = enable_compile_cache()
    import jax

    clock = CompileClock()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[smoke] FAIL: no TPU found (JAX reports platform "
              f"{dev.platform!r}); this smoke runs on the chip only",
              file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"[smoke] FAIL: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 3

    from repro.core.closed_loop import SceneScale
    from repro.kernels import ops
    from repro.kernels.backend import runner_fingerprint

    fp = runner_fingerprint()
    print(f"[smoke] runner: {json.dumps(fp)}")
    print(f"[smoke] compile cache: {cache_dir}")
    scale = SceneScale.paper()
    print_config(scale)
    timings, parity = {}, {}
    workdir = ROOT / "experiments" / "chip_smoke"
    try:
        check(fp["kernel_backend"] == "compiled",
              "Pallas kernels compiled, not interpreted")
        if args.chips == 4:
            with Phase("population", clock, timings):
                phase_population(scale, args.chips, parity)
        else:
            from repro.configs.ngp import paper

            h = paper().hash
            print(f"[smoke] hash: all {h.n_levels} levels "
                  f"({h.level_entries(0)}..{h.level_entries(h.n_levels - 1)}"
                  " rows) take one XLA gather")
            with Phase("search", clock, timings):
                bits = phase_search(scale)
            with Phase("compile", clock, timings):
                loaded = phase_compile(scale, bits, workdir)
            with Phase("serve", clock, timings):
                phase_serve(loaded, parity)
        fallbacks = {k: v for k, v in ops.AUTO_RESOLVED.items()
                     if k[1] != "compiled"}
        print(f"[smoke] auto kernel resolutions: "
              f"{dict(sorted((f'{a}:{b}', n) for (a, b), n in ops.AUTO_RESOLVED.items()))}")
        check(not fallbacks, "no 'auto' kernel call fell back to the "
              "reference or interpret mode")
    except SmokeFailure as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    print(f"[smoke] timings: {json.dumps(timings)}")
    print(f"[smoke] parity: {json.dumps(parity)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
