"""Frame serving cells: a trained, quantized field served through the
program's `ServeEngine` to closed-loop clients that each keep a fixed
number of full frames in flight.

Set-up (all of it counted in `setup_s`): train the configuration's field
from the seed with the program's `train_ngp`; calibrate activation
ranges with the program's `ngp_apply(..., return_taps=True)`; bake the
occupancy grid and build the packed artifact as `QuantArtifact.load`
does; settle the engine's sample budget on the worst slot of every frame
the window may serve; stand the engine up with `hero.serve` (which
compiles the march tier at that budget).

Window: clients submit frames, the engine steps, finished frames go back
to their client, which submits its next pose. `rays_per_s` counts the
rays of every work item the engine returned inside the window.

After the window: the device's memory peak is read, the program's serve
state is dropped, and a sample of the work items served in the window,
drawn from the seed, is rendered again by the configuration's plain
reference and compared ray by ray.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time
from collections import deque
from typing import Dict, List

import numpy as np

from bench import common, work
from bench.traffic import generate


@dataclasses.dataclass
class Field:
    """What set-up built: the float checkpoint and the serve artifact."""

    params: Dict
    artifact: object
    calib_pts: np.ndarray
    calib_dirs: np.ndarray


def _ngp_config(m: Dict):
    from repro.nerf.hash_encoding import HashEncodingConfig
    from repro.nerf.ngp import NGPConfig

    return NGPConfig(
        hash=HashEncodingConfig(
            n_levels=m["n_levels"], n_features=m["n_features"],
            log2_table_size=m["log2_table_size"],
            base_resolution=m["base_resolution"],
            max_resolution=m["max_resolution"]),
        hidden_dim=m["hidden_dim"], geo_feat_dim=m["geo_feat_dim"],
        color_hidden_dim=m["color_hidden_dim"], sh_degree=m["sh_degree"],
        density_activation=m["density_activation"],
    )


def policy_bits(cfg: Dict) -> List[int]:
    """The configuration's policy in the program's unit walk order: hash
    levels, then each linear's activation and weight."""
    pol = cfg["policy"]
    bits = list(pol["hash_bits"])
    for name in ("sigma/0", "sigma/1", "color/0", "color/1", "color/2"):
        bits += [pol["linears"][name]["act"], pol["linears"][name]["weight"]]
    return bits


def build_field(cfg: Dict, seed: int, phases: Dict[str, float]) -> Field:
    """Train, calibrate, bake and pack, as the configuration states;
    seconds of each step go into `phases`."""
    import jax
    import jax.numpy as jnp

    from repro.hero import QuantArtifact
    from repro.nerf.dataset import make_dataset
    from repro.nerf.fast_render import build_fused_pack, repack_fused_pack
    from repro.nerf.ngp import make_quant_units, ngp_apply, ngp_linear_names, spec_from_policy
    from repro.nerf.occupancy import bake_occupancy_cached
    from repro.nerf.render import RenderConfig
    from repro.nerf.scenes import SceneConfig
    from repro.nerf.train import TrainConfig, train_ngp
    from repro.quant.policy import QuantPolicy

    t = time.perf_counter()

    def lap(name):
        nonlocal t
        now = time.perf_counter()
        phases[name] = now - t
        t = now

    ncfg = _ngp_config(cfg["model"])
    rcfg = RenderConfig(n_samples=cfg["n_samples"], near=cfg["near"],
                        far=cfg["far"], white_bg=cfg["white_bg"])
    ds = make_dataset(SceneConfig(name=cfg["scene"], image_hw=cfg["image_hw"],
                                  n_train_views=cfg["n_train_views"],
                                  n_test_views=1))
    lap("dataset")
    train_seed, calib_seed = generate.seed_words(seed, 2, 0)
    params, _ = train_ngp(ds, ncfg, rcfg, TrainConfig(
        steps=cfg["train_steps"], batch_rays=cfg["batch_rays"], lr=cfg["lr"],
        seed=train_seed))
    jax.block_until_ready(params)
    lap("train")

    rng = np.random.default_rng(calib_seed)
    idx = rng.integers(0, ds.train_rays_o.shape[0], size=cfg["calib_rays"])
    ts = np.linspace(cfg["near"], cfg["far"], cfg["n_samples"], dtype=np.float32)
    o, d = ds.train_rays_o[idx], ds.train_rays_d[idx]
    pts = np.clip(o[:, None] + d[:, None] * ts[None, :, None] + 0.5, 0.0, 1.0)
    pts = pts.reshape(-1, 3)[:cfg["calib_points"]].astype(np.float32)
    dirs = np.broadcast_to(d[:, None], (idx.size, ts.size, 3)).reshape(-1, 3)
    dirs = np.ascontiguousarray(dirs[:cfg["calib_points"]], np.float32)

    with jax.default_matmul_precision("highest"):
        taps = jax.jit(lambda p, x, y: {
            n: (jnp.min(v), jnp.max(v)) for n, v in
            ngp_apply(p, x, y, ncfg, None, return_taps=True)[2].items()})(
                params, jnp.asarray(pts), jnp.asarray(dirs))
        act_ranges = jnp.asarray(
            [round_range(float(taps[n][0]), float(taps[n][1]),
                         cfg["act_range_sig_bits"])
             for n in ngp_linear_names(ncfg)], jnp.float32)
        lap("calibrate")
        occ = bake_occupancy_cached(
            params, ncfg, resolution=cfg["occ_resolution"],
            threshold=cfg["occ_threshold"], supersample=cfg["occ_supersample"],
            dilate=cfg["occ_dilate"])
    lap("bake")
    bits = policy_bits(cfg)
    policy = QuantPolicy.uniform(make_quant_units(ncfg), 8).with_bits(bits)
    spec = dataclasses.replace(spec_from_policy(ncfg, policy, act_ranges),
                               paper_exact=cfg["policy"]["paper_exact"])
    pack = repack_fused_pack(build_fused_pack(params, ncfg, spec, layout="planar"),
                             "tile:128")
    art = QuantArtifact(
        scene=cfg["scene"], bits=bits, cfg=ncfg, rcfg=rcfg, scene_cfg={},
        params=params, act_ranges=act_ranges, pack=pack, occ=occ,
        hardware={}, metrics={})
    jax.block_until_ready(pack)
    lap("pack")
    return Field(params=params, artifact=art, calib_pts=pts, calib_dirs=dirs)


def round_range(lo: float, hi: float, sig_bits: int):
    """The configuration's calibration rule: the min/max range rounded
    outward to `sig_bits` significant bits of its larger endpoint."""
    m, e = math.frexp(max(abs(lo), abs(hi), 1e-30))  # top = m * 2^e
    step = 2.0 ** ((e - 1 if m == 0.5 else e) - sig_bits)
    return [math.floor(lo / step) * step, math.ceil(hi / step) * step]


class Stream:
    """One client's frames: poses, their rays, and what was served.
    Clients submit a frame's pixels center first, as a foveated client
    wants the center of its view first (and so the first work items of a
    window hold geometry, not empty sky)."""

    def __init__(self, poses, hw: int, focal: float):
        self.poses, self.hw, self.focal = poses, hw, focal
        self.order = generate.center_out(hw)
        self.next = 0
        self.done: Dict[int, np.ndarray] = {}  # frame -> served colors

    def rays(self, i: int):
        """Frame i's rays in the order the client submits them."""
        o, d = generate.camera_rays(self.poses[i], self.hw, self.focal)
        return o[self.order], d[self.order]


# The settled budget is rounded up to this many samples, so that every
# seed compiles the same few programs.
BUDGET_ALIGN = 4096


def settle_budget(occ, streams: List[Stream], frames: int, slot_rays: int,
                  render: Dict, headroom: float) -> Dict:
    """The sample budget for these frames: the engine's own growth rule
    (worst slot x headroom, capped) applied to the worst slot, counted
    by the benchmark's occupancy oracle, and rounded up to
    `BUDGET_ALIGN` samples."""
    worst = 0
    for s in streams:
        for i in range(frames):
            ro, rd = s.rays(i)
            c = work.active_samples(occ, ro.reshape(-1, slot_rays, 3),
                                    rd.reshape(-1, slot_rays, 3), render)
            worst = max(worst, int(c.max()))
    cap = slot_rays * render["n_samples"]
    budget = int(math.ceil(worst * headroom / BUDGET_ALIGN) * BUDGET_ALIGN)
    return {"worst_slot": worst, "budget": int(np.clip(budget, BUDGET_ALIGN, cap))}


class Window:
    """The closed loop: clients keep `in_flight` frames queued; the
    engine's single-scene FIFO renders their work items in order."""

    def __init__(self, engine, scene: str, streams: List[Stream], mix: Dict,
                 spans: common.Spans):
        self.engine, self.scene, self.streams = engine, scene, streams
        self.mix, self.spans = mix, spans
        self.items_per_frame = -(-mix["image_hw"] ** 2 // mix["slot_rays"])
        self.queue: deque = deque()  # (client, frame, seq) not yet rendered
        self.frames: deque = deque()  # (client, frame, rid) in submit order
        self.left: Dict[int, int] = {}  # rid -> items not yet rendered
        self.rendered: List[tuple] = []  # (client, frame, seq) in order
        self.live: Dict[tuple, int] = {}  # (client, frame) -> rid

    def submit(self, c: int) -> None:
        s = self.streams[c]
        if s.next >= len(s.poses):
            raise RuntimeError(
                f"client {c} ran out of frames ({len(s.poses)}): raise "
                "`frames` in the traffic mix")
        i = s.next
        s.next += 1
        with self.spans("bench.traffic"):
            ro, rd = s.rays(i)
        with self.spans("bench.submit"):
            rid = self.engine.submit(ro, rd, scene=self.scene)
        self.frames.append((c, i, rid))
        self.left[rid] = self.items_per_frame
        self.live[(c, i)] = rid
        self.queue.extend((c, i, q) for q in range(self.items_per_frame))

    def run(self, seconds: float) -> float:
        """Serve until `seconds` have passed; returns the window's
        length, from the first submit to the end of the last step."""
        t0 = time.perf_counter()
        for c in range(len(self.streams)):
            for _ in range(self.mix["in_flight"]):
                self.submit(c)
        while True:
            with self.spans("bench.step"):
                k = self.engine.step()
            for _ in range(k):
                c, i, q = self.queue.popleft()
                self.rendered.append((c, i, q))
                self.left[self.live[(c, i)]] -= 1
            while self.frames and self.left[self.frames[0][2]] == 0:
                c, i, rid = self.frames.popleft()
                with self.spans("bench.result"):
                    self.streams[c].done[i] = self.engine.result(rid)
                del self.live[(c, i)]
                self.submit(c)
            if time.perf_counter() - t0 >= seconds:
                return time.perf_counter() - t0

    def served(self, item: tuple) -> np.ndarray:
        """Colors the engine returned for one rendered work item."""
        c, i, q = item
        R = self.mix["slot_rays"]
        s = self.streams[c]
        if i in s.done:
            colors = s.done[i]
        else:
            colors, done = self.engine.partial(self.live[(c, i)])
            assert done[q * R:(q + 1) * R].all(), item
        return colors[q * R:(q + 1) * R]


# A ray is "off" where its largest channel differs from the reference by
# more than this: 1/39 of one 8-bit color level, well above float rounding
# (about 1e-6 on these colors) and below any changed quantization decision.
RAY_OFF = 1e-4


def gap_numbers(got: np.ndarray, want: np.ndarray) -> Dict:
    """Ray-by-ray comparison of (N, 3) colors: the gap of a ray is its
    largest channel difference."""
    gap = np.abs(got.astype(np.float64) - want).max(axis=1)
    p50, p90, p99 = np.percentile(gap, [50, 90, 99])
    return {
        "color_gap_mean": float(gap.mean()),
        "color_gap_max": float(gap.max()),
        "color_gap_p50": float(p50),
        "color_gap_p90": float(p90),
        "color_gap_p99": float(p99),
        "rays_off_1e-5": float(np.mean(gap > 1e-5)),
        "rays_off_share": float(np.mean(gap > RAY_OFF)),
        "rays_off_1_255": float(np.mean(gap > 1.0 / 255.0)),
    }


def sampled_rays(win: "Window", items: List[tuple]):
    R = win.mix["slot_rays"]
    ro, rd, got = [], [], []
    for it in items:
        c, i, q = it
        o, d = win.streams[c].rays(i)
        ro.append(o[q * R:(q + 1) * R])
        rd.append(d[q * R:(q + 1) * R])
        got.append(win.served(it))
    return np.concatenate(ro), np.concatenate(rd), np.concatenate(got)


def run(cell: Dict, cfg: Dict, mix: Dict, seed: int, seconds: float,
        trace: bool, clock: common.CompileClock, t_start: float,
        fault=None, controls=()) -> Dict:
    """One run of a frames cell. `fault` (tests only) breaks the timed
    path underneath: it is called with the engine before the window.
    `controls` (the control measurement only) are reference precisions
    put in the program's place and compared the same way."""
    import jax

    from repro import hero
    from repro.hero import ServeConfig
    from repro.kernels import ops

    spans = common.Spans()
    phases: Dict[str, float] = {}
    field = build_field(cfg, seed, phases)
    t = time.perf_counter()
    art = field.artifact
    hw, focal = mix["image_hw"], mix["focal_mult"] * mix["image_hw"]
    poses = generate.client_poses(mix, seed, mix["frames"])
    streams = [Stream(p, hw, focal) for p in poses]
    render = {k: cfg[k] for k in ("near", "far", "n_samples")}
    scfg = ServeConfig(slots=mix["slots"], slot_rays=mix["slot_rays"])
    settled = settle_budget(art.occ.occ, streams, mix["frames"],
                            mix["slot_rays"], render, scfg.budget_headroom)
    scfg = dataclasses.replace(scfg, budget=settled["budget"])
    phases["budget"] = time.perf_counter() - t
    t = time.perf_counter()
    svc = hero.serve(art, scfg)
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
            "setup_s": setup_s, "phases_s": phases, "sample_budget": settled,
            "setup_compile": before,
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
        run_ctx = _trace_context(cell, cfg, mix, win, art, stats, window_s,
                                 settled["budget"], prof_dir, device)

    # The reference runs after the program's serve state is dropped.
    rng = np.random.default_rng(generate.seed_words(seed, 4, 5))
    n = min(mix["check_items"], len(win.rendered))
    pick = sorted(rng.choice(len(win.rendered), size=n, replace=False))
    items = [win.rendered[j] for j in pick]
    served = {it: win.served(it) for it in items}
    win.served = lambda it: served[it]
    win.engine = engine = svc = field.artifact = art = None
    gc.collect()
    t = time.perf_counter()
    ref_mod = common.reference_module(cfg)
    ref = ref_mod.Reference(field.params, cfg, field.calib_pts, field.calib_dirs)
    ro, rd, got = sampled_rays(win, items)
    want = ref.render(ro, rd)
    out["check"] = {"rays_compared": int(got.shape[0]),
                    "numbers": gap_numbers(got, want),
                    "seconds": time.perf_counter() - t}
    out["controls"] = {
        p: gap_numbers(ref_mod.Reference(field.params, cfg, field.calib_pts,
                                         field.calib_dirs, precision=p)
                       .render(ro, rd), want)
        for p in controls}
    out["trace_ctx"] = run_ctx
    return out


def _trace_context(cell, cfg, mix, win, art, stats, window_s, budget,
                   prof_dir, device):
    """What the per-layer readers read in a traced run."""
    import glob

    from bench import trace as tr

    files = sorted(glob.glob(str(prof_dir / "plugins/profile/*/*.xplane.pb")))
    red = tr.reduce_events(tr.read_xplane(files[-1]))
    R = mix["slot_rays"]
    ro, rd = [], []
    by_frame: Dict[tuple, tuple] = {}
    for c, i, q in win.rendered:
        if (c, i) not in by_frame:
            by_frame[(c, i)] = win.streams[c].rays(i)
        o, d = by_frame[(c, i)]
        ro.append(o[q * R:(q + 1) * R])
        rd.append(d[q * R:(q + 1) * R])
    render = {k: cfg[k] for k in ("near", "far", "n_samples")}
    active = work.active_samples(art.occ.occ, np.stack(ro), np.stack(rd), render)
    return {
        "reduction": red, "stats": stats, "window_s": window_s,
        "slots": len(win.rendered), "budget": budget,
        "active_samples": int(active.sum()), "model": cfg["model"],
        "render": render, "mix": mix, "occ_resolution": cfg["occ_resolution"],
        "peaks": work.peaks(device["kind"]) if device["platform"] == "tpu" else None,
    }
