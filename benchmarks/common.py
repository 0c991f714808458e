"""Shared experiment substrate for the paper-table benchmarks.

One `run_scene_level()` call produces every method's numbers for a
(scene, operating-level) cell — NGP full precision, NGP-PTQ, NGP-QAT,
NGP-CAQ (proxy), HERO — and caches them as JSON under experiments/ so
table2 / table3 / fig4 render from the same run.

Scales (CPU-feasible; PSNR deltas between methods are the reproduction
target, DESIGN.md §6):
  quick    — smoke scale, minutes (CI)
  standard — default for bench_output.txt
"""
from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path
from typing import Dict, Optional

from repro.kernels.backend import enable_compile_cache

# Every benchmark imports this module before its first compile.
enable_compile_cache()

from repro.core import EnvConfig, NGPQuantEnv, SearchConfig, hero_search
from repro.core.baselines import caq_proxy_baseline, ptq_baseline, qat_baseline
from repro.core.ddpg import DDPGConfig
from repro.hwsim import HWConfig
from repro.nerf.dataset import make_dataset
from repro.nerf.hash_encoding import HashEncodingConfig
from repro.nerf.ngp import NGPConfig
from repro.nerf.render import RenderConfig
from repro.nerf.scenes import SceneConfig
from repro.nerf.train import TrainConfig, train_ngp

SCENES = ("chair", "lego", "ficus")
RESULTS_DIR = Path("experiments/ngp_tables")


def runner_block() -> Dict:
    """The runner fingerprint every BENCH_*.json embeds under "runner".

    Machine-dependent throughput numbers are only comparable on the same
    kernel backend + device; the regression gates refuse to compare
    reports whose fingerprints differ (`refuse_backend_mismatch`)."""
    from repro.kernels.backend import runner_fingerprint

    return runner_fingerprint()


def refuse_backend_mismatch(report: Dict, base: Dict, label: str) -> bool:
    """True when `report` and `base` came from comparable runners.

    Prints the refusal (and the fix: refresh the committed baseline on
    THIS runner) when they did not — the caller must fail its gate, not
    fall through to a meaningless number comparison."""
    import sys

    from repro.kernels.backend import fingerprint_mismatch

    why = fingerprint_mismatch(base.get("runner"), report.get("runner"))
    if why:
        print(f"[{label}] BASELINE NOT COMPARABLE: {why}. Refusing the "
              f"regression comparison — refresh the committed baseline "
              f"from a run on this runner.", file=sys.stderr)
        return False
    return True


@dataclasses.dataclass(frozen=True)
class BenchScale:
    name: str
    image_hw: int
    n_train_views: int
    n_test_views: int
    n_levels: int
    log2_table: int
    max_res: int
    hidden: int
    n_samples: int
    train_steps: int
    finetune_steps: int
    episodes: int
    trace_rays: int


SCALES = {
    "quick": BenchScale("quick", 24, 5, 2, 4, 9, 32, 16, 16, 120, 12, 6, 256),
    "standard": BenchScale(
        "standard", 32, 8, 2, 8, 11, 64, 32, 24, 300, 25, 14, 512
    ),
}


def build_env(
    scene: str, scale: BenchScale, latency_target=None, seed=0,
    render_backend: str = "fused",
):
    ds = make_dataset(SceneConfig(
        name=scene, image_hw=scale.image_hw,
        n_train_views=scale.n_train_views, n_test_views=scale.n_test_views,
    ))
    cfg = NGPConfig(
        hash=HashEncodingConfig(
            n_levels=scale.n_levels, log2_table_size=scale.log2_table,
            base_resolution=4, max_resolution=scale.max_res,
        ),
        hidden_dim=scale.hidden, color_hidden_dim=scale.hidden,
        geo_feat_dim=15, sh_degree=3,
    )
    rcfg = RenderConfig(n_samples=scale.n_samples)
    tcfg = TrainConfig(steps=scale.train_steps, batch_rays=512, lr=5e-3)
    params, _ = train_ngp(ds, cfg, rcfg, tcfg)
    env = NGPQuantEnv(
        params, ds, cfg, rcfg, tcfg,
        EnvConfig(
            finetune_steps=scale.finetune_steps,
            trace_rays=scale.trace_rays,
            latency_target=latency_target,
            render_backend=render_backend,
        ),
        HWConfig(coarse_levels=min(8, scale.n_levels // 2)),
        seed=seed,
    )
    # Full-precision anchor through the same engine every method uses
    # (occupancy-culled fused when render_backend="fused").
    fp_psnr = env.eval_psnr(params, None)
    return env, fp_psnr


def run_scene_level(
    scene: str,
    level: str,  # "MDL" | "MGL"
    scale: BenchScale,
    seed: int = 0,
    verbose: bool = True,
) -> Dict:
    """All methods for one (scene, level). Caches to JSON."""
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    cache = RESULTS_DIR / f"{scene}_{level}_{scale.name}.json"
    if cache.exists():
        return json.loads(cache.read_text())

    t0 = time.time()
    # Operating points (paper Sec. IV-A): uniform 6-bit at MDL / 5-bit at
    # MGL for PTQ & QAT; HERO gets a latency target tied to the level.
    uniform_bits = 6 if level == "MDL" else 5
    env, fp_psnr = build_env(scene, scale, seed=seed)

    # HERO's latency target: MDL = PTQ-uniform latency (high fidelity at
    # lower-or-equal cost); MGL = 85% of it (resource constrained). The
    # budget is per-call search state, not env state.
    ptq = ptq_baseline(env, uniform_bits)
    target = ptq.latency_cycles * (1.0 if level == "MDL" else 0.85)

    qat = qat_baseline(env, uniform_bits)
    caq = caq_proxy_baseline(
        env, mode=level, target_loss=10 ** (-3.2),
    )
    hero = hero_search(
        env,
        SearchConfig(n_episodes=scale.episodes, verbose=verbose, seed=seed),
        DDPGConfig(warmup_episodes=max(2, scale.episodes // 4),
                   updates_per_episode=16, seed=seed),
        latency_target=target,
    )
    hb = hero.best

    def row(name, psnr, lat, fqr, mbytes, bits=None):
        return {
            "name": name, "psnr": psnr, "latency_cycles": lat,
            "fqr": fqr, "model_bytes": mbytes,
            "cost_efficiency": psnr / lat if lat else None,
            "bits": bits,
        }

    out = {
        "scene": scene, "level": level, "scale": scale.name,
        "seconds": round(time.time() - t0, 1),
        "fp_psnr": fp_psnr,
        "rows": [
            row("NGP", fp_psnr, None, 32.0, None),
            row("NGP-PTQ", ptq.psnr, ptq.latency_cycles, ptq.fqr,
                ptq.model_bytes, ptq.bits),
            row("NGP-QAT", qat.psnr, qat.latency_cycles, qat.fqr,
                qat.model_bytes, qat.bits),
            row("NGP-CAQ", caq.psnr, caq.latency_cycles, caq.fqr,
                caq.model_bytes, caq.bits),
            row("HERO", hb.psnr, hb.latency_cycles, hb.fqr,
                hb.model_bytes, hb.bits),
        ],
    }
    cache.write_text(json.dumps(out, indent=2))
    return out


def load_all(scale_name: str) -> Dict:
    out = {}
    for scene in SCENES:
        for level in ("MDL", "MGL"):
            p = RESULTS_DIR / f"{scene}_{level}_{scale_name}.json"
            if p.exists():
                out[(scene, level)] = json.loads(p.read_text())
    return out
