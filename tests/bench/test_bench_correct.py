"""`correct` of a frames cell, driven end to end at a tiny size on the CPU
with the look for a chip skipped: a sound run is correct; a run whose
timed path is broken underneath is not, for each fault a serve cell can
have; and the control (the plain reference computed in the precision
below the configuration's, put in the program's place) fails the limit.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

CELL = "ngp19.fresh800"
SEED = 2 ** 33 + 77


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch, tmp_path):
    # With the variable set, the harness leaves JAX's cache as it is
    # (off in a test process), and writes nothing into the checkout.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def tiny():
    from bench import common
    from bench.traffic import generate

    cfg = common.load_config("ngp-paper-t19")
    # All 16 levels and 32 samples a ray: the control's table rounding
    # then reaches enough activation codes to show at this size.
    cfg["model"].update(log2_table_size=9, base_resolution=4,
                        max_resolution=32, hidden_dim=16, color_hidden_dim=16)
    cfg.update(image_hw=16, n_train_views=3, train_steps=10)
    mix = generate.load_mix("fresh_frames")
    mix.update(image_hw=32, frames=400, check_items=8)
    return cfg, mix


def run(fault=None, controls=()):
    from bench import run as bench_run

    cfg, mix = tiny()
    if controls:
        from bench import common, serve

        out = serve.run({"name": CELL, "chips": 1}, cfg, mix, SEED, 0.5, False,
                        common.CompileClock(), 0.0, controls=controls)
        return out, cfg["correct"]
    line, _ = bench_run.run_cell(CELL, SEED, 0.5, False, require_tpu=False,
                                 fault=fault, cfg_override=cfg, mix_override=mix)
    return line


def break_step(engine, how):
    """Wrap the engine's device step so that what it returns is wrong."""
    stepper = engine._stepper
    real = stepper.step_items
    last = {}

    def broken(scene, artifact, items, ro, rd):
        colors = np.array(real(scene, artifact, items, ro, rd))
        if how == "half_left_out":  # half of each slot's rays never rendered
            colors[:, colors.shape[1] // 2:] = 0.0
        elif how == "answer_altered":  # one 8-bit color level, where produced
            colors[..., 0] += 1.0 / 255.0
        elif how == "state_unchanged":  # the step hands back its last output
            stale = last.get("colors", np.zeros_like(colors))
            last["colors"] = colors
            colors = stale
        return colors

    stepper.step_items = broken


def test_sound_run_is_correct():
    line = run()
    assert line["correct"] is True, line["check"]
    assert list(line)[-1] == "check"
    assert set(line["metrics"]) == {"rays_per_s", "setup_s"}


@pytest.mark.parametrize("how", ["half_left_out", "answer_altered",
                                 "state_unchanged"])
def test_broken_timed_path_is_not_correct(how):
    line = run(fault=lambda engine: break_step(engine, how))
    assert line["correct"] is False, (how, line["check"])


def test_control_fails_the_limit():
    out, limits = run(controls=("high",))
    prog = out["check"]["numbers"]
    ctrl = out["controls"]["high"]
    for k, lim in limits.items():
        assert prog[k] <= lim
    assert any(ctrl[k] > lim for k, lim in limits.items()), (ctrl, limits)
