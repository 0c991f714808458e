#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in `BENCHMARK.json`; its configuration is
`bench/configs/<config>.json`, its traffic `bench/traffic/<mix>.json`,
whose `kind` names the module that drives it (`bench/<kind>.py`), and
each metric is read by `bench/metrics/<metric>.py`. The
last line of standard output is one JSON object (`correct`, `attempted`,
`failed`, `metrics`, `device`, and with `--trace 1` `breakdown`, then
`check`: each compared number beside its limit). With `--trace 0` the
metrics are the cell's end-to-end metrics, with `--trace 1` its
per-layer metrics, read from a profiler trace of the window.

Without a TPU, with fewer chips than the cell asks for, or without the
repository's `src/` beside `bench/`, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def fail(msg: str, code: int) -> int:
    print(f"[bench] FAIL: {msg}", file=sys.stderr)
    return code


def cell_entry(manifest, name: str):
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(manifest, cell, trace: bool):
    """The `BENCHMARK.json` entries of the metrics this cell reports: its
    end-to-end metrics, or with `trace` the per-layer metrics that move
    one of them and list the cell."""
    e2e = [m for m in manifest["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if m["moves"] in moved
            and cell["name"] in m.get("workloads", [cell["name"]])]


def result_line(manifest, cell, out, trace: bool, limits) -> dict:
    from bench import common

    metrics = {}
    for m in metrics_of(manifest, cell, trace):
        v = common.read_metric(m["name"], out["trace_ctx"] if trace else out)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    nums = out["check"]["numbers"]
    check = {k: {"value": nums[k], "limit": lim} for k, lim in limits.items()}
    device = dict(out["device"])
    line = {
        "correct": all(c["value"] <= c["limit"] for c in check.values()),
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": metrics,
        "device": device,
    }
    if trace:
        red = out["trace_ctx"]["reduction"]
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        line["breakdown"] = red.breakdown()
    line["check"] = check
    return line


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, fault=None, cfg_override=None,
             mix_override=None):
    """Run one cell; returns (result line, the run's full output).
    `require_tpu=False`, `fault`, and the overrides are for the tests."""
    from bench import common
    from bench.traffic import generate
    from repro.kernels.backend import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clock = common.CompileClock()
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = cell_entry(manifest, name)
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(fail(f"no TPU found (JAX reports {devs[0].platform!r})", 3))
    if require_tpu and len(devs) < cell["chips"]:
        raise SystemExit(fail(f"cell needs {cell['chips']} chips, JAX sees {len(devs)}", 3))
    cfg = cfg_override or common.load_config(cell["config"])
    mix = mix_override or generate.load_mix(cell["traffic"])
    driver = importlib.import_module(f"bench.{mix['kind']}")
    out = driver.run(cell, cfg, mix, seed, seconds, trace, clock, T_START,
                     fault=fault)
    return result_line(manifest, cell, out, trace, cfg["correct"]), out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        return fail("--seed must be a non-negative integer", 2)
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"the repository's src/repro is not beside {Path(__file__).parent}", 2)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))

    line, out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    print("[bench] setup " + json.dumps(out["setup"], default=float), flush=True)
    print("[bench] check " + json.dumps(out["check"]), flush=True)
    for k, c in line["check"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
