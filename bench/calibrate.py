#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10 \\
        [--controls high,bfloat16] [--trace-seed S]

For each seed: one run of the cell with a short window at the cell's own
load (the same set-up, engine and sample of served work items as a
benchmark run), printing the compared numbers of the program against the
configuration's plain reference and, for each control precision, of the
reference computed in that precision put in the program's place. Seeds
run in one process, so set-up compiles once. One JSON line per seed, the
last line a summary; lines also go to `chiprun_out/calibrate-<cell>.jsonl`.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def write_slice(name: str, path: Path, start_s: float = 1.0,
                length_s: float = 0.06) -> None:
    """A short slice of the traced window (device events and `bench.*`
    host spans, names cut to 200 characters) with its reduction: the
    recorded fixture of the trace reduction's test."""
    import glob

    from bench import common
    from bench import trace as tr

    pb = sorted(glob.glob(str(common.OUT / "trace" / name
                              / "plugins/profile/*/*.xplane.pb")))[-1]
    events = tr.read_xplane(pb)
    win = next(e for e in events if e.name == tr.WINDOW_SPAN)
    lo = win.start_ns + start_s * 1e9
    hi = lo + length_s * 1e9
    keep = [tr.Event(e.plane, e.line, e.name[:200], e.start_ns - lo, e.dur_ns)
            for e in events
            if tr.is_device_plane(e.plane) and e.line in (tr.OP_LINE, tr.MODULE_LINE)
            and e.start_ns >= lo and e.end_ns <= hi]
    for e in events:
        if (not tr.is_device_plane(e.plane) and e.name.startswith(tr.SPAN_PREFIX)
                and e.name != tr.WINDOW_SPAN and e.end_ns > lo and e.start_ns < hi):
            s, t = max(e.start_ns, lo), min(e.end_ns, hi)
            keep.append(tr.Event(e.plane, e.line, e.name, s - lo, t - s))
    keep.append(tr.Event(win.plane, win.line, tr.WINDOW_SPAN, 0.0, hi - lo))
    red = tr.reduce_events(keep)
    march = red.program(r"_slot_march_impl")
    hg, rm = red.kernel(r"hash_gather"), red.kernel(r"ray_march")
    path.write_text(json.dumps({
        "source": f"{name}, {length_s * 1e3:.0f} ms of a traced window, "
                  f"from {start_s} s after its start",
        "events": [list(dataclasses.astuple(e)) for e in keep],
        "expected": {"window_s": red.window_s, "busy_s": red.busy_s,
                     "march_programs": march[0], "march_s": march[1],
                     "hash_gather_ops": hg[0], "hash_gather_s": hg[1],
                     "ray_march_ops": rm[0], "ray_march_s": rm[1]},
    }, indent=1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--controls", default="high,bfloat16")
    ap.add_argument("--trace-seed", type=int, default=None)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="run the controls on the first N seeds only")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import common, serve
    from bench.run import cell_entry
    from bench.traffic import generate
    from repro.kernels.backend import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("[calibrate] FAIL: no TPU", file=sys.stderr)
        return 3
    clock = common.CompileClock()
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = cell_entry(manifest, args.workload)
    cfg = common.load_config(cell["config"])
    mix = generate.load_mix(cell["traffic"])
    controls = tuple(c for c in args.controls.split(",") if c)
    dest = ROOT / "chiprun_out" / f"calibrate-{cell['name']}.jsonl"
    dest.parent.mkdir(exist_ok=True)
    rows = []
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        trace = seed == args.trace_seed
        out = serve.run(cell, cfg, mix, seed, args.seconds, trace, clock, t0,
                        controls=controls if k < args.control_seeds else ())
        row = {"seed": seed, "program": out["check"]["numbers"],
               "controls": out["controls"], "rays_compared": out["check"]["rays_compared"],
               "setup": out["setup"], "window_s": out["window_s"],
               "rays": out["rays"], "rays_per_s": out["rays"] / out["window_s"],
               "check_s": out["check"]["seconds"],
               "device": out["device"], "wall_s": time.perf_counter() - t0}
        if trace:
            red = out["trace_ctx"]["reduction"]
            row["trace"] = {
                "busy_s": red.busy_s, "window_s": red.window_s,
                "programs": red.programs,
                "ops": dict(sorted(red.ops.items(), key=lambda kv: -kv[1][1])[:40]),
                "gaps": red.gap_by_span,
                "metrics": {m["name"]: common.read_metric(m["name"], out["trace_ctx"])
                            for m in manifest["per_layer"]
                            if cell["name"] in m.get("workloads", [cell["name"]])},
            }
            write_slice(cell["name"], dest.parent / f"slice-{cell['name']}.json")
        rows.append(row)
        line = json.dumps(row, default=float)
        print(line, flush=True)
        with dest.open("a") as f:
            f.write(line + "\n")
    summary = {"program_max": {k: max(r["program"][k] for r in rows)
                               for k in rows[0]["program"]},
               "control_min": {p: {k: min(r["controls"][p][k] for r in rows
                                       if p in r["controls"])
                                   for k in rows[0]["program"]} for p in controls}}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
