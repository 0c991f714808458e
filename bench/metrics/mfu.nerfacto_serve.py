"""The whole Nerfacto serve step's share of the chip's int8 peak (the
precision the quantized linears run in): the program's counts of
proposal samples (`render.proposal_samples`) and shaded samples
(`render.shade_samples`) in the window, each times its field operations
per sample (`bench/nerfacto_work.py`), over the window, over the peak."""

from bench import nerfacto_work


def read(run):
    counters = run["stats"].get("trace", {}).get("counters", {})
    prop = counters.get("render.proposal_samples", 0)
    shade = counters.get("render.shade_samples", 0)
    if run["peaks"] is None or prop + shade == 0:
        return None
    m = run["model"]
    ops = (prop * nerfacto_work.proposal_ops_per_sample(m)
           + shade * nerfacto_work.shade_ops_per_sample(m))
    return 100.0 * ops / run["window_s"] / run["peaks"]["int8_ops"]
