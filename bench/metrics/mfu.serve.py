"""The whole serve step's share of the chip's int8 peak (the precision the
quantized linears run in): field operations per occupancy-active sample
(`bench/work.py`) times the active samples served in the window, over the
window, over the peak."""

from bench import work


def read(run):
    if run["peaks"] is None or run["active_samples"] == 0:
        return None
    ops = work.field_ops_per_sample(run["model"]) * run["active_samples"]
    return 100.0 * ops / run["window_s"] / run["peaks"]["int8_ops"]
