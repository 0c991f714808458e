"""The one-hot hash gather kernel's share of its roofline: the least time
for the bytes its calls must move (indices, one F-wide row per index, the
result; from call shapes, `bench/work.py`) at the chip's HBM bandwidth,
over the kernel's device time in the trace. Each call gathers one
one-hot level for a slot's sample budget (8 corners per sample)."""

from bench import work

KERNEL = r"hash_gather"


def read(run):
    n, seconds = run["reduction"].kernel(KERNEL)
    if n == 0 or seconds <= 0 or run["peaks"] is None:
        return None
    per_call = work.gather_bytes(8 * run["budget"], run["model"]["n_features"])
    least = n * per_call / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
