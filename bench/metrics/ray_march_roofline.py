"""The occupancy ray-march kernel's share of its roofline: the least time
for the bytes each call must move (a slot's rays in, the grid once, the
(rays, samples) mask out; `bench/work.py`) at the chip's HBM bandwidth,
over the kernel's device time in the trace."""

from bench import work

KERNEL = r"ray_march"


def read(run):
    n, seconds = run["reduction"].kernel(KERNEL)
    if n == 0 or seconds <= 0 or run["peaks"] is None:
        return None
    per_call = work.ray_march_bytes(run["mix"]["slot_rays"],
                                    run["render"]["n_samples"],
                                    run["occ_resolution"])
    least = n * per_call / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
