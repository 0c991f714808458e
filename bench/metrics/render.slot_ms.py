"""Device milliseconds per slot rendered: the device time of the
renderer's per-slot programs (`jit__slot_march_impl`, `jit__slot_warp_impl`,
`jit__slot_plan_impl`) in the trace, over the slots rendered."""

PROGRAMS = r"_slot_(march|warp|plan)_impl"


def read(run):
    n, seconds = run["reduction"].program(PROGRAMS)
    if n == 0 or run["slots"] == 0:
        return None
    return 1e3 * seconds / run["slots"]
