"""Device milliseconds per slot rendered in the proposal passes: the
device time of `jit__slot_propose_impl` in the trace, over the slots
rendered."""

PROGRAM = r"_slot_propose_impl"


def read(run):
    n, seconds = run["reduction"].program(PROGRAM)
    if n == 0 or run["slots"] == 0:
        return None
    return 1e3 * seconds / run["slots"]
