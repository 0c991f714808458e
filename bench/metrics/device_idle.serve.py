"""Share of the traced window in which no operation ran on the device."""


def read(run):
    red = run["reduction"]
    if red.n_devices == 0 or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
