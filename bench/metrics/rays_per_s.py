"""Rays of every work item the engine returned inside the window, over
the window."""


def read(run):
    return run["rays"] / run["window_s"]
