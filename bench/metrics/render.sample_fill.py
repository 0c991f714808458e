"""Share of the samples the renderer computed that were occupancy-active:
active samples of the window's slots (the benchmark's occupancy oracle)
over the sample budget times the slots rendered."""


def read(run):
    computed = run["budget"] * run["slots"]
    if computed == 0:
        return None
    return 100.0 * run["active_samples"] / computed
