"""The program's own count of the renderer's sample fill: the active
samples its march slots report (`render.active_samples`, the true count
each slot already fetches) over the sample budget computed for them
(`render.budget_samples`): the in-program twin of `render.sample_fill`."""


def read(run):
    counters = run["stats"].get("trace", {}).get("counters", {})
    budget = counters.get("render.budget_samples", 0)
    if budget == 0:
        return None
    return 100.0 * counters.get("render.active_samples", 0) / budget
