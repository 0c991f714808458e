"""Host milliseconds per rendered slot that the engine spends outside its
device wait: the program's `engine.step` span total less its
`render.wait` span total (`repro.obs` aggregates in the engine's stats),
over the slots rendered."""


def read(run):
    spans = run["stats"].get("trace", {}).get("spans", {})
    if "engine.step" not in spans or run["slots"] == 0:
        return None
    wait = spans.get("render.wait", {}).get("total_s", 0.0)
    return 1e3 * (spans["engine.step"]["total_s"] - wait) / run["slots"]
