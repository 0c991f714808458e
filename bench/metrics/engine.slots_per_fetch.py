"""Slots rendered per blocking device-to-host fetch of the engine: the
slots the window rendered over the program's `render.fetches` counter
(one per blocking fetch in `FusedDeviceStep.step_items`, overflow
re-renders included). A step that dispatches every slot before its one
fetch reads its live slots, 4 in a full bucket; a program without the
counter reads nothing."""


def read(run):
    counters = run["stats"].get("trace", {}).get("counters", {})
    fetches = counters.get("render.fetches", 0)
    if fetches == 0:
        return None
    return run["slots"] / fetches
