"""Share of the traced window in which the device is idle while the host
is inside the program's `engine.step` span: the device's idle intervals
(the first device plane, as `bench/trace.py` finds them) intersected
with those spans, on the profiler's one clock.

`idle_split` also names what the host was doing in that idle time: the
innermost program span (`engine.*`, `render.*`, `pose.*`, `host.*`)
around each idle stretch inside `engine.step`.

The reader finds this run's trace as the newest `*.xplane.pb` under the
benchmark's trace directory (`bench/serve.py` writes one per run)."""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

from bench import common
from bench import trace as tr

STEP = "engine.step"
PROGRAM_SPANS = ("engine.", "render.", "pose.", "host.")


def latest_trace():
    files = list((common.OUT / "trace").glob("**/*.xplane.pb"))
    return max(files, key=lambda p: p.stat().st_mtime) if files else None


def idle_split(events: Sequence[tr.Event]) -> Optional[Tuple[float, Dict[str, float]]]:
    """(window seconds, {innermost program span: device-idle seconds
    inside `engine.step`}) over the `bench.window` span; None where the
    trace has no window, no device plane or no `engine.step` span."""
    win = [e for e in events if e.name == tr.WINDOW_SPAN
           and not tr.is_device_plane(e.plane)]
    dev = [e for e in events if tr.is_device_plane(e.plane)]
    spans = [e for e in events if not tr.is_device_plane(e.plane)
             and e.name.startswith(PROGRAM_SPANS)]
    if not win or not dev or not any(e.name == STEP for e in spans):
        return None
    lo, hi = win[0].start_ns, win[0].end_ns
    plane = min(e.plane for e in dev)
    ops = [e for e in dev if e.plane == plane and e.line == tr.OP_LINE]
    basis = ops or [e for e in dev if e.plane == plane and e.line == tr.MODULE_LINE]
    busy = tr._union(tr._clip([(e.start_ns, e.end_ns) for e in basis], lo, hi))
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]

    # Sweep every boundary; between two, the device is idle or not, and
    # the open spans name the host's work (innermost = shortest).
    points = []
    for s, e in idle:
        points += [(s, 1, None), (e, 0, None)]
    for sp in spans:
        s, e = max(sp.start_ns, lo), min(sp.end_ns, hi)
        if e > s:
            points += [(s, 1, sp), (e, 0, sp)]
    points.sort(key=lambda p: (p[0], p[1]))
    split: Dict[str, float] = {}
    is_idle, open_spans, t_prev = False, [], lo
    for t, opening, sp in points:
        if t > t_prev and is_idle and any(o.name == STEP for o in open_spans):
            inner = min(open_spans, key=lambda o: o.dur_ns).name
            split[inner] = split.get(inner, 0.0) + (t - t_prev) / 1e9
        t_prev = t
        if sp is None:
            is_idle = bool(opening)
        elif opening:
            open_spans.append(sp)
        else:
            open_spans.remove(sp)
    return (hi - lo) / 1e9, split


def read(run):
    path = latest_trace()
    if path is None:
        return None
    got = idle_split(tr.read_xplane(path))
    if got is None:
        return None
    window_s, split = got
    return 100.0 * sum(split.values()) / window_s
