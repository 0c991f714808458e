"""Reduction of a JAX profiler trace (`.xplane.pb`) to the numbers the
benchmark reports: device busy and idle time over the traced window,
device time by jitted program and by kernel, and the longest idle gaps,
each named by the benchmark's host span that covers it.

Two stages, so that the reduction is checked on a small recorded trace:
`read_xplane` turns the file into plain `Event` tuples; `reduce_events`
works on those alone.

Device planes are those named `/device:<KIND>:<n>`. On a TPU their
`XLA Modules` line holds one event per jitted program run and their
`XLA Ops` line one event per operation (a Pallas kernel is one op, named
after its kernel function). Busy time is the union of the op intervals
(module intervals where a plane has no op line). Host spans are the
`bench.*` `TraceAnnotation` events on the host plane; the window is the
`bench.window` span.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def read_xplane(path) -> List[Event]:
    """Every timed event of every plane in one `.xplane.pb` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def program_name(event_name: str) -> str:
    """`jit__slot_march_impl(42)` -> `jit__slot_march_impl`."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _matching(table: Dict[str, Tuple[int, float]], pattern: str):
    hits = [v for name, v in table.items() if re.search(pattern, name)]
    return sum(c for c, _ in hits), sum(s for _, s in hits)


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float  # averaged over the device planes
    n_devices: int
    programs: Dict[str, Tuple[int, float]]  # name -> (count, seconds)
    ops: Dict[str, Tuple[int, float]]  # op name -> (count, seconds)
    gaps: List[Tuple[str, float]]  # (host span, seconds), longest first
    gap_by_span: Dict[str, float]  # host span -> idle seconds

    def kernel(self, pattern: str) -> Tuple[int, float]:
        """(count, seconds) summed over ops whose name matches `pattern`
        (a regular expression searched in the op name)."""
        return _matching(self.ops, pattern)

    def program(self, pattern: str) -> Tuple[int, float]:
        """The same over jitted programs."""
        return _matching(self.programs, pattern)

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        """The device ops that took most time (named by their HLO name,
        the text before ` = `) and the longest idle gaps."""
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:top]
        return {
            "device_ops": [[n.split(" = ")[0].lstrip("%"), s]
                           for n, (_, s) in ops],
            "idle_gaps": [[n, s] for n, s in self.gaps[:top]],
        }


def reduce_events(events: Sequence[Event],
                  window: Optional[Tuple[float, float]] = None) -> Reduction:
    """Busy/idle, per-program and per-op device time, and idle gaps named
    by host spans, inside `window` (ns; default: the `bench.window`
    span, else the extent of the device events)."""
    spans = [e for e in events
             if not is_device_plane(e.plane) and e.name.startswith(SPAN_PREFIX)]
    if window is None:
        win = [e for e in spans if e.name == WINDOW_SPAN]
        if win:
            window = (win[0].start_ns, win[0].end_ns)
    dev = [e for e in events if is_device_plane(e.plane)]
    if window is None:
        if not dev:
            raise ValueError("trace has neither a window span nor device events")
        window = (min(e.start_ns for e in dev), max(e.end_ns for e in dev))
    lo, hi = window

    planes = sorted({e.plane for e in dev})
    busy_total = 0.0
    busy_planes = []
    programs: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    ops: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for plane in planes:
        evs = [e for e in dev if e.plane == plane]
        op_evs = [e for e in evs if e.line == OP_LINE]
        mod_evs = [e for e in evs if e.line == MODULE_LINE]
        basis = op_evs or mod_evs
        busy = _union(_clip([(e.start_ns, e.end_ns) for e in basis], lo, hi))
        busy_planes.append(busy)
        busy_total += sum(e - s for s, e in busy)
        for e in mod_evs:
            if e.start_ns >= lo and e.end_ns <= hi:
                p = programs[program_name(e.name)]
                p[0] += 1
                p[1] += e.dur_ns
        for e in op_evs:
            if e.start_ns >= lo and e.end_ns <= hi:
                o = ops[e.name]
                o[0] += 1
                o[1] += e.dur_ns

    # Idle gaps on the first device plane, each named by the innermost
    # host span that overlaps it most.
    gaps: List[Tuple[str, float]] = []
    gap_by_span: Dict[str, float] = defaultdict(float)
    if busy_planes:
        busy = busy_planes[0]
        edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
        inner = [e for e in spans if e.name != WINDOW_SPAN]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            best, best_ov, best_dur = "other", 0.0, float("inf")
            for sp in inner:
                ov = min(e, sp.end_ns) - max(s, sp.start_ns)
                # Ties go to the shorter (innermost) span.
                if ov > best_ov or (ov == best_ov > 0
                                    and sp.dur_ns < best_dur):
                    best, best_ov, best_dur = sp.name, ov, sp.dur_ns
            gaps.append((best, (e - s) / 1e9))
            gap_by_span[best] += (e - s) / 1e9
        gaps.sort(key=lambda g: -g[1])

    n = max(len(planes), 1)
    return Reduction(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_total / n / 1e9,
        n_devices=len(planes),
        programs={k: (int(c), s / 1e9) for k, (c, s) in programs.items()},
        ops={k: (int(c), s / 1e9) for k, (c, s) in ops.items()},
        gaps=gaps,
        gap_by_span=dict(gap_by_span),
    )
