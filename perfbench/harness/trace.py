"""From a profiler trace to the numbers the per-layer metrics read.

A ``--trace 1`` run records the measured window with the JAX profiler.
The benchmark's own host spans (``jax.profiler.TraceAnnotation``,
names starting ``bench.``) land in the same trace, on the same clock as
the device's operations.  This module reduces the trace to:

``window_s``
    Length of the ``bench.window`` span: the traced window.
``busy_s``
    Union of the intervals in which an operation (synchronous or
    asynchronous) ran on a device,
    clipped to the window and averaged over the devices.
``sweep_device_s`` / ``sweeps``
    Time in which an operation of the sweep ran on a device (the busy
    time inside executions of the programs whose module name holds the
    sweep's name, the jitted FD sweep), and the number of executions
    that started in the window, averaged over devices.
``device_ops``
    The ten operations with the most device time, by name.
``idle_gaps``
    The ten longest intervals of the window in which no device
    operation ran, each named by the innermost benchmark span the host
    was in at its middle (``idle`` when it was in none).

Events are read into plain tuples first (``load``), so the reduction
itself runs on the CPU over any recorded list of events: that is how
``perfbench/tests/test_trace.py`` checks it.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

OPS_LINES = ("XLA Ops", "Async XLA Ops")
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


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


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` a profiler session wrote under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(paths)}")
    return paths[0]


def load(path: str) -> List[Event]:
    """Every event of the device planes and the benchmark's host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        device = is_device(plane.name)
        for line in plane.lines:
            if device and line.name not in OPS_LINES + (MODULES_LINE,):
                continue
            for ev in line.events:
                if device or ev.name.startswith(SPAN_PREFIX):
                    # an op's event name is its whole HLO instruction;
                    # keep the instruction's name
                    name = ev.name.split(" = ", 1)[0] if device else ev.name
                    out.append(Event(plane.name, line.name, name,
                                     float(ev.start_ns),
                                     float(ev.duration_ns)))
    return out


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, disjoint cover of the given (start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _length(iv) -> float:
    return sum(e - s for s, e in iv)


def _intersect(a, b) -> List[Tuple[float, float]]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _span_at(spans: List[Event], t: float) -> str:
    """The innermost benchmark span (shortest one) covering ``t``."""
    best: Optional[Event] = None
    for sp in spans:
        if sp.start_ns <= t < sp.end_ns and (best is None
                                             or sp.dur_ns < best.dur_ns):
            best = sp
    return best.name if best is not None else "idle"


def reduce(events: List[Event], sweep_name: str) -> Dict:
    """The trace's numbers; see the module docstring."""
    windows = [e for e in events if e.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(windows)}")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    spans = [e for e in events if not is_device(e.plane)
             and e.name != WINDOW_SPAN]
    planes = sorted({e.plane for e in events if is_device(e.plane)})
    if not planes:
        raise RuntimeError("the trace holds no device plane")
    busy, sweep_s, sweeps = 0.0, 0.0, 0
    op_time: Dict[str, float] = collections.defaultdict(float)
    gaps: List[Tuple[float, float]] = []         # (length, middle)
    for plane in planes:
        ops = [e for e in events
               if e.plane == plane and e.line in OPS_LINES]
        cover = union(_clip([(e.start_ns, e.end_ns) for e in ops], lo, hi))
        busy += _length(cover)
        for e in ops:
            if lo <= e.start_ns < hi:
                op_time[e.name] += e.dur_ns
        mods = [e for e in events if e.plane == plane
                and e.line == MODULES_LINE and sweep_name in e.name
                and lo <= e.start_ns < hi]
        sweeps += len(mods)
        sweep_s += _length(_intersect(cover, union(
            [(e.start_ns, e.end_ns) for e in mods])))
        edges = [lo] + [x for iv in cover for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, (s + e) / 2))
    nd = len(planes)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(gaps, key=lambda g: -g[0])[:10]
    return {
        "devices": nd,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy / nd * 1e-9,
        "sweep_device_s": sweep_s / nd * 1e-9,
        "sweeps": sweeps / nd,
        "device_ops": [[name, t / nd * 1e-9] for name, t in top_ops],
        "idle_gaps": [[_span_at(spans, mid), t * 1e-9] for t, mid in gaps],
    }
