"""Reduction of a ``jax.profiler`` trace to the device numbers the per-layer
metrics read: busy and idle time per chip, time in XLA ``while`` loops, the
device ops that took most time, and the longest idle gaps by what the host
was doing.

Only JAX's own reader (``jax.profiler.ProfileData``) is used. Device planes
are ``/device:TPU:<n>``; their op events sit on the line ``XLA Ops``, nested:
a segment program's tasks sit in ``conditional`` ops (the pause flag), a
scan's ``while`` inside those, and every iteration's ops inside the loop.
Busy time is the union of the outermost ops; loop time the union of the
``while`` ops at any depth; op time is self time (less the nested ops). The
benchmark's own host spans (``jax.profiler.TraceAnnotation``, names
``bench.*``) sit on host planes; ``bench.traced`` spans the traced window.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.traced"
STEP = "bench.step"
LOOP = re.compile(r"^while$")
GAPS_ATTRIBUTED = 500  # the longest idle gaps, attributed to host activity


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: int, e: int, w0: int, w1: int) -> Optional[Tuple[int, int]]:
    s, e = max(s, w0), min(e, w1)
    return (s, e) if e > s else None


def _nested(events) -> List[Tuple[str, int, int, int, int, int]]:
    """(name, start, end, depth, self_ns, parent) of a line's events, in
    start order: depth by nesting, self time as the duration less that of the
    direct children, parent as an index into the result (-1 at the top)."""
    out: List[List[Any]] = []
    stack: List[int] = []  # indices into out of the open events
    for ev in sorted(events, key=lambda e: (e.start_ns, -e.duration_ns)):
        s, e = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][4] -= e - s
        out.append([ev.name, s, e, len(stack), e - s, stack[-1] if stack else -1])
        stack.append(len(out) - 1)
    return [tuple(x) for x in out]


def _innermost(line: List[Tuple], starts: List[int], t: int):
    """The deepest event of one host line that holds the instant ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0 and line[i][2] <= t:
        i = line[i][5]  # the last event begun before t has ended: try its parent
    return line[i] if i >= 0 else None


def op_name(hlo: str) -> str:
    """An op event's HLO text shortened to its HLO op name without
    numeric suffixes: ``%fusion.57 = f32[8]... fusion(...)`` -> ``fusion``."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+|\.clone)+$", "", name)


def reduce_trace(planes: Sequence[Any], chips: Sequence[int]) -> Dict[str, Any]:
    """The traced window's numbers for the chips ``chips`` (device ids)."""
    planes = list(planes)
    host_lines = [_nested(list(line.events)) for p in planes
                  if not DEVICE_PLANE.match(p.name) for line in p.lines]
    host = [ev for line in host_lines for ev in line]
    windows = [(ev[1], ev[2]) for ev in host if ev[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} span in the trace, found {len(windows)}")
    w0, w1 = windows[0]
    steps = sum(1 for ev in host if ev[0] == STEP and ev[1] >= w0 and ev[2] <= w1)
    per_chip: Dict[int, Dict[str, float]] = {}
    op_time: Dict[str, float] = {}
    gaps: List[Tuple[int, int]] = []
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) not in chips:
            continue
        intervals, loops = [], []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for name, s, e, depth, self_ns, _ in _nested(list(line.events)):
                c = _clip(s, e, w0, w1)
                if c is None:
                    continue
                short = op_name(name)
                if depth == 0:
                    intervals.append(c)
                if LOOP.match(short):
                    loops.append(c)
                share = (c[1] - c[0]) / max(e - s, 1)  # of the event inside the window
                op_time[short] = op_time.get(short, 0.0) + self_ns * share * 1e-9
        loop_ns = sum(e - s for s, e in _union(loops))  # nested loops count once
        busy = _union(intervals)
        busy_ns = sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
        per_chip[int(m.group(1))] = {"busy_s": busy_ns * 1e-9, "loop_s": loop_ns * 1e-9}
    missing = set(chips) - set(per_chip)
    if missing:
        raise ValueError(f"no device plane in the trace for chips {sorted(missing)}")
    # what the host was doing in the longest idle gaps: the deepest host
    # event (of any thread) at the gap's middle, the traced window aside
    line_starts = [[ev[1] for ev in line] for line in host_lines]
    idle_by: Dict[str, float] = {}
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:GAPS_ATTRIBUTED]:
        mid = (s + e) // 2
        found = [ev for line, st in zip(host_lines, line_starts)
                 for ev in [_innermost(line, st, mid)] if ev is not None and ev[0] != WINDOW]
        what = max(found, key=lambda ev: ev[3])[0] if found else "(no host span)"
        idle_by[what] = idle_by.get(what, 0.0) + (e - s) * 1e-9
    n = len(per_chip)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "steps": steps,
        "busy_s": sum(c["busy_s"] for c in per_chip.values()) / n,
        "loop_s": sum(c["loop_s"] for c in per_chip.values()) / n,
        "per_chip": per_chip,
        "op_self_s": sum(op_time.values()) / n,
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(idle_by.items(), key=lambda kv: -kv[1])[:10],
    }


def load_planes(path: str):
    """Planes of an ``.xplane.pb`` file, or of a gzip-compressed one."""
    import gzip

    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return list(ProfileData.from_serialized_xspace(f.read()).planes)
    return list(ProfileData.from_file(path).planes)
