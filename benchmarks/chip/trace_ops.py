"""Reduction of a profiler trace to the numbers the readers use.

Two stages.  :func:`load` reads the ``.xplane.pb`` file that
``jax.profiler`` writes and keeps three lists of ``(name, start_ns,
end_ns)``: the operations on the device (the ``XLA Ops`` line of each
``/device:`` plane, nested: a loop's event holds its body's), the
programs on the device (its ``XLA Modules`` line), and the benchmark's
own host spans (host events named ``bench.*``).  Device and host
events share the profiler's clock.

The device is busy while one of its programs (modules) runs.  The ops
inside a program leave gaps between them (the loop control of a
``lax.scan``, for one) in which the device is still executing that
program; those are not time the host left the device idle.  The
rest are plain functions over those lists, checked on a small recorded
trace by ``test_chipbench_trace.py``.
"""
from __future__ import annotations

import glob
import os
from bisect import bisect_left, bisect_right
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, end_ns)
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def op_name(text: str) -> str:
    """``availscan_select.3`` of ``%availscan_select.3 = s32[1,8] ...``:
    device ops are named by their whole HLO instruction."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: str) -> Dict[str, list]:
    """Device ops (by instruction name), device modules and benchmark
    spans of the newest trace under ``trace_dir``.  ``by_device`` keeps
    each chip's modules apart, for its busy time."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out: Dict[str, list] = dict(ops=[], modules=[], spans=[], by_device=[])
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name == OPS_LINE:
                out["ops"].extend(
                    (op_name(e.name), int(e.start_ns),
                     int(e.start_ns) + int(e.duration_ns))
                    for e in line.events)
            elif device and line.name == MODULES_LINE:
                mods = [(e.name, int(e.start_ns),
                         int(e.start_ns) + int(e.duration_ns))
                        for e in line.events]
                if mods:
                    out["by_device"].append(mods)
                    out["modules"].extend(mods)
            elif not device:
                out["spans"].extend(
                    (e.name, int(e.start_ns),
                     int(e.start_ns) + int(e.duration_ns))
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return out


def clip(events: Sequence[Event], t0: int, t1: int) -> List[Event]:
    """Events cut to the window ``[t0, t1)``; those outside dropped."""
    return [(n, max(s, t0), min(e, t1)) for n, s, e in events
            if e > t0 and s < t1]


def union(events: Sequence[Event]) -> List[Tuple[int, int]]:
    """Merged busy intervals of ``events``, ascending."""
    merged: List[List[int]] = []
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(events: Sequence[Event], t0: int, t1: int) -> int:
    """Nanoseconds of ``[t0, t1)`` in which some event runs."""
    return sum(e - s for s, e in union(clip(events, t0, t1)))


def gaps(events: Sequence[Event], t0: int, t1: int
         ) -> List[Tuple[int, int]]:
    """The idle intervals of ``[t0, t1)``: no event runs in them."""
    out, at = [], t0
    for s, e in union(clip(events, t0, t1)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < t1:
        out.append((at, t1))
    return out


def named_time(events: Sequence[Event], prefix: str) -> Tuple[int, int]:
    """``(summed ns, count)`` of the events whose name starts with
    ``prefix``."""
    hits = [e - s for n, s, e in events if n.startswith(prefix)]
    return sum(hits), len(hits)


def self_times(events: Sequence[Event]) -> List[Tuple[str, int]]:
    """``(name, self ns)`` per event: its time less that of the events
    nested in it (a device's ops nest: a loop holds its body's ops)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [e - s for _, s, e in events]
    stack: List[int] = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(events[i][0], own[i]) for i in range(len(events))]


def top(events: Sequence[Event], n: int = 10) -> List[list]:
    """The ``n`` names with the most summed self time (one HLO
    instruction is one name): ``[[name, s], ...]``."""
    tot: Dict[str, int] = defaultdict(int)
    for name, ns in self_times(events):
        tot[name] += ns
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def attribute(idle: Sequence[Tuple[int, int]], spans: Sequence[Event]
              ) -> Dict[str, int]:
    """Idle nanoseconds by what the host was doing: each part of a gap
    goes to the innermost (latest-starting) benchmark span that covers
    it, and to ``"no span"`` where none does."""
    pts = sorted({t for _, s, e in spans for t in (s, e)})
    owner: List[str] = ["no span"] * max(len(pts) - 1, 0)
    for name, s, e in sorted(spans, key=lambda ev: ev[1]):
        for k in range(bisect_left(pts, s), bisect_left(pts, e)):
            owner[k] = name[len(SPAN_PREFIX):]
    out: Dict[str, int] = defaultdict(int)
    for g0, g1 in idle:
        covered = 0
        k = max(bisect_right(pts, g0) - 1, 0)
        while k < len(owner) and pts[k] < g1:
            part = min(pts[k + 1], g1) - max(pts[k], g0)
            if part > 0:
                out[owner[k]] += part
                covered += part
            k += 1
        if g1 - g0 > covered:
            out["no span"] += g1 - g0 - covered
    return dict(out)
