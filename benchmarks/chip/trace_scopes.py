"""Reduction of a profiler trace by the program's own spans and scopes.

The program names its work in the trace (DESIGN.md §13): host
spans ``repro.*`` around the service's offer, staging, dispatch, drain
and restore, and ``jax.named_scope``\\ s on the device inside each
scan step of ``admit_stream``: ``admit``, then the phases
``admit.release``, ``admit.quota``, ``admit.search`` (inside it
``admit.search.reject``, ``.candidates``, ``.rects``, ``.mask``),
``admit.commit`` and ``admit.displace``.  A device op's scope path is
its HLO ``op_name`` metadata, e.g.
``jit(admit_stream_donated)/while/body/closed_call/admit/admit.search/
cond/branch_1_fun/admit.search.rects/...``: its *phase* is the first
component that starts with ``admit.``, its innermost scope the last.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes with
``trace_ops.load`` and keeps besides the ``repro.*`` host spans.
The op events name no scope, so :func:`hlo_paths` reads each
instruction's path from the compiled scan programs and
:func:`paths_by_module` gives each op the path of its instruction in
the module it ran in.  The rest are plain functions over those lists,
checked on a small recorded trace by ``test_chipbench_scopes.py``.
Against a
program without the spans and scopes every reduction reads nothing
(``None`` or empty), and none raises.
"""
from __future__ import annotations

import glob
import os
import re
from bisect import bisect_right
from collections import defaultdict
from typing import Dict, List, Optional, Sequence

import trace_ops

Event = trace_ops.Event
PROGRAM_PREFIX = "repro."
SCOPE = "admit"                      # the scan step's own scope
SCAN_PROGRAM = "jit(admit_stream"    # both scan programs' path prefix
SCAN_MODULE = "admit_stream"         # ... and their modules' names
CONTROL_OPS = ("while", "cond")


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------


def load(trace_dir: str) -> Dict[str, list]:
    """``trace_ops.load`` of the newest trace under ``trace_dir``, plus
    its ``repro.*`` host spans as ``program_spans``.  A TPU v5e op
    event carries no ``op_name`` (its only stats are
    ``device_duration_ps``, ``device_offset_ps`` and ``Time Scale
    Multiplier``), so scope paths come from :func:`paths_by_module`."""
    from jax.profiler import ProfileData
    out = trace_ops.load(trace_dir)
    newest = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                           recursive=True), key=os.path.getmtime)
    out["program_spans"] = [
        (e.name, int(e.start_ns), int(e.start_ns) + int(e.duration_ns))
        for plane in ProfileData.from_file(newest).planes
        if not plane.name.startswith("/device:")
        for line in plane.lines for e in line.events
        if e.name.startswith(PROGRAM_PREFIX)]
    return out


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?"
                    r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")


def hlo_paths(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` path, from compiled HLO text
    (``jitted.lower(...).compile().as_text()``): the instruction names
    are those of the trace's op events."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def paths_by_module(ops: Sequence[Event], modules: Sequence[Event],
                    maps: Dict[str, Dict[str, str]]) -> List[str]:
    """Scope paths of ``ops`` from per-program instruction maps: an op
    takes the map of the module it runs inside (``maps`` is keyed by
    a substring of the module's name; the longest key that matches
    wins), ``""`` outside them."""
    keys = sorted(maps, key=len, reverse=True)
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for name, s, e in ops:
        i = bisect_right(starts, s) - 1
        path = ""
        if i >= 0 and mods[i][2] >= e:
            key = next((k for k in keys if k in mods[i][0]), None)
            if key is not None:
                path = maps[key].get(name, "")
        out.append(path)
    return out


# ---------------------------------------------------------------------------
# scopes
# ---------------------------------------------------------------------------


def components(path: str) -> List[str]:
    return path.split("/") if path else []


def phase(path: str) -> Optional[str]:
    """The step phase of an op: its outermost ``admit.*`` scope."""
    return next((c for c in components(path) if c.startswith(SCOPE + ".")),
                None)


def innermost(path: str) -> Optional[str]:
    """The op's innermost ``admit.*`` scope."""
    return next((c for c in reversed(components(path))
                 if c.startswith(SCOPE + ".")), None)


def scope_key(path: str) -> str:
    """The op's innermost ``admit.*`` scope, under its phase where the
    two differ (``admit.displace/admit.search.rects``: a search the
    displacement runs); ``admit`` for step ops outside every phase."""
    outer, inner = phase(path), innermost(path)
    if outer is None:
        return SCOPE
    return inner if inner.startswith(outer) else f"{outer}/{inner}"


def in_step(path: str) -> bool:
    """Whether the op runs inside a scan step's ``admit`` scope."""
    return SCOPE in components(path)


def in_scan(path: str) -> bool:
    return path.startswith(SCAN_PROGRAM)


def is_control(name: str) -> bool:
    """A ``while`` or ``cond`` op: its self time is loop or branch
    control, not work of its own."""
    return name.split(".")[0] in CONTROL_OPS


def scan_split(ops: Sequence[Event], paths: Sequence[str],
               modules: Sequence[Event], t0: int, t1: int
               ) -> Dict[str, int]:
    """Nanoseconds of ``[t0, t1)`` the scan programs spent, by part.

    Each op's self time (``trace_ops.self_times``) goes to the scope it
    names: ``admit.*`` phases and their inner scopes (keyed by
    :func:`scope_key`, so ``admit.search`` holds the search's own ops
    only), ``admit`` (in a step, outside every phase), ``outside
    admit`` (scan ops outside the step, such as its per-step slicing),
    and ``loop control`` (the scan loop's own ``while`` time plus the
    scan modules' time that no op covers)."""
    both = [(ev, p) for ev, p in zip(trace_ops.clip(ops, t0, t1),
                                     _clip_paths(ops, paths, t0, t1))
            if in_scan(p)]
    if not both:
        return {}
    scan_ops = [ev for ev, _ in both]
    out: Dict[str, int] = defaultdict(int)
    for (name, ns), (_, path) in zip(trace_ops.self_times(scan_ops), both):
        if in_step(path):
            out[scope_key(path)] += ns
        elif name.split(".")[0] == "while":
            out["loop control"] += ns
        else:
            out["outside admit"] += ns
    mods = [m for m in modules if SCAN_MODULE in m[0]]
    out["loop control"] += max(trace_ops.busy_ns(mods, t0, t1)
                               - trace_ops.busy_ns(scan_ops, t0, t1), 0)
    return dict(out)


def _clip_paths(ops: Sequence[Event], paths: Sequence[str], t0: int,
                t1: int) -> List[str]:
    """``paths`` of the ops that ``trace_ops.clip`` keeps, in order."""
    return [p for (_, s, e), p in zip(ops, paths) if e > t0 and s < t1]


def phase_ns(split: Dict[str, int], name: str) -> Optional[int]:
    """Self ns of the phase ``name`` (its inner scopes included) in a
    :func:`scan_split`; ``None`` where no op named it."""
    hits = [ns for k, ns in split.items()
            if k == name or k.startswith((name + ".", name + "/"))]
    return sum(hits) if hits else None


def coverage(ops: Sequence[Event], paths: Sequence[str]) -> Optional[float]:
    """Share of the step's work that a phase scope names: self time of
    the ops inside ``admit`` that lie in an ``admit.*`` scope, over
    that of all ops inside ``admit``, ``while`` and ``cond`` ops' own
    time left out of both."""
    own = trace_ops.self_times(ops)
    total = named = 0
    for (name, ns), path in zip(own, paths):
        if not in_step(path) or is_control(name):
            continue
        total += ns
        if phase(path) is not None:
            named += ns
    return named / total if total else None


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------


def idle_by_span(modules: Sequence[Event], spans: Sequence[Event],
                 program_spans: Sequence[Event], t0: int, t1: int
                 ) -> Dict[str, int]:
    """Device-idle ns of ``[t0, t1)`` by the innermost host span, over
    the benchmark's spans (named without their ``bench.`` prefix, as
    ``trace_ops.attribute`` names them) and the program's (named in
    full, ``repro.drain.sync``)."""
    idle = trace_ops.gaps(modules, t0, t1)
    both = list(spans) + [(trace_ops.SPAN_PREFIX + n, s, e)
                          for n, s, e in program_spans]
    return trace_ops.attribute(idle, both)


def share_of(idle: Dict[str, int], prefix: str, window_ns: int
             ) -> Optional[float]:
    """Percent of the window idle under spans named ``prefix*``;
    ``None`` where no such span took any idle time."""
    hits = [ns for k, ns in idle.items() if k.startswith(prefix)]
    if not hits or window_ns <= 0:
        return None
    return 100.0 * sum(hits) / window_ns


def mean_span_us(program_spans: Sequence[Event], name: str,
                 t0: int, t1: int) -> Optional[float]:
    """Mean duration of the spans called ``name`` that start in the
    window, in microseconds."""
    hits = [e - s for n, s, e in program_spans
            if n == name and t0 <= s < t1]
    return sum(hits) / len(hits) / 1e3 if hits else None
