"""From a profiler trace to the numbers the per-layer metrics read.

``reduce_trace`` reads one ``.xplane.pb`` with ``jax.profiler.ProfileData``
and returns a ``TraceSummary``:

* the window: the host span that the harness opens around its measured
  window (``WINDOW_SPAN``), on the profiler's clock;
* device busy time: the union of the intervals in which an operation ran
  on a device, clipped to the window and averaged over the device planes;
* device time per program: the summed durations of each program's
  executions (``XLA Modules`` events, named by the jitted function), with
  the execution id the runtime appends stripped;
* the program's spans (host events whose names start ``repro.``, named in
  ``src/repro/trace.py``), read per thread line: each span's self time in
  the window, the part of it that none of its children on the same line
  covers, summed per name over threads;
* idle time by what the program was doing: every gap between busy
  intervals is given to the innermost program span (the shortest) open at
  the gap's midpoint on any thread, or to "none", and gap time is summed
  per span name.

The device planes and their lines are chosen by predicates, so the same
code reads a TPU trace (``/device:TPU:<n>`` planes) and, in the tests, a
trace recorded on the CPU.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."           # the harness's own (tools/span_breakdown.py)
PROGRAM_PREFIX = "repro."        # the program's spans
BUSY_LINES = ("XLA Ops",)
PROGRAM_LINES = ("XLA Modules",)

Interval = Tuple[float, float]
Span = Tuple[float, float, str]


def is_tpu_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                    # mean over device planes
    devices: int
    programs: Dict[str, float] = field(default_factory=dict)  # s, summed
    span_self_s: Dict[str, float] = field(default_factory=dict)  # s, summed
    idle_by_program_span: Dict[str, float] = \
        field(default_factory=dict)                              # s, mean

    def program_seconds(self, pattern: str) -> float:
        """Device seconds of every program whose name matches ``pattern``
        (a regular expression searched in the name)."""
        rx = re.compile(pattern)
        return sum(s for name, s in self.programs.items() if rx.search(name))


def find_xspace(logdir: str) -> str:
    """The one ``.xplane.pb`` the profiler wrote under ``logdir``."""
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {logdir}, "
                                f"found {len(found)}")
    return found[0]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] around merged ``busy`` intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def program_name(event_name: str) -> str:
    """``jit_foo(123)`` -> ``jit_foo``: drop the runtime's execution id."""
    return re.sub(r"\(\d+\)$", "", event_name)


def label_gaps(idle: List[Interval],
               spans: List[Span]) -> Dict[str, float]:
    """Seconds of idle time per innermost host span at each gap's
    midpoint ("none" where no span is open)."""
    out: Dict[str, float] = {}
    spans = sorted(spans)
    active: List[Span] = []
    i = 0
    for s, e in sorted(idle, key=lambda g: g[0] + g[1]):   # by midpoint
        mid = 0.5 * (s + e)
        while i < len(spans) and spans[i][0] <= mid:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] >= mid]
        key = min(active, key=lambda sp: sp[1] - sp[0])[2] if active \
            else "none"
        out[key] = out.get(key, 0.0) + (e - s)
    return out


def self_times(lines: Iterable[List[Span]], lo: float,
               hi: float) -> Dict[str, float]:
    """Seconds per span name of each span's part of [lo, hi] that none of
    its children covers; ``lines`` holds the spans of one thread each, so
    a span's parent is the innermost span of its line that encloses it."""
    out: Dict[str, float] = {}
    for line in lines:
        stack: List[list] = []         # open [start, end, name, children]
        closed: List[list] = []
        for s, e, name in sorted(line, key=lambda sp: (sp[0], -sp[1])):
            while stack and stack[-1][1] <= s:
                closed.append(stack.pop())
            if stack:
                stack[-1][3].append((s, e))
            stack.append([s, e, name, []])
        closed.extend(stack)
        for s, e, name, children in closed:
            own = clip([(s, e)], lo, hi)
            if not own:
                continue
            a, b = own[0]
            covered = sum(y - x for x, y in union(clip(children, a, b)))
            out[name] = out.get(name, 0.0) + (b - a) - covered
    return out


def reduce_trace(path: str,
                 device_plane: Callable[[str], bool] = is_tpu_plane,
                 busy_line: Callable[[str], bool] = lambda n: n in BUSY_LINES,
                 program_line: Callable[[str], bool] =
                 lambda n: n in PROGRAM_LINES,
                 window_span: str = WINDOW_SPAN) -> TraceSummary:
    """Reduce the trace at ``path``; see the module docstring."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    window: Optional[Interval] = None
    lines: List[List[Span]] = []       # the program's spans, per thread
    devices = []
    for plane in profile.planes:
        if device_plane(plane.name):
            devices.append(plane)
        for line in plane.lines:
            spans: List[Span] = []
            for ev in line.events:
                name = ev.name
                if name.startswith(PROGRAM_PREFIX):
                    spans.append((ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9, name))
                elif name == window_span:
                    window = (ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9)
            if spans:
                lines.append(spans)
    if window is None:
        raise ValueError(f"no {window_span!r} span in {path}")
    if not devices:
        raise ValueError(f"no device plane in {path}")
    lo, hi = window
    flat = [sp for line in lines for sp in line]
    busy_total, programs, idle_total = 0.0, {}, {}
    for plane in devices:
        busy: List[Interval] = []
        for line in plane.lines:
            is_busy, is_prog = busy_line(line.name), program_line(line.name)
            if not (is_busy or is_prog):
                continue
            for ev in line.events:
                s = ev.start_ns * 1e-9
                iv = clip([(s, s + ev.duration_ns * 1e-9)], lo, hi)
                if not iv:
                    continue
                if is_busy:
                    busy.append(iv[0])
                if is_prog:
                    name = program_name(ev.name)
                    programs[name] = programs.get(name, 0.0) \
                        + iv[0][1] - iv[0][0]
        merged = union(busy)
        busy_total += sum(e - s for s, e in merged)
        for k, v in label_gaps(gaps(merged, lo, hi), flat).items():
            idle_total[k] = idle_total.get(k, 0.0) + v
    n = len(devices)
    return TraceSummary(window_s=hi - lo, busy_s=busy_total / n, devices=n,
                        programs=programs,
                        span_self_s=self_times(lines, lo, hi),
                        idle_by_program_span={k: v / n
                                              for k, v in idle_total.items()})
