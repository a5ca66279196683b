"""The program's spans in one traced benchmark run: host self time per span,
and the device's idle time by the program span it fell in.

    python3 -m tools.span_breakdown --workload isabel.ladder --seed <n> \\
        --seconds 51 [--out PATH]

From the root of a checkout, on the machine with the TPU the cell asks
for.  It runs the cell once with ``--trace 1`` through ``bench/harness.py``
and reduces the same ``.xplane.pb`` that the harness reduces a second time,
for the program's own spans (``repro.*``, named in ``src/repro/trace.py``):

* ``span_self_s``: each span's time in the benchmark's window less the part
  its child spans on the same thread line cover, summed per name over all
  threads, in seconds;
* ``idle_by_program_span``: every idle gap of the device given to the
  innermost program span (the shortest one) open at the gap's midpoint, on
  any thread, as ``bench/trace_reduce.py`` does for the benchmark's
  ``bench.*`` spans ("none" where no program span is open), in seconds
  averaged over the device planes;
* ``idle_by_bench_and_program_span``: the same idle time by the pair of
  labels ``"<bench span> / <program span>"`` each gap gets, so that the
  benchmark's ``breakdown`` can be read in the program's spans.

It prints the result line with both added under ``program_spans``, and
writes it to ``--out`` when given.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Tuple

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace_reduce as tr  # noqa: E402

PREFIX = "repro."
Span = Tuple[float, float, str]


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
            own = tr.clip([(s, e)], lo, hi)
            if not own:
                continue
            a, b = own[0]
            covered = sum(y - x for x, y in tr.union(tr.clip(children, a, b)))
            out[name] = out.get(name, 0.0) + (b - a) - covered
    return out


def innermost(idle: List[tr.Interval], spans: List[Span]) -> List[str]:
    """For each gap of ``idle``, in order, the label ``label_gaps`` gives
    it: the shortest span open at its midpoint, or "none"."""
    spans = sorted(spans)
    labels = [""] * len(idle)
    active: List[Span] = []
    i = 0
    for j in sorted(range(len(idle)), key=lambda j: sum(idle[j])):
        mid = 0.5 * sum(idle[j])
        while i < len(spans) and spans[i][0] <= mid:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] >= mid]
        labels[j] = min(active, key=lambda sp: sp[1] - sp[0])[2] if active \
            else "none"
    return labels


def reduce_program_spans(path: str,
                         device_plane: Callable[[str], bool] = tr.is_tpu_plane,
                         busy_line: Callable[[str], bool] =
                         lambda n: n in tr.BUSY_LINES,
                         window_span: str = tr.WINDOW_SPAN) -> dict:
    """``span_self_s``, ``idle_by_program_span`` and
    ``idle_by_bench_and_program_span`` of the trace at ``path`` (see the
    module docstring); the window and the device's busy intervals are
    found as ``bench.trace_reduce.reduce_trace`` finds them."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    window = None
    lines: List[List[Span]] = []
    bench: List[Span] = []
    devices = []
    for plane in profile.planes:
        if device_plane(plane.name):
            devices.append(plane)
        for line in plane.lines:
            spans = []
            for ev in line.events:
                iv = (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                if ev.name == window_span:
                    window = iv
                elif ev.name.startswith(PREFIX):
                    spans.append((iv[0], iv[1], ev.name))
                elif ev.name.startswith(tr.SPAN_PREFIX):
                    bench.append((iv[0], iv[1], ev.name))
            if spans:
                lines.append(spans)
    if window is None:
        raise ValueError(f"no {window_span!r} span in {path}")
    if not devices:
        raise ValueError(f"no device plane in {path}")
    lo, hi = window
    flat = [sp for line in lines for sp in line]
    idle: Dict[str, float] = {}
    both: Dict[str, float] = {}
    for plane in devices:
        busy = [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                for line in plane.lines if busy_line(line.name)
                for ev in line.events]
        gaps = tr.gaps(tr.union(tr.clip(busy, lo, hi)), lo, hi)
        for (s, e), p, b in zip(gaps, innermost(gaps, flat),
                                innermost(gaps, bench)):
            idle[p] = idle.get(p, 0.0) + (e - s)
            both[f"{b} / {p}"] = both.get(f"{b} / {p}", 0.0) + (e - s)
    n = len(devices)
    return {"span_self_s": self_times(lines, lo, hi),
            "idle_by_program_span": {k: v / n for k, v in idle.items()},
            "idle_by_bench_and_program_span": {k: v / n
                                               for k, v in both.items()}}


def run(workload: str, seed: int, seconds: float) -> dict:
    """One traced run of ``workload``: its result line, with
    ``program_spans`` reduced from the same trace as the benchmark's own
    numbers, and ``end_to_end_traced``: the end-to-end metrics read from
    this traced run (the benchmark reports them from untraced runs only),
    for the cost of tracing."""
    from bench import harness
    found: dict = {}
    reduce, result_of = tr.reduce_trace, harness._result

    def reduce_both(path: str) -> tr.TraceSummary:
        found.update(reduce_program_spans(path))
        return reduce(path)

    def with_end_to_end(cell, readings, *args, **kwargs) -> dict:
        out = result_of(cell, readings, *args, **kwargs)
        out["end_to_end_traced"] = {
            m["name"]: harness.metric_reader(m["name"], cell.root)(readings)
            for m in cell.metrics if "bound" in m}
        return out

    tr.reduce_trace, harness._result = reduce_both, with_end_to_end
    try:
        cell = harness.load_cell(workload)
        result = harness.run_cell(cell, seed, seconds, True,
                                  t_start=time.perf_counter())
    finally:
        tr.reduce_trace, harness._result = reduce, result_of
    result["program_spans"] = found
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from bench.harness import BenchError
    try:
        result = run(args.workload, args.seed, args.seconds)
    except BenchError as e:
        print(f"span_breakdown: {e}", file=sys.stderr)
        return 2
    line = json.dumps(result)
    if args.out:
        Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
