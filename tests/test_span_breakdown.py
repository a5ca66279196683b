"""tools/span_breakdown.py: per-span self time and device idle by program
span, on intervals made by hand, on a trace recorded on the CPU with
nested program spans on two threads, and through one traced run of the
benchmark's cell at a tiny shape."""
import functools
import random
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT)]

from bench import harness, trace_reduce  # noqa: E402
from repro import trace  # noqa: E402
from tools import span_breakdown as sb  # noqa: E402

ON_CPU = dict(device_plane=lambda n: n == "/host:CPU",
              busy_line=lambda n: n.startswith("tf_XLA"))


def test_self_time_subtracts_the_children_on_its_line():
    lines = [[(0.0, 10.0, "a"), (2.0, 4.0, "b"), (3.0, 3.5, "c"),
              (6.0, 8.0, "b")],
             [(1.0, 5.0, "a")]]             # another thread: no parent
    got = sb.self_times(lines, 0.0, 9.0)    # the window clips a to 9
    assert got == pytest.approx({"a": (9 - 2 - 2) + 4, "b": 1.5 + 2,
                                 "c": 0.5})


def test_each_gap_gets_the_label_that_label_gaps_sums_by():
    rng = random.Random(1)
    spans = []
    for i in range(300):
        a = rng.uniform(0, 100)
        spans.append((a, a + rng.uniform(0, 5), f"s{i % 7}"))
    idle, t = [], 0.0
    while t < 100:
        a = t + rng.uniform(0, 1)
        t = a + rng.uniform(0, 0.5)
        idle.append((a, t))
    summed = {}
    for (s, e), label in zip(idle, sb.innermost(idle, spans)):
        summed[label] = summed.get(label, 0.0) + (e - s)
    assert summed == pytest.approx(trace_reduce.label_gaps(idle, spans))


@pytest.fixture(scope="module")
def nested_trace(tmp_path_factory):
    """Two threads, each a request span around an estimate span around a
    device wait; device work runs only inside the estimate."""
    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    barrier = threading.Barrier(2)

    def worker(i):
        barrier.wait(10)
        with trace.span(trace.REQUEST, client=f"c{i}", seq=i, tau=1e-2):
            time.sleep(0.02)
            with trace.span(trace.ESTIMATE):
                f(x).block_until_ready()
                with trace.span(trace.DEVICE_WAIT):
                    time.sleep(0.03)
    logdir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
    jax.profiler.stop_trace()
    return trace_reduce.find_xspace(logdir)


def _durations(path):
    """Per thread line: {span name: seconds} straight from the trace."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            d = {ev.name: ev.duration_ns * 1e-9 for ev in line.events
                 if ev.name.startswith("repro.")}
            if d:
                out.append(d)
    return out


def test_a_cpu_trace_with_nested_spans_on_two_threads(nested_trace):
    got = sb.reduce_program_spans(nested_trace, **ON_CPU)
    lines = _durations(nested_trace)
    assert len(lines) == 2
    want = {trace.REQUEST: sum(d[trace.REQUEST] - d[trace.ESTIMATE]
                               for d in lines),
            trace.ESTIMATE: sum(d[trace.ESTIMATE] - d[trace.DEVICE_WAIT]
                                for d in lines),
            trace.DEVICE_WAIT: sum(d[trace.DEVICE_WAIT] for d in lines)}
    assert got["span_self_s"] == pytest.approx(want, rel=1e-9, abs=1e-9)
    assert got["span_self_s"][trace.REQUEST] >= 2 * 0.02
    idle = got["idle_by_program_span"]
    assert set(idle) <= {trace.REQUEST, trace.ESTIMATE, trace.DEVICE_WAIT,
                         "none"}
    # each thread's 30-ms wait has no device work in it, and the wait is
    # the innermost span open there
    assert idle[trace.DEVICE_WAIT] >= 0.03
    bench = trace_reduce.reduce_trace(
        nested_trace, program_line=ON_CPU["busy_line"], **ON_CPU)
    assert sum(idle.values()) == pytest.approx(
        bench.window_s - bench.busy_s, rel=1e-6, abs=1e-9)
    # no bench.* span but the window: every gap's bench label is "none"
    assert got["idle_by_bench_and_program_span"] == pytest.approx(
        {f"none / {k}": v for k, v in idle.items()})


def test_a_traced_run_of_the_cell_through_the_tool(monkeypatch):
    monkeypatch.setattr(harness, "check_devices", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "off")
    monkeypatch.setattr(harness, "load_cell", functools.partial(
        _tiny_cell, harness.load_cell))
    monkeypatch.setattr(trace_reduce, "reduce_trace", functools.partial(
        trace_reduce.reduce_trace, program_line=ON_CPU["busy_line"],
        **ON_CPU))
    monkeypatch.setattr(sb, "reduce_program_spans", functools.partial(
        sb.reduce_program_spans, **ON_CPU))
    reduce = trace_reduce.reduce_trace
    out = sb.run("isabel.ladder", 2**31 + 23, 2.0)
    assert trace_reduce.reduce_trace is reduce      # put back
    assert out["correct"], out["checks"]
    spans = out["program_spans"]
    assert {trace.REQUEST, trace.RECONSTRUCT, trace.ESTIMATE,
            trace.READER_REFRESH, trace.DEVICE_WAIT,
            trace.TRANSFER_D2H} <= set(spans["span_self_s"])
    assert set(spans["span_self_s"]) <= set(trace.SPANS)
    assert all(v >= 0 for v in spans["span_self_s"].values())
    assert set(spans["idle_by_program_span"]) <= set(trace.SPANS) | {"none"}
    assert sum(spans["idle_by_bench_and_program_span"].values()) == \
        pytest.approx(sum(spans["idle_by_program_span"].values()))
    assert out["end_to_end_traced"]["answers_per_s"] > 0
    assert out["end_to_end_traced"]["setup_s"] > 0


def _tiny_cell(load, name):
    cell = load(name)
    cell.manifest.update(shape=[9, 17, 17])
    return cell
