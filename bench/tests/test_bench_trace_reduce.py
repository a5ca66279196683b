"""The trace reduction, on intervals made by hand, on small traces
recorded on the CPU by the test itself, and on a fixed CPU trace kept
beside the tests (``data/program_spans.xplane.pb``: two request threads
with nested program spans, and a span that opens before the window)."""
import sys
import threading
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace_reduce as tr  # noqa: E402
from repro import trace  # noqa: E402

FIXED = Path(__file__).resolve().parent / "data" / "program_spans.xplane.pb"


def test_union_merges_overlaps_and_sorts():
    assert tr.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert tr.union([]) == []


def test_gaps_and_clip_cover_the_window():
    busy = tr.union(tr.clip([(-1, 1), (2, 3), (9, 12)], 0, 10))
    assert busy == [(0, 1), (2, 3), (9, 10)]
    assert tr.gaps(busy, 0, 10) == [(1, 2), (3, 9)]
    assert tr.gaps([], 0, 10) == [(0, 10)]


def test_idle_gaps_go_to_the_innermost_open_span():
    spans = [(0.0, 10.0, "repro.request"), (2.5, 8.0, "repro.device.wait")]
    idle = [(1.0, 2.0), (3.0, 4.0), (10.5, 11.0)]
    assert tr.label_gaps(idle, spans) == {"repro.request": 1.0,
                                          "repro.device.wait": 1.0,
                                          "none": 0.5}


def test_idle_gap_goes_to_the_shortest_span_open_on_any_thread():
    """Spans of two threads overlap; a gap goes to the shortest span open
    at its midpoint, whichever thread holds it, and a span that closed
    before the midpoint takes nothing."""
    spans = [(0.0, 10.0, "repro.request"),          # thread 1
             (1.0, 6.0, "repro.request"),           # thread 2
             (1.5, 5.5, "repro.transfer.d2h"),      # thread 2
             (2.0, 3.0, "repro.store.read")]        # thread 1
    idle = [(2.9, 3.3), (5.0, 5.2), (7.0, 8.0), (12.0, 12.5)]
    assert tr.label_gaps(idle, spans) == pytest.approx({
        "repro.transfer.d2h": 0.4 + 0.2, "repro.request": 1.0, "none": 0.5})


def test_self_time_subtracts_the_children_on_its_line():
    lines = [[(0.0, 10.0, "a"), (2.0, 4.0, "b"), (3.0, 3.5, "c"),
              (6.0, 8.0, "b")],
             [(1.0, 5.0, "a")]]             # another thread: no parent
    got = tr.self_times(lines, 0.0, 9.0)    # the window clips a to 9
    assert got == pytest.approx({"a": (9 - 2 - 2) + 4, "b": 1.5 + 2,
                                 "c": 0.5})


def test_self_time_counts_only_the_window():
    """A span wholly outside the window is left out; one that crosses an
    edge counts its part inside, less its children's part inside."""
    lines = [[(-5.0, -1.0, "before")],
             [(8.0, 12.0, "b"), (9.0, 11.0, "c")],
             [(-2.0, 1.0, "d"), (-1.5, 0.5, "e")],
             [(10.0, 11.0, "after")]]
    got = tr.self_times(lines, 0.0, 10.0)
    assert got == pytest.approx({"b": 1.0, "c": 1.0, "d": 0.5, "e": 0.5})


def test_program_span_fields_default_to_empty():
    s = tr.TraceSummary(window_s=1.0, busy_s=0.5, devices=1)
    assert s.span_self_s == {} and s.idle_by_program_span == {}
    assert s.programs == {}


def test_program_name_drops_the_execution_id():
    assert tr.program_name("jit__decode_fused(42)") == "jit__decode_fused"
    assert tr.program_name("jit_f") == "jit_f"


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    logdir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    return tr.find_xspace(logdir)


def _cpu_lines(name: str) -> bool:
    return name.startswith("tf_XLA")


CPU = dict(device_plane=lambda n: n == "/host:CPU", busy_line=_cpu_lines,
           program_line=_cpu_lines)


def test_reduce_a_cpu_trace(cpu_trace):
    s = tr.reduce_trace(cpu_trace, **CPU)
    assert s.devices == 1
    assert 0 < s.busy_s <= s.window_s < 5.0
    assert s.program_seconds(r"^dot_general") > 0
    # no program span in this trace: every gap is "none"
    idle = sum(s.idle_by_program_span.values())
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6, abs=1e-9)
    assert set(s.idle_by_program_span) == {"none"}
    assert s.span_self_s == {}


@pytest.fixture(scope="module")
def nested_trace(tmp_path_factory):
    """Two threads, each a request span around an estimate span around a
    device wait; device work runs only inside the estimate."""
    import jax
    import jax.numpy as jnp
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
    logdir = str(tmp_path_factory.mktemp("nested"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
    jax.profiler.stop_trace()
    assert not any(t.is_alive() for t in threads)
    return tr.find_xspace(logdir)


def _durations(path):
    """Per thread line: {span name: seconds} straight from the trace."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            d = {ev.name: ev.duration_ns * 1e-9 for ev in line.events
                 if ev.name.startswith(tr.PROGRAM_PREFIX)}
            if d:
                out.append(d)
    return out


def test_reduce_a_cpu_trace_with_program_spans_on_two_threads(nested_trace):
    s = tr.reduce_trace(nested_trace, **CPU)
    lines = _durations(nested_trace)
    assert len(lines) == 2
    want = {trace.REQUEST: sum(d[trace.REQUEST] - d[trace.ESTIMATE]
                               for d in lines),
            trace.ESTIMATE: sum(d[trace.ESTIMATE] - d[trace.DEVICE_WAIT]
                                for d in lines),
            trace.DEVICE_WAIT: sum(d[trace.DEVICE_WAIT] for d in lines)}
    assert s.span_self_s == pytest.approx(want, rel=1e-9, abs=1e-9)
    assert s.span_self_s[trace.REQUEST] >= 2 * 0.02
    idle = s.idle_by_program_span
    assert set(idle) <= {trace.REQUEST, trace.ESTIMATE, trace.DEVICE_WAIT,
                         "none"}
    # each thread's 30-ms wait has no device work in it, and the wait is
    # the innermost span open there
    assert idle[trace.DEVICE_WAIT] >= 0.03
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s,
                                               rel=1e-6, abs=1e-9)


# FIXED as the reduction read it before it read the program's spans
FIXED_DEVICE = {
    "window_s": 0.044018404000000004,
    "busy_s": 0.0013415140000000055,
    "programs": {"ThreadpoolListener::StartRegion": 0.0,
                 "wrapped_sine": 0.0006870880000000024,
                 "end: wrapped_sine": 5.718000000001777e-06,
                 "dot_general.1": 0.0006540910000000025,
                 "end: dot_general.1": 1.9299999999972117e-06,
                 "ThunkExecutor::Execute (wait for completion)":
                     3.3500000000408203e-07,
                 "ThreadpoolListener::StopRegion": 0.0},
    "device_idle_share": 96.95237928208391}
# FIXED's program spans as tools/span_breakdown.py reduced them
FIXED_SPANS = {
    "span_self_s": {"repro.device.wait": 0.040889788,
                    "repro.retrieval.estimate": 0.0034966040000000004,
                    "repro.request": 0.020754095999999993,
                    "repro.transfer.d2h": 0.020258469,
                    "repro.codec.inflate": 0.005704480000000001},
    "idle_by_program_span": {"repro.codec.inflate": 0.011383334000000002,
                             "repro.retrieval.estimate":
                                 0.00031580100000000444,
                             "repro.device.wait": 0.020498291000000005,
                             "repro.transfer.d2h": 0.010438529000000009,
                             "none": 4.093499999999889e-05}}


def test_fixed_trace_keeps_its_device_numbers_bit_for_bit():
    s = tr.reduce_trace(str(FIXED), **CPU)
    assert s.window_s == FIXED_DEVICE["window_s"]
    assert s.busy_s == FIXED_DEVICE["busy_s"]
    assert s.programs == FIXED_DEVICE["programs"]
    r = harness.Readings(setup_s=1.0, answers=[], counters={}, trace=s)
    assert harness.metric_reader("device_idle_share")(r) == \
        FIXED_DEVICE["device_idle_share"]


def test_fixed_trace_gives_each_program_span_its_time():
    """Self time per span, the inflate span clipped at the window's
    start, and idle time by innermost span, summing to the idle."""
    s = tr.reduce_trace(str(FIXED), **CPU)
    assert s.span_self_s == pytest.approx(FIXED_SPANS["span_self_s"],
                                          rel=1e-12, abs=1e-15)
    assert s.idle_by_program_span == pytest.approx(
        FIXED_SPANS["idle_by_program_span"], rel=1e-12, abs=1e-15)
    assert sum(s.idle_by_program_span.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-12)


def test_reduce_refuses_a_trace_without_a_device_or_window(cpu_trace):
    with pytest.raises(ValueError, match="device plane"):
        tr.reduce_trace(cpu_trace)
    with pytest.raises(ValueError, match="span"):
        tr.reduce_trace(cpu_trace, device_plane=lambda n: n == "/host:CPU",
                        window_span="bench.nothing")
