"""The trace reduction, on intervals made by hand and on a small trace
recorded on the CPU by the test itself."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace_reduce as tr  # noqa: E402


def test_union_merges_overlaps_and_sorts():
    assert tr.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert tr.union([]) == []


def test_gaps_and_clip_cover_the_window():
    busy = tr.union(tr.clip([(-1, 1), (2, 3), (9, 12)], 0, 10))
    assert busy == [(0, 1), (2, 3), (9, 10)]
    assert tr.gaps(busy, 0, 10) == [(1, 2), (3, 9)]
    assert tr.gaps([], 0, 10) == [(0, 10)]


def test_idle_gaps_go_to_the_innermost_open_span():
    spans = [(0.0, 10.0, "bench.serve"), (2.5, 8.0, "bench.estimate")]
    idle = [(1.0, 2.0), (3.0, 4.0), (10.5, 11.0)]
    assert tr.label_gaps(idle, spans) == {"bench.serve": 1.0,
                                          "bench.estimate": 1.0,
                                          "none": 0.5}


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


def test_reduce_a_cpu_trace(cpu_trace):
    s = tr.reduce_trace(cpu_trace, device_plane=lambda n: n == "/host:CPU",
                        busy_line=_cpu_lines, program_line=_cpu_lines)
    assert s.devices == 1
    assert 0 < s.busy_s <= s.window_s < 5.0
    assert s.program_seconds(r"^dot_general") > 0
    idle = sum(s.idle_by_span.values())
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6, abs=1e-9)
    assert set(s.idle_by_span) <= {"bench.step", "none"}


def test_reduce_refuses_a_trace_without_a_device_or_window(cpu_trace):
    with pytest.raises(ValueError, match="device plane"):
        tr.reduce_trace(cpu_trace)
    with pytest.raises(ValueError, match="span"):
        tr.reduce_trace(cpu_trace, device_plane=lambda n: n == "/host:CPU",
                        window_span="bench.nothing")
