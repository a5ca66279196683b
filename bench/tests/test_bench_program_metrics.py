"""The per-layer metrics read from the program's own counters
(``RetrievalServer.metrics()``) and spans (``src/repro/trace.py``):
reported by a traced run of the cell on the CPU at its configuration's
CPU test size, and nothing read from a program without them."""
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace_reduce  # noqa: E402
from bench.check import Answer  # noqa: E402

METRICS = ("d2h_bytes_per_answer", "h2d_bytes_per_answer",
           "iterations_per_answer", "window_compiles")
# each span metric and the program span whose self time it reads
SPAN_METRICS = {"d2h_ms_per_answer": "repro.transfer.d2h",
                "device_wait_ms_per_answer": "repro.device.wait",
                "batch_window_ms_per_answer": "repro.batch.window",
                "inflate_ms_per_answer": "repro.codec.inflate",
                "store_read_ms_per_answer": "repro.store.read",
                "contrib_sum_ms_per_answer": "repro.reader.refresh",
                "estimate_ms_per_answer": "repro.retrieval.estimate"}


def test_traced_run_reports_the_program_counter_metrics(on_cpu, cpu_planes,
                                                        tiny_cell):
    cell = tiny_cell("isabel.ladder")
    assert set(METRICS) <= {m["name"] for m in cell.metrics}
    out = harness.run_cell(cell, 2**31 + 17, 2.0, True,
                           t_start=time.perf_counter(), log=lambda s: None)
    assert out["correct"], out["checks"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(METRICS) <= set(got)
    assert got["d2h_bytes_per_answer"] > 0
    assert got["h2d_bytes_per_answer"] > 0
    assert got["iterations_per_answer"] >= 1
    assert got["window_compiles"] >= 0


@pytest.mark.parametrize("name", METRICS)
def test_program_counter_metrics_read_nothing_without_the_counters(name):
    r = harness.Readings(setup_s=1.0, answers=[], counters={})
    assert harness.metric_reader(name)(r) is None


def _answers(n):
    return [Answer(client=0, session="s", qoi="VTOT", tau=1e-2,
                   submitted=0.0, done=1.0) for _ in range(n)]


def _summary(**spans):
    return trace_reduce.TraceSummary(window_s=10.0, busy_s=1.0, devices=1,
                                     span_self_s=dict(spans))


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_program_span_metrics_read_self_time_per_answer(name):
    """1e3 x the span's self time over every answer of the window, and
    no other span's time."""
    r = harness.Readings(setup_s=1.0, answers=_answers(4), counters={},
                         trace=_summary(**{SPAN_METRICS[name]: 0.5,
                                           "repro.request": 9.0}))
    assert harness.metric_reader(name)(r) == pytest.approx(125.0)
    spec = {m["name"]: m for m in harness.load_cell("isabel.ladder").metrics}
    assert spec[name]["source"] == "program_span"
    assert spec[name]["moves"] == "answers_per_s"


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_program_span_metrics_read_nothing_without_the_span(name):
    read = harness.metric_reader(name)
    no_trace = harness.Readings(setup_s=1.0, answers=_answers(2), counters={})
    other = harness.Readings(setup_s=1.0, answers=_answers(2), counters={},
                             trace=_summary(**{"repro.request": 1.0}))
    no_answer = harness.Readings(setup_s=1.0, answers=[], counters={},
                                 trace=_summary(**{SPAN_METRICS[name]: 1.0}))
    assert read(no_trace) is None
    assert read(other) is None
    assert read(no_answer) is None
