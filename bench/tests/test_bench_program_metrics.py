"""The per-layer metrics read from the program's own counters
(``RetrievalServer.metrics()``): reported by a traced run of the cell on
the CPU at a tiny shape, and nothing read from a program without them."""
import functools
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace_reduce  # noqa: E402

METRICS = ("d2h_bytes_per_answer", "h2d_bytes_per_answer",
           "iterations_per_answer", "window_compiles")


@pytest.fixture
def on_cpu(monkeypatch):
    monkeypatch.setattr(harness, "check_devices", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "off")
    monkeypatch.setattr(trace_reduce, "reduce_trace", functools.partial(
        trace_reduce.reduce_trace, device_plane=lambda n: n == "/host:CPU",
        busy_line=lambda n: n.startswith("tf_XLA"),
        program_line=lambda n: n.startswith("tf_XLA")))


def test_traced_run_reports_the_program_counter_metrics(on_cpu):
    cell = harness.load_cell("isabel.ladder")
    cell.manifest.update(shape=[9, 17, 17])
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
