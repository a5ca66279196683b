"""Each cell's traffic through the harness's own pieces at its
configuration's CPU test size (the test steers the platform by replacing
the harness's device check), and the entry point's refusals."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from repro import trace  # noqa: E402

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]
SPAN_METRICS = ("d2h_ms_per_answer", "device_wait_ms_per_answer",
                "batch_window_ms_per_answer", "inflate_ms_per_answer",
                "store_read_ms_per_answer", "contrib_sum_ms_per_answer",
                "estimate_ms_per_answer")


def run(cell, seed=2**31 + 11, seconds=2.0, trace=False, **kw):
    return harness.run_cell(cell, seed, seconds, trace,
                            t_start=time.perf_counter(), log=lambda s: None,
                            **kw)


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_its_answers_pass_the_check(on_cpu, tiny_cell, name,
                                                  capsys):
    cell = tiny_cell(name)
    out = run(cell)
    assert list(out)[:5] == KEYS and list(out)[-1] == "checks"
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    e2e = {m["name"] for m in cell.metrics if "bound" in m}
    assert set(out["metrics"]) == e2e
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["checks"]["checked"]["value"] >= 1
    harness.emit(out)
    lines = capsys.readouterr()
    assert json.loads(lines.out.strip().splitlines()[-1]) == json.loads(
        json.dumps(out))
    assert lines.err.strip().splitlines()[-1].startswith("check checked")


def test_traced_run_reports_the_per_layer_metrics(on_cpu, cpu_planes,
                                                  tiny_cell, monkeypatch):
    # the fused decode and its batcher, which the chip takes at the cell's
    # size; the CPU test size's coefficient groups are below its threshold
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_decode_path", "fused")
    out = run(tiny_cell("isabel.ladder"), trace=True)
    assert out["correct"], out["checks"]
    for name in ("device_idle_share", "queue_wait_share",
                 "segment_cache_hit_share", "bytes_per_answer",
                 "answer_p90_s.few") + SPAN_METRICS:
        assert name in out["metrics"], name
    for name in SPAN_METRICS:
        assert out["metrics"][name]["value"] >= 0, name
        assert out["metrics"][name]["unit"] == "ms", name
    assert 0 <= out["metrics"]["device_idle_share"]["value"] <= 100
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]
    labels = {k for k, _ in out["breakdown"]["idle_gaps"]}
    assert labels <= set(trace.SPANS) | {"none"}
    assert labels & set(trace.SPANS)
    assert "spans_absent" not in out


def _entry(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "isabel.ladder",
         "--seed", "5", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_entry_point_refuses_a_host_without_a_tpu():
    p = _entry(ROOT)
    assert p.returncode == 2 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_entry_point_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _entry(tmp_path)
    assert p.returncode == 2 and p.stdout == ""


def test_batch_warm_up_skips_a_batcher_that_changed_its_calls():
    assert harness._record_batches(None) is None
    assert harness._record_batches(object()) is None

    class Batcher:
        def submit_decode(self, *args):         # the call changed shape
            raise TypeError("takes no such arguments")

        def flush(self):
            pass
    logged = []
    harness._warm_batches(Batcher(), {"k": ("submit_decode", (1,), {})}, 2,
                          logged.append)
    assert logged and "skipped" in logged[0]
