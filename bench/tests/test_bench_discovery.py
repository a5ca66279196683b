"""Configurations, traffic mixes and metrics are found by name from new
files and new BENCHMARK.json entries, with no edit to a file the
benchmark already has; and the traffic generator gives every seed the
same work in another order."""
import collections
import itertools
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, harness, trace_reduce, traffic  # noqa: E402


@pytest.fixture
def checkout(tmp_path):
    """A copy of the benchmark with one configuration, mix and metric
    added as new files and new entries."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = tmp_path / "bench" / "configs" / "toy"
    cfg.mkdir()
    (cfg / "config.json").write_text(json.dumps({
        "name": "toy", "nodes": 33, "qois": ["SUM"],
        "limits": {"err_over_bound": 1.0, "bound_over_tau": 1.0}}))
    (cfg / "generate.py").write_text(
        "import numpy as np\n"
        "def generate(manifest, seed):\n"
        "    return {'A': np.full(manifest['nodes'], float(seed))}\n")
    (cfg / "reference.py").write_text(
        "VARIABLES = {'SUM': ('A',)}\n"
        "def qoi(name, fields):\n"
        "    return fields['A'] * 2\n")
    (tmp_path / "bench" / "traffic" / "toy_mix.json").write_text(json.dumps({
        "loop": "closed", "clients": 3, "taus": [0.1, 0.01],
        "qoi_order": None, "qoi_zipf_s": 0.0, "stagger": True}))
    (tmp_path / "bench" / "metrics" / "toy_metric.py").write_text(
        "def read(r):\n"
        "    return len(r.answers) or None\n")
    spec["configs"].append({"name": "toy", "source": "https://example.org",
                            "file": "bench/configs/toy/config.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "toy.cell", "config": "toy",
                              "traffic": "toy_mix", "chips": 1,
                              "why": "a test"})
    spec["per_layer"].append({"name": "toy_metric", "unit": "answers",
                              "better": "higher", "source": "host_clock",
                              "layer": "client", "moves": "answers_per_s",
                              "workloads": ["toy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    yield tmp_path
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def test_new_config_mix_and_metric_are_found_by_name(checkout):
    cell = harness.load_cell("toy.cell", root=checkout)
    assert cell.manifest["name"] == "toy" and cell.mix["clients"] == 3
    fields = cell.module("generate").generate(cell.manifest, 4)
    assert cell.module("reference").qoi("SUM", fields)[0] == 8.0
    names = [m["name"] for m in cell.metrics]
    assert "toy_metric" in names and "answers_per_s" in names
    assert "dispatch_ratio" not in names         # listed for other cells
    read = harness.metric_reader("toy_metric", root=checkout)
    r = harness.Readings(setup_s=1.0, answers=[], counters={})
    assert read(r) is None
    sessions = traffic.client_sessions(cell.mix, cell.manifest["qois"])
    assert len(sessions) == 3
    assert next(sessions[0]).qoi == "SUM"


def test_existing_cells_still_load(checkout):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"], root=checkout)
        assert cell.name == w["name"]
        assert cell.manifest["name"] == w["config"]


def test_a_copied_configuration_joins_as_new_files(checkout, tiny_cell):
    """A configuration copied from isabel-velocity under a new name, with
    its own traffic mix, cell and a metric that reads one program span,
    all new files and new entries: the cell loads, the metric reads its
    span, and the test size is the copy's own ``cpu_test``."""
    src = ROOT / "bench" / "configs" / "isabel-velocity"
    dst = checkout / "bench" / "configs" / "isabel-copy"
    shutil.copytree(src, dst, ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.loads((dst / "config.json").read_text())
    manifest.update(name="isabel-copy", cpu_test={"shape": [5, 9, 9]})
    (dst / "config.json").write_text(json.dumps(manifest))
    mix = json.loads((ROOT / "bench" / "traffic" / "ladder.json")
                     .read_text())
    (checkout / "bench" / "traffic" / "copy_mix.json").write_text(
        json.dumps(dict(mix, clients=1)))
    (checkout / "bench" / "metrics" / "copy_refresh_ms.py").write_text(
        "def read(r):\n"
        "    return r.span_ms_per_answer('repro.reader.refresh')\n")
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "isabel-copy", "source": "a test",
                            "file": "bench/configs/isabel-copy/config.json",
                            "reduced": ["shape"], "why": "a test"})
    spec["workloads"].append({"name": "copy.cell", "config": "isabel-copy",
                              "traffic": "copy_mix", "chips": 1,
                              "why": "a test"})
    spec["per_layer"].append({"name": "copy_refresh_ms", "unit": "ms",
                              "better": "lower", "source": "program_span",
                              "layer": "reader and recompose",
                              "moves": "answers_per_s",
                              "workloads": ["copy.cell"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = tiny_cell("copy.cell", root=checkout)
    assert cell.config_dir == dst and cell.manifest["name"] == "isabel-copy"
    assert cell.manifest["shape"] == [5, 9, 9]
    assert cell.mix["clients"] == 1
    names = [m["name"] for m in cell.metrics]
    assert "copy_refresh_ms" in names and "answers_per_s" in names
    assert "d2h_ms_per_answer" not in names      # listed for isabel.ladder
    fields = cell.module("generate").generate(cell.manifest, 2**31 + 3)
    assert all(f.shape == (5, 9, 9) for f in fields.values())
    assert set(cell.module("reference").VARIABLES) == {"VTOT"}
    read = harness.metric_reader("copy_refresh_ms", root=checkout)
    summary = trace_reduce.TraceSummary(
        window_s=1.0, busy_s=0.5, devices=1,
        span_self_s={"repro.reader.refresh": 0.25})
    answers = [check.Answer(client=0, session="s", qoi="VTOT", tau=0.1,
                            submitted=0.0, done=1.0)] * 5
    r = harness.Readings(setup_s=1.0, answers=answers, counters={},
                         trace=summary)
    assert read(r) == pytest.approx(50.0)
    # the original's test size and manifest are its own
    assert tiny_cell("isabel.ladder", root=checkout).manifest["shape"] == \
        [9, 17, 17]


def test_a_configuration_without_cpu_test_names_the_key(checkout,
                                                        tiny_cell):
    with pytest.raises(pytest.fail.Exception, match="'cpu_test'") as e:
        tiny_cell("toy.cell", root=checkout)
    assert "toy" in str(e.value)


def test_the_run_never_reads_cpu_test():
    """``cpu_test`` is test data: the entry point and the harness that a
    run on the chip goes through never name it."""
    for path in (ROOT / "bench" / "run.py", ROOT / "bench" / "harness.py"):
        assert "cpu_test" not in path.read_text(), path


def test_unknown_cell_is_refused(checkout):
    with pytest.raises(harness.BenchError, match="no workload"):
        harness.load_cell("nope", root=checkout)


def test_zipf_sequence_holds_each_share_in_every_prefix():
    seq = traffic.zipf_sequence(6, 1.1, 64)
    w = [(r + 1) ** -1.1 for r in range(6)]
    p = [x / sum(w) for x in w]
    for n in range(1, 65):
        counts = collections.Counter(seq[:n])
        for r in range(6):
            assert abs(counts[r] - p[r] * n) < 1.0 + 1e-9


@pytest.mark.parametrize("clients", [1, 2, 3, 8])
def test_every_run_gets_the_same_sessions_with_staggered_starts(clients):
    m = dict(traffic.load(ROOT / "bench" / "traffic" / "ladder.json"),
             clients=clients, qoi_order=["VTOT", "T", "C"], qoi_zipf_s=1.1)
    qois = ["VTOT", "T", "C", "Mach"]

    def sessions():
        return [[(s.name, s.qoi, s.taus) for s in itertools.islice(it, 40)]
                for it in traffic.client_sessions(m, qois)]
    assert sessions() == sessions()
    firsts = [s[0] for s in sessions()]
    taus = tuple(m["taus"])
    starts = [len(taus) - len(t) for _, _, t in firsts]
    assert starts == [c * len(taus) // clients for c in range(clients)]
    assert len({name for name, _, _ in firsts}) == clients
    dealt = collections.Counter(q for per in sessions() for _, q, _ in per)
    assert set(dealt) == {"VTOT", "T", "C"}      # the mix's order, not Mach
    assert dealt["VTOT"] > dealt["T"] > dealt["C"]
