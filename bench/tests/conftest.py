"""Fixtures shared by the benchmark's tests: the harness steered to the
CPU, its trace reduction pointed at the CPU's planes, and each cell cut to
the test size its configuration's manifest gives under ``cpu_test``."""
import functools
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace_reduce  # noqa: E402

CPU_TEST = "cpu_test"


def _is_cpu_line(name: str) -> bool:
    return name.startswith("tf_XLA")


def cpu_test_cell(name: str, root: Path = ROOT) -> harness.Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its manifest
    updated by the manifest's own ``cpu_test`` entry.  A configuration
    without one fails the test with a message that names the key."""
    cell = harness.load_cell(name, root=root)
    if CPU_TEST not in cell.manifest:
        pytest.fail(f"configuration {cell.manifest.get('name')!r} of cell "
                    f"{name!r} has no {CPU_TEST!r} key in its manifest under "
                    f"{cell.config_dir}: give it the sizes that a CPU test "
                    f"run can hold")
    cell.manifest.update(cell.manifest[CPU_TEST])
    return cell


@pytest.fixture
def tiny_cell():
    return cpu_test_cell


@pytest.fixture
def on_cpu(monkeypatch):
    """The harness's device check passes on the CPU, and the test
    process's compile cache is left as it was."""
    monkeypatch.setattr(harness, "check_devices", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "off")


@pytest.fixture
def cpu_planes(monkeypatch):
    """The harness's trace reduction reads the CPU's XLA lines as the
    device."""
    monkeypatch.setattr(trace_reduce, "reduce_trace", functools.partial(
        trace_reduce.reduce_trace, device_plane=lambda n: n == "/host:CPU",
        busy_line=_is_cpu_line, program_line=_is_cpu_line))
