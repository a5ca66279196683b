"""The S3D species configuration: its reference against the program's
products, its generator, the check's control and fault in its cell, and
the cell's own per-layer metrics, at the configuration's CPU test size."""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import control, harness  # noqa: E402

CELL = "s3d.ladder"
CONFIG = ROOT / "bench" / "configs" / "s3d-species"


def _module(stem):
    return harness.load_module(CONFIG / f"{stem}.py")


def run(cell, plant=None, trace=False, seed=2**31 + 41):
    return harness.run_cell(cell, seed, 2.0, trace,
                            t_start=time.perf_counter(), log=lambda s: None,
                            plant=plant)


def test_reference_matches_the_program_products():
    from repro.core import s3d
    manifest = harness.load_cell(CELL).manifest
    fields = _module("generate").generate(dict(manifest, shape=[5, 6, 7]), 3)
    ref = _module("reference")
    program = s3d.all_qois()
    assert list(ref.VARIABLES) == list(program) == manifest["qois"]
    for name in ref.VARIABLES:
        assert set(ref.VARIABLES[name]) == set(program[name].variables())
        np.testing.assert_allclose(ref.qoi(name, fields),
                                   np.asarray(program[name].value(fields)),
                                   rtol=1e-12, atol=0)


def test_generator_is_seeded_float64_positive_and_spans_its_decades():
    manifest = harness.load_cell(CELL).manifest
    tiny = dict(manifest, shape=[9, 10, 11])
    gen = _module("generate").generate
    a, b, c = gen(tiny, 2**31 + 5), gen(tiny, 2**31 + 5), gen(tiny, 6)
    assert list(a) == list(manifest["fields"])
    for name, spec in manifest["fields"].items():
        x = a[name]
        assert x.dtype == np.float64 and x.shape == (9, 10, 11)
        assert not np.array_equal(x.astype(np.float32).astype(np.float64), x)
        np.testing.assert_array_equal(x, b[name])
        assert not np.array_equal(x, c[name])
        lo, hi = spec["range"]
        assert (x > 0).all()
        # the exponent field runs exactly over [log10 lo, log10 hi]
        np.testing.assert_allclose([x.min(), x.max()], [lo, hi], rtol=1e-12)
        assert np.log10(hi / lo) >= 2            # decades inside the field


@pytest.mark.parametrize("kind, check", [("control", "bound_over_tau"),
                                         ("stale", "err_over_bound")])
def test_control_and_stale_fault_are_not_correct(on_cpu, tiny_cell, kind,
                                                 check):
    out = run(tiny_cell(CELL), plant=control.PLANTS[kind])
    assert not out["correct"]
    assert out["checks"][check]["value"] > 1.0
    assert out["failed"] == 0          # it claims to certify every answer


def test_traced_run_reports_the_cells_metrics(on_cpu, cpu_planes, tiny_cell):
    out = run(tiny_cell(CELL), trace=True)
    assert out["correct"], out["checks"]
    metrics = out["metrics"]
    assert {"reassign_ms_per_answer", "planes_per_answer"} <= set(metrics)
    assert metrics["reassign_ms_per_answer"]["value"] > 0
    assert metrics["planes_per_answer"]["value"] > 0
    assert metrics["planes_per_answer"]["unit"] == "planes"
