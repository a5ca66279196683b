"""The benchmark's own references, generators and peaks table, against
the program's QoIs and generators on tiny fields."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, synthetic  # noqa: E402

CONFIGS = ROOT / "bench" / "configs"


def _module(config, stem):
    return harness.load_module(CONFIGS / config / f"{stem}.py")


@pytest.mark.parametrize("config", ["isabel-velocity", "ge-cfd"])
def test_reference_matches_the_program_qoi(config):
    from repro.core import ge
    from repro.data.synthetic import ge_like_fields
    ref = _module(config, "reference")
    fields = ge_like_fields(n=513, seed=3)
    program = ge.all_qois()
    for name in ref.VARIABLES:
        mine = ref.qoi(name, fields)
        theirs = np.asarray(program[name].value(fields))
        np.testing.assert_allclose(mine, theirs, rtol=1e-12, atol=0)
        assert set(ref.VARIABLES[name]) == set(program[name].variables())


def test_ge_generator_is_the_program_generator():
    from repro.data.synthetic import ge_like_fields
    manifest = json.loads((CONFIGS / "ge-cfd" / "config.json").read_text())
    tiny = dict(manifest, **manifest["cpu_test"])
    assert tiny["nodes"] == 1000
    mine = _module("ge-cfd", "generate").generate(tiny, 2**31 + 7)
    theirs = ge_like_fields(n=1000, seed=2**31 + 7)
    assert mine.keys() == theirs.keys()
    for k in mine:
        np.testing.assert_array_equal(mine[k], theirs[k])
    assert (mine["Vx"] == 0).sum() == 20


def test_isabel_generator_is_float32_valued_and_seeded():
    manifest = harness.load_cell("isabel.ladder").manifest
    tiny = dict(manifest, shape=[5, 9, 9])
    gen = _module("isabel-velocity", "generate").generate
    a, b, c = gen(tiny, 11), gen(tiny, 11), gen(tiny, 12)
    for name, spec in manifest["fields"].items():
        assert a[name].dtype == np.float64 and a[name].shape == (5, 9, 9)
        np.testing.assert_array_equal(a[name].astype(np.float32), a[name])
        np.testing.assert_array_equal(a[name], b[name])
        assert not np.array_equal(a[name], c[name])
        lo, hi = spec["range"]
        assert lo - 1e-4 <= a[name].min() and a[name].max() <= hi + 1e-4


def test_smooth_field_is_the_program_copy():
    from repro.data.synthetic import smooth_field
    np.testing.assert_array_equal(synthetic.smooth_field((7, 9), 5),
                                  smooth_field((7, 9), 5))


def test_peaks_known_and_unknown_kind():
    v5e = harness.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError, match="no peaks"):
        harness.load_peaks("TPU v9 imaginary")


def test_check_devices_refuses_the_cpu():
    with pytest.raises(harness.BenchError, match="no TPU"):
        harness.check_devices(1)
