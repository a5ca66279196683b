"""Integration: Algorithm 2 end-to-end — QoI tolerances are guaranteed met
on every representation, byte accounting is monotone, masks work."""
import numpy as np
import pytest

from repro.core import ge, s3d
from repro.core.refactor import refactor_variables
from repro.core.retrieval import QoIRequest, assign_eb, retrieve_qoi_controlled
from repro.data.synthetic import ge_like_fields, s3d_like_fields

N = 1 << 12


@pytest.fixture(scope="module")
def fields():
    return ge_like_fields(n=N, seed=0)


@pytest.fixture(scope="module")
def archives(fields):
    return {m: refactor_variables(fields, method=m, nbits=40, n_snapshots=8)
            for m in ("hb", "ob", "psz3", "psz3_delta")}


def _check_actual_errors(qois, fields, res):
    orig = {k: np.asarray(v) for k, v in fields.items()}
    for name, expr in qois.items():
        truth = np.asarray(expr.value(orig))
        approx = np.asarray(expr.value(res.values))
        actual = np.abs(truth - approx).max()
        est = res.est_errors[name]
        assert actual <= est * (1 + 1e-9), \
            f"{name}: actual {actual} exceeds estimate {est}"
        assert actual <= res.tau_abs[name] * (1 + 1e-9), \
            f"{name}: actual {actual} exceeds tolerance {res.tau_abs[name]}"


@pytest.mark.parametrize("method", ["hb", "ob", "psz3", "psz3_delta"])
def test_qoi_control_all_methods(fields, archives, method):
    qois = ge.all_qois()
    reqs = [QoIRequest(name=k, expr=e, tau_rel=1e-3) for k, e in qois.items()]
    res = retrieve_qoi_controlled(archives[method].open(), reqs)
    assert res.converged
    _check_actual_errors(qois, fields, res)
    assert 0 < res.bitrate < 64  # must beat raw f64


@pytest.mark.parametrize("tau", [1e-2, 1e-4, 1e-6])
def test_progressive_tolerances_hb(fields, archives, tau):
    qois = {"VTOT": ge.v_total(), "PT": ge.total_pressure()}
    reqs = [QoIRequest(name=k, expr=e, tau_rel=tau) for k, e in qois.items()]
    res = retrieve_qoi_controlled(archives["hb"].open(), reqs)
    assert res.converged
    _check_actual_errors(qois, fields, res)


def test_progressive_session_reuse_is_incremental(fields, archives):
    """Successively tighter requests on ONE session only add bytes —
    Definition 1's incremental-recomposition contract."""
    qois = {"VTOT": ge.v_total()}
    session = archives["hb"].open()
    last_bytes = 0
    bitrates = []
    for tau in [1e-1, 1e-3, 1e-5]:
        reqs = [QoIRequest("VTOT", qois["VTOT"], tau)]
        res = retrieve_qoi_controlled(session, reqs)
        assert res.converged
        assert res.bytes_retrieved >= last_bytes
        last_bytes = res.bytes_retrieved
        bitrates.append(res.bitrate)
    assert bitrates[0] < bitrates[-1]


def test_outlier_mask_prevents_divergence(fields, archives):
    """The zero-velocity wall region must not force full-precision retrieval
    (paper §V-A): VTOT converges with finite estimates despite sqrt(0)."""
    res = retrieve_qoi_controlled(
        archives["hb"].open(), [QoIRequest("VTOT", ge.v_total(), 1e-4)])
    assert res.converged
    assert np.isfinite(res.est_errors["VTOT"])


def test_s3d_multiplication_qois():
    fields = s3d_like_fields(shape=(17, 9, 9))
    sub = {k: fields[k] for k in ("x0", "x1", "x3", "x4")}
    arch = refactor_variables(sub, method="hb", nbits=40,
                              mask_zero_velocity=False)
    qois = {k: s3d.all_qois()[k] for k in ("x1x3", "x0x4")}
    reqs = [QoIRequest(k, e, 1e-4) for k, e in qois.items()]
    res = retrieve_qoi_controlled(arch.open(), reqs)
    assert res.converged
    _check_actual_errors(qois, sub, res)


def test_assign_eb_minimum_rule():
    """Alg 3: a variable used by several QoIs gets the tightest tolerance."""
    reqs = [QoIRequest("a", ge.v_total(), 1e-2),
            QoIRequest("b", ge.mach(), 1e-5)]
    eps = assign_eb(reqs, {v: 10.0 for v in
                           ("Vx", "Vy", "Vz", "P", "D")})
    assert eps["Vx"] == pytest.approx(1e-5 * 10.0)  # Mach is tighter
    assert eps["P"] == pytest.approx(1e-5 * 10.0)


def test_estimated_always_upper_bounds_actual_across_bitrates(fields, archives):
    """Fig 4 invariant: est >= actual at every progressive stage."""
    session = archives["hb"].open()
    expr = ge.total_pressure()
    orig = {k: np.asarray(v) for k, v in fields.items()}
    truth = np.asarray(expr.value(orig))
    for tau in [1e-1, 1e-2, 1e-3, 1e-4, 1e-5]:
        res = retrieve_qoi_controlled(session, [QoIRequest("PT", expr, tau)])
        approx = np.asarray(expr.value(res.values))
        actual = np.abs(truth - approx).max()
        assert actual <= res.est_errors["PT"] * (1 + 1e-9)
