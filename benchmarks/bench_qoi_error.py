"""Paper Figs 4, 5, 6: max estimated vs max actual QoI error under a ladder
of requested QoI tolerances (PMGARD-HB), on GE-like (6 QoIs), NYX-like
(total velocity, 3D) and S3D-like (molar-concentration products) data.

Validated invariants: actual <= estimated (guarantee) and actual <= τ_abs
(requested tolerance met) at every point of every curve.
"""
from __future__ import annotations


from benchmarks.common import actual_qoi_error, timed
from repro.core import ge, s3d
from repro.core.refactor import refactor_variables
from repro.core.retrieval import QoIRequest, retrieve_qoi_controlled
from repro.data.synthetic import ge_like_fields, nyx_like_fields, s3d_like_fields

TAUS = [0.1 * 2.0 ** -i for i in range(0, 20, 3)]


def _sweep(fields, qois, mask_zero_velocity=True, label=""):
    arch = refactor_variables(fields, method="hb", nbits=48,
                              mask_zero_velocity=mask_zero_velocity)
    rows = []
    session = arch.open()      # progressive: one session, tightening taus
    for tau in TAUS:
        reqs = [QoIRequest(k, e, tau) for k, e in qois.items()]
        dt, res = timed(retrieve_qoi_controlled, session, reqs)
        ok = True
        worst_est, worst_act = 0.0, 0.0
        for k, e in qois.items():
            act = actual_qoi_error(e, fields, res.values)
            est = res.est_errors[k]
            ok &= act <= est * (1 + 1e-9) and act <= res.tau_abs[k] * (1 + 1e-9)
            worst_est = max(worst_est, est / max(res.tau_abs[k], 1e-300))
            worst_act = max(worst_act, act / max(res.tau_abs[k], 1e-300))
        rows.append((f"qoi_error/{label}/tau={tau:.2e}", dt * 1e6,
                     f"bitrate={res.bitrate:.3f};est/tau={worst_est:.3f};"
                     f"act/tau={worst_act:.3f};guaranteed={ok}"))
        assert ok, f"QoI guarantee violated at {label} tau={tau}"
    return rows


def run():
    rows = []
    ge_fields = ge_like_fields(n=1 << 15, seed=0)
    rows += _sweep(ge_fields, ge.all_qois(), label="GE-small")

    nyx = nyx_like_fields(shape=(33, 33, 33))
    rows += _sweep(nyx, {"VTOT": ge.v_total()}, mask_zero_velocity=False,
                   label="NYX")

    # Hurricane (Table III): non-cubic 3D velocity grid
    hurricane = nyx_like_fields(shape=(17, 33, 33), seed=42)
    rows += _sweep(hurricane, {"VTOT": ge.v_total()},
                   mask_zero_velocity=False, label="Hurricane")

    s3d_fields = s3d_like_fields(shape=(33, 17, 17))
    sub = {k: s3d_fields[k] for k in ("x0", "x1", "x3", "x4", "x5")}
    rows += _sweep(sub, s3d.all_qois(), mask_zero_velocity=False,
                   label="S3D")
    return rows
