"""The QoI catalog follows the archive: a server offers each known QoI its
archive's variables allow, refuses the others before any retrieval, and
serves the S3D species products certified."""
import numpy as np
import pytest

import repro.launch.serve as serve
from repro.core import catalog, ge, s3d
from repro.data.synthetic import ge_like_fields, nyx_like_fields, s3d_like_fields

SPECIES = ("x0", "x1", "x3", "x4", "x5")
TAUS = (1e-2, 1e-4, 1e-6)
# Each float64 product, served and true, is rounded once: by at most half
# an ulp of its largest magnitude.  The two roundings are the only room a
# certified bound leaves the true error, and the only room between the
# program's range (of its device product) and NumPy's.
ROUNDING = 2.0 * np.finfo(np.float64).eps


def _species(shape, seed=13):
    fields = s3d_like_fields(shape=shape, seed=seed)
    return {k: fields[k] for k in SPECIES}


@pytest.mark.parametrize("make, expected", [
    (lambda: ge_like_fields(n=257, seed=1), set(ge.all_qois())),
    (lambda: nyx_like_fields(shape=(5, 9, 9), seed=2), {"VTOT"}),
    (lambda: _species((5, 9, 9)), {"x1x3", "x0x4", "x1x5", "x3x4"}),
], ids=["ge", "isabel-velocity", "s3d-species"])
def test_offered_catalog_follows_the_archive(make, expected):
    server = serve.RetrievalServer(make(), workers=1)
    try:
        assert set(server.qois) == expected
    finally:
        server.close()


def test_catalogs_share_no_name():
    assert not set(ge.all_qois()) & set(s3d.all_qois())
    assert len(catalog.KNOWN) == len(ge.all_qois()) + len(s3d.all_qois())
    assert catalog.KNOWN["x1x3"] == s3d.all_qois()["x1x3"]


def test_unknown_qoi_is_refused_before_any_retrieval():
    server = serve.RetrievalServer(nyx_like_fields(shape=(5, 9, 9), seed=2),
                                   workers=1)
    try:
        req = serve.Request(client="c0", qois=["x1x3"], tau=1e-2)
        with pytest.raises(ValueError, match=r"'x1x3'.*offers VTOT"):
            server.submit(req)
        with pytest.raises(ValueError, match="offers VTOT"):
            server.handle(req)
        with pytest.raises(ValueError, match="offers VTOT"):
            server.handle_inline(req)
        m = server.metrics()
        assert not server.sessions
        assert m["retrieval_iterations_total"] == 0
        assert m["retrieval_planes_total"] == 0
        assert m["xfer_d2h_bytes_total"] == m["xfer_h2d_bytes_total"] == 0
    finally:
        server.close()


def test_species_products_are_served_certified(tmp_path):
    """Each product down the tau ladder in a session of its own, through
    ``RetrievalServer.submit`` over a store, against the plain NumPy
    product of the full-precision fields."""
    fields = _species((17, 17, 17))
    server = serve.RetrievalServer(fields, store_path=str(tmp_path / "s.prs"),
                                   workers=2, decode_batch_ms=5.0)
    try:
        for name, (a, b) in zip(s3d.all_qois(), s3d.PRODUCTS):
            truth = fields[a] * fields[b]
            for tau in TAUS:
                out = server.submit(serve.Request(
                    client=name, qois=[name], tau=tau)).result(300)
                assert out["guaranteed"] and not out["degraded"]
                session = server.sessions[name]
                served = session.current(a)[0] * session.current(b)[0]
                peak = float(np.max(np.abs(truth)))
                bound = out["est_errors"][name]
                err = float(np.max(np.abs(served - truth)))
                rng = float(np.max(served) - np.min(served))
                assert err <= bound + ROUNDING * peak, (name, tau)
                assert bound <= tau * rng + ROUNDING * peak, (name, tau)
    finally:
        server.close()
