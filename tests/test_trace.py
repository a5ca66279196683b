"""The served path's spans and counters (repro.trace): spans are host
events in the profiler's trace and record nothing without one; the
transfer, round and compile counters that ``RetrievalServer.metrics()``
reports equal what they count."""
import glob
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.bitplane.segments as segments
import repro.core.refactor as refactor
import repro.core.retrieval as retrieval
import repro.launch.serve as serve
from repro import trace
from repro.core.qoi import Var
from repro.data.synthetic import nyx_like_fields
from repro.kernels import ops

SHAPE = (9, 17, 17)
TAUS = (1e-2, 1e-4)


def _repro_events(logdir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    return [ev.name for plane in ProfileData.from_file(path).planes
            for line in plane.lines for ev in line.events
            if ev.name.startswith("repro.")]


def test_spans_record_nothing_without_a_profiler_and_counters_count(
        tmp_path):
    xfer = trace.TransferStats()
    with trace.span(trace.REQUEST, client="c0", seq=0, tau=1e-2):
        out = trace.to_host(jnp.arange(4.0), xfer)
    trace.note_h2d(xfer, np.zeros(3))
    assert xfer.as_dict() == {"d2h_bytes_total": float(out.nbytes),
                              "h2d_bytes_total": 24.0}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with trace.span(trace.ESTIMATE):
        trace.to_host(jnp.arange(2.0))
    jax.profiler.stop_trace()
    # only what ran under the profiler is in its trace
    assert sorted(_repro_events(tmp_path)) == sorted(
        [trace.ESTIMATE, trace.DEVICE_WAIT, trace.TRANSFER_D2H])


def test_span_names_are_one_table():
    assert len(set(trace.SPANS)) == len(trace.SPANS) == 10
    assert all(name.startswith("repro.") for name in trace.SPANS)


@pytest.fixture
def spied(monkeypatch):
    """Every device-to-host copy of the retrieval path and every request's
    Alg-2 rounds, seen from outside the counters."""
    copied, rounds = [], []

    def spy_to_host(x, xfer=None):
        out = trace.to_host(x, xfer)
        copied.append(out.nbytes)
        return out

    def spy_retrieve(session, reqs):
        res = retrieval.retrieve_qoi_controlled(session, reqs)
        rounds.append(len(res.iterations))
        return res
    for module in (refactor, segments, retrieval):
        monkeypatch.setattr(module, "to_host", spy_to_host)
    monkeypatch.setattr(serve, "retrieve_qoi_controlled", spy_retrieve)
    return copied, rounds


@pytest.mark.parametrize("path", ["fused", "host"])
def test_counters_equal_what_is_reckoned(tmp_path, spied, path):
    """Two clients down the tau ladder on a tiny served archive, with the
    decode on the device (``fused``: batcher tickets and device values)
    and on the host (the host route's scatter and upload)."""
    copied, rounds = spied
    fields = nyx_like_fields(shape=SHAPE, seed=3)
    prev = ops.set_decode_path(path)
    try:
        server = serve.RetrievalServer(fields, store_path=str(
            tmp_path / "v.prs"), workers=2, decode_batch_ms=5.0)
        try:
            def client(c):
                for tau in TAUS:
                    out = server.submit(serve.Request(
                        client=f"c{c}", qois=["VTOT"], tau=tau)).result(120)
                    assert out["guaranteed"]
            threads = [threading.Thread(target=client, args=(c,))
                       for c in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(300)
                assert not t.is_alive()
            m = server.metrics()
        finally:
            server.close()
    finally:
        ops.set_decode_path(prev)
    assert m["xfer_d2h_bytes_total"] == sum(copied) > 0
    assert m["retrieval_iterations_total"] == sum(rounds) >= 2 * len(TAUS)
    # every round uploads VTOT's three value fields and their bounds
    field_bytes = 8 * int(np.prod(SHAPE))
    assert m["xfer_h2d_bytes_total"] >= sum(rounds) * 6 * field_bytes


def test_compiles_count_from_the_servers_construction(tmp_path):
    fields = nyx_like_fields(shape=(5, 9, 9), seed=4)
    server = serve.RetrievalServer(fields, workers=1)
    try:
        assert server.metrics()["compiles_total"] == 0
        f = jax.jit(lambda x: x * 3.0 + 1.0)
        f(np.ones(11)).block_until_ready()
        first = server.metrics()
        f(np.ones(11)).block_until_ready()          # cached: no compile
        again = server.metrics()
    finally:
        server.close()
    assert first["compiles_total"] >= 1
    assert first["compile_seconds_total"] > 0
    assert again["compiles_total"] == first["compiles_total"]


def test_estimator_program_has_a_stable_name():
    expr = Var("a") * Var("b")
    values = {"a": np.full(5, 2.0), "b": np.full(5, 3.0)}
    ebs = {"a": np.full(5, 0.1), "b": np.full(5, 0.1)}
    xfer = trace.TransferStats()
    val, bound = retrieval._estimate(expr, values, ebs, xfer)
    assert np.allclose(val, 6.0) and (bound > 0).all()
    assert xfer.d2h_bytes == val.nbytes + bound.nbytes
    assert xfer.h2d_bytes == 4 * 5 * 8
    fn = retrieval._JIT_CACHE[(expr, ("a", "b"), ((5,), (5,)))]
    text = fn.lower(values, ebs).as_text()
    assert "jit__qoi_estimate" in text and "lambda" not in text
