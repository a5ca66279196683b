"""Differential decode conformance: every decode path agrees bit-for-bit.

The codebase now carries THREE independent decode implementations —

  * "host"   — the numpy byte-plane fallback (reference),
  * "kernel" — the interpret-mode Pallas ``bitplane_unpack`` kernel feeding
               the host sign/scale stage,
  * "fused"  — the device-resident fused unpack + sign + scale
               (``kernels/ops.decode_values_fused``, one jit dispatch) —

selected by ``ops.set_decode_path``.  Progressive retrieval is only
trustworthy if the choice is *unobservable*: identical values (down to the
sign of zero), identical certified bounds, and identical FetchStats byte
accounting on every method, at every plane count, on both sides of the
hi/lo uint32 split (nbits=48 > 32 forces split words), for all-negative and
all-nonnegative sign planes, and across empty refinements.  This suite
pins exactly that, property-based via tests/_hypothesis_shim (the real
hypothesis package when installed, a deterministic seeded sweep otherwise).

Tier-1 by design: no ``slow`` marker — a decode-path divergence must fail
the default gate, not a nightly.
"""
import numpy as np
import pytest

from _hypothesis_shim import given, settings, strategies as st

from repro.bitplane.encoder import (DEFAULT_NBITS, decode_magnitudes,
                                    decode_prefix, decode_values,
                                    encode_level, plane_bound)
from repro.bitplane.segments import LevelStream
from repro.core.refactor import METHODS, refactor_variables
from repro.kernels import ops
from repro.options import SessionOptions
from repro.store import memory_store_archive

PATHS = ("host", "kernel", "fused")
# {0, 1} = degenerate prefixes, {47, 48} = deepest planes, {15..17, 31..33}
# = both sides of the hi/lo uint32 word split (planes 0..15 shift the hi
# word, 16..47 the lo word) and of the 32-plane mark
PLANE_COUNTS = (0, 1, 15, 16, 17, 31, 32, 33, 47, 48)


@pytest.fixture()
def restore_decode_path():
    prev = ops.decode_path()
    yield
    ops.set_decode_path(prev)


def _bits(a):
    return np.asarray(a, dtype=np.float64).view(np.uint64)


def _coeffs(n, seed, sign_mode):
    rng = np.random.default_rng(seed)
    c = rng.standard_normal(n) * np.exp(rng.uniform(-6, 6, size=n))
    if sign_mode == "all_neg":
        c = -np.abs(c) - 1e-9
    elif sign_mode == "all_nonneg":
        c = np.abs(c)
    else:
        c[rng.integers(0, 2, size=n).astype(bool)] *= -1.0
    return c


def _decode_all_paths(lbp, k):
    out = {}
    prev = ops.decode_path()
    try:
        for path in PATHS:
            ops.set_decode_path(path)
            out[path] = decode_prefix(lbp, k)
    finally:
        ops.set_decode_path(prev)
    return out


# ----------------------------------------------------- prefix decode level --


@pytest.mark.parametrize("k", PLANE_COUNTS)
@pytest.mark.parametrize("sign_mode", ("mixed", "all_neg", "all_nonneg"))
def test_prefix_decode_paths_bit_identical(k, sign_mode):
    """The plane-count x sign-plane grid: every path, every prefix depth,
    both sides of the hi/lo split, all-negative and all-nonnegative signs."""
    lbp = encode_level(_coeffs(700, seed=k * 7 + 1, sign_mode=sign_mode))
    vals = _decode_all_paths(lbp, k)
    for path in PATHS[1:]:
        assert np.array_equal(_bits(vals["host"]), _bits(vals[path])), \
            f"path {path!r} diverged from host at k={k} ({sign_mode})"
    # the certified bound is decode-path independent by construction (it is
    # metadata arithmetic) — pin it anyway so a refactor cannot couple them
    assert plane_bound(lbp, k) == plane_bound(lbp, min(k, lbp.nbits))


@settings(max_examples=15, deadline=None)
@given(data=st.data())
def test_prefix_decode_paths_bit_identical_property(data):
    """Property form: random sizes (crossing uint32-word boundaries), random
    magnitudes spanning ~12 decades, random prefix depth."""
    n = data.draw(st.sampled_from([1, 31, 32, 33, 257, 700, 1024]))
    k = data.draw(st.integers(min_value=0, max_value=DEFAULT_NBITS))
    seed = data.draw(st.integers(min_value=0, max_value=2 ** 16))
    sign_mode = data.draw(st.sampled_from(["mixed", "all_neg", "all_nonneg"]))
    lbp = encode_level(_coeffs(n, seed=seed, sign_mode=sign_mode))
    vals = _decode_all_paths(lbp, k)
    for path in PATHS[1:]:
        assert np.array_equal(_bits(vals["host"]), _bits(vals[path]))


def test_all_zero_group_every_path(restore_decode_path):
    """exponent=None groups decode to exact zeros on every path."""
    lbp = encode_level(np.zeros(100))
    assert lbp.exponent is None
    for path in PATHS:
        ops.set_decode_path(path)
        v = decode_prefix(lbp, 48)
        assert v.shape == (100,) and not v.any()


def test_shared_entry_matches_legacy_pair(restore_decode_path):
    """decode_prefix is the one decode entry point (train/checkpoint.py
    restores through it): on every path it must equal the legacy
    decode_magnitudes -> decode_values pair bit-for-bit."""
    lbp = encode_level(_coeffs(513, seed=3, sign_mode="mixed"))
    for k in (0, 1, 17, 48):
        legacy = decode_values(lbp, decode_magnitudes(lbp, k))
        for path in PATHS:
            ops.set_decode_path(path)
            assert np.array_equal(_bits(decode_prefix(lbp, k)),
                                  _bits(legacy)), (path, k)


# ------------------------------------------------ streams and refinements --


def _stream_schedule(lbp, schedule, path):
    prev = ops.set_decode_path(path)
    try:
        s = LevelStream(lbp)
        trace = []
        for k in schedule:
            moved = s.fetch_to_planes(k)
            trace.append((moved, s.bytes_fetched, s.fetched, s.bound,
                          _bits(s.values()).copy()))
        return trace
    finally:
        ops.set_decode_path(prev)


@pytest.mark.parametrize("schedule", [
    (0, 1, 1, 17, 17, 48),     # empty refinements interleaved with real ones
    (16, 16, 32, 32, 48, 48),  # refine exactly at the hi/lo boundary
    (48, 48),                  # one-shot then a no-op refinement
    (0, 0, 0),                 # nothing ever moves
])
def test_stream_refinement_schedules_identical_across_paths(schedule):
    """A LevelStream walked through any refinement schedule — including
    empty refinements (repeat requests at an already-fetched depth) — must
    report identical per-step moved bytes, cumulative bytes, plane counts,
    bounds, and decoded bits on every path.  The fused path defers its
    decode to flush time, which must never leak into the accounting."""
    lbp = encode_level(_coeffs(700, seed=11, sign_mode="mixed"))
    ref = _stream_schedule(lbp, schedule, "host")
    for path in PATHS[1:]:
        got = _stream_schedule(lbp, schedule, path)
        for step, (r, g) in enumerate(zip(ref, got)):
            assert r[:4] == g[:4], (path, step)       # bytes/counts/bound
            assert np.array_equal(r[4], g[4]), (path, step)


def test_fused_values_device_matches_host_values(restore_decode_path):
    """values_device() (the recompose feed) and values() expose the same
    bits; on the host path values_device() is absent (None)."""
    lbp = encode_level(_coeffs(700, seed=5, sign_mode="mixed"))
    ops.set_decode_path("fused")
    s = LevelStream(lbp)
    s.fetch_to_planes(33)
    dev = s.values_device()
    assert dev is not None
    assert np.array_equal(_bits(np.asarray(dev)), _bits(s.values()))
    ops.set_decode_path("host")
    s2 = LevelStream(lbp)
    s2.fetch_to_planes(33)
    assert s2.values_device() is None
    assert np.array_equal(_bits(s2.values()), _bits(s.values()))


# ------------------------------------------------------ zero-plane flushes --


def _fused_inputs(lbp, k):
    from repro.bitplane.encoder import inflate_planes, sign_plane_bytes
    m = lbp.meta()
    words, shifts = inflate_planes(m.count, m.nbits, lbp.planes[:k], 0)
    sb = sign_plane_bytes(m.count, lbp.signs)
    scale = np.float64(2.0) ** (m.exponent - m.nbits)
    return words, shifts, sb, scale


def test_zero_plane_fused_flush_is_noop():
    """A flush with ZERO new planes (e.g. a follow-mode refresh that moved
    nothing) must pass the magnitude state through untouched and decode the
    same bits — for both degenerate word layouts, (0,) and (0, 0) — and
    ``prepare_fused_decode`` must keep the group's TRUE word width for
    them, not collapse state/signs to zero-width arrays."""
    lbp = encode_level(_coeffs(700, seed=21, sign_mode="mixed"))
    m = lbp.meta()
    words, shifts, sb, scale = _fused_inputs(lbp, 17)
    mag, vals = ops.decode_values_fused(words, shifts, None, sb, scale,
                                        m.count)
    ref = _bits(np.asarray(vals)).copy()
    for empty in (np.zeros((0,), np.uint32), np.zeros((0, 0), np.uint32)):
        mag2, vals2 = ops.decode_values_fused(empty,
                                              np.zeros(0, np.uint64),
                                              mag, sb, scale, m.count)
        assert np.array_equal(np.asarray(mag2), np.asarray(mag)), empty.shape
        assert vals2.shape == (m.count,)
        assert np.array_equal(_bits(np.asarray(vals2)), ref), empty.shape
    nwords = (m.count + 31) // 32
    w, sh, st, sbp = ops.prepare_fused_decode(np.zeros((0,), np.uint32),
                                              np.zeros(0, np.uint64),
                                              mag, sb, m.count)
    assert w.shape[1] == nwords
    assert st.shape[0] == nwords * 32 and sbp.shape[0] == nwords * 4
    assert not w.any() and not sh.any()          # pure no-op planes


def test_batched_zero_plane_ticket_bit_identical(restore_decode_path):
    """A DecodeBatcher bucket containing a zero-plane item: the empty item
    keeps its group's word width (so it SHARES the bucket with a real
    same-width flush instead of forcing a stray dispatch), comes back
    shaped (count,), and matches the solo fused dispatch bit-for-bit —
    without disturbing its batch-mate."""
    from repro.serve.batch import DecodeBatcher

    ops.set_decode_path("fused")
    lbp = encode_level(_coeffs(700, seed=22, sign_mode="mixed"))
    m = lbp.meta()
    words, shifts, sb, scale = _fused_inputs(lbp, 17)
    empty_w = np.zeros((0,), np.uint32)
    empty_s = np.zeros(0, np.uint64)
    mag_a, vals_a = ops.decode_values_fused(words, shifts, None, sb, scale,
                                            m.count)
    state = np.asarray(mag_a)
    mag_b, vals_b = ops.decode_values_fused(empty_w, empty_s, state, sb,
                                            scale, m.count)
    batcher = DecodeBatcher(window_ms=0.0)
    t_real = batcher.submit_decode(words, shifts, None, sb, scale, m.count)
    t_zero = batcher.submit_decode(empty_w, empty_s, state, sb, scale,
                                   m.count)
    assert t_real.key == t_zero.key          # one shared vmapped bucket
    batcher.flush()
    got_mag_z, got_vals_z = t_zero.result()
    _, got_vals_r = t_real.result()
    stats = batcher.stats.as_dict()
    assert stats["decode_dispatches"] == 1 and stats["decode_batched"] == 2
    assert got_vals_z.shape == (m.count,)
    assert np.array_equal(_bits(np.asarray(got_vals_r)),
                          _bits(np.asarray(vals_a)))
    assert np.array_equal(_bits(np.asarray(got_vals_z)),
                          _bits(np.asarray(vals_b)))
    assert np.array_equal(np.asarray(got_mag_z), np.asarray(mag_b))


# -------------------------------------------- sessions across all methods --


def _session_run(archive, path, eps_ladder=(1e-2, 1e-5)):
    prev = ops.set_decode_path(path)
    try:
        with memory_store_archive(archive) as sa:
            session = sa.open(SessionOptions(prefetch_depth=0))
            out = []
            for eps in eps_ladder:
                for name in archive.variables:
                    data, achieved = session.reconstruct(name, eps)
                    out.append((name, eps, achieved, _bits(data).copy()))
            stats = sa.fetcher.stats
            return out, session.bytes_retrieved, stats.bytes_fetched, \
                stats.store_reads
    finally:
        ops.set_decode_path(prev)


@pytest.mark.parametrize("method", METHODS)
def test_session_paths_bit_identical_all_methods(method):
    """Store-backed progressive sessions under every method (hb / ob /
    psz3 / psz3_delta): reconstructions, certified bounds, session byte
    accounting AND the fetcher's FetchStats (bytes_fetched, store_reads)
    must not depend on the decode path."""
    rng = np.random.default_rng(2)
    fields = {"u": rng.standard_normal((33, 17)),
              "v": np.abs(rng.standard_normal(400))}    # all-nonneg signs
    archive = refactor_variables(fields, method=method)
    ref, ref_bytes, ref_fetched, ref_reads = _session_run(archive, "host")
    for path in PATHS[1:]:
        got, got_bytes, got_fetched, got_reads = _session_run(archive, path)
        assert got_bytes == ref_bytes, path
        assert got_fetched == ref_fetched, path
        assert got_reads == ref_reads, path
        for (rn, re_, rb, rv), (gn, ge_, gb, gv) in zip(ref, got):
            assert (rn, re_) == (gn, ge_)
            assert rb == gb, (path, rn, re_)
            assert np.array_equal(rv, gv), (path, rn, re_)


def test_incremental_tighten_equals_fresh_session_fused(restore_decode_path):
    """Fused path, progressive tightening: a session walked down an eps
    ladder ends bit-identical (data AND bytes) to a fresh fused session at
    the final eps — deferred flushes compose across refinements."""
    rng = np.random.default_rng(7)
    fields = {"w": rng.standard_normal((65,))}
    archive = refactor_variables(fields, method="hb")
    ops.set_decode_path("fused")
    walked = archive.open()
    for eps in (1e-1, 1e-3, 1e-6):
        data_w, _ = walked.reconstruct("w", eps)
    fresh = archive.open()
    data_f, _ = fresh.reconstruct("w", 1e-6)
    assert np.array_equal(_bits(data_w), _bits(data_f))
    assert walked.bytes_retrieved == fresh.bytes_retrieved
    # and the host reference agrees
    ops.set_decode_path("host")
    data_h, _ = archive.open().reconstruct("w", 1e-6)
    assert np.array_equal(_bits(data_h), _bits(data_f))


# ------------------------------------------------- device scatter+recompose --


def test_scatter_recompose_matches_host_scatter():
    """Device scatter+partial-recompose (the fused contribution path) is
    bit-identical to the host scatter feeding recompose_hb_from, for every
    level including the base group, and under the vmapped batch variant."""
    import jax.numpy as jnp

    from repro.transform.hierarchical import (recompose_hb_from,
                                              scatter_recompose_from,
                                              scatter_recompose_from_batch)
    rng = np.random.default_rng(9)
    field = rng.standard_normal((33, 33))
    archive = refactor_variables({"f": field}, method="hb")
    var = archive.variables["f"]
    shape, levels = var.padded_shape, var.levels
    session = archive.open()
    session.reconstruct("f", 1e-4)
    reader = session.readers["f"]
    singles, idx_b, vals_b = [], [], []
    for l in range(levels + 1):
        vals = reader.streams[l].values()
        idx = var.group_indices[l]
        start = min(l, levels - 1)
        flat = np.zeros(int(np.prod(shape)))
        flat[idx] = vals
        host = np.asarray(recompose_hb_from(flat.reshape(shape), levels,
                                            start))
        dev = np.asarray(scatter_recompose_from(jnp.asarray(vals), shape,
                                                levels, start))
        assert np.array_equal(_bits(host), _bits(dev)), l
        singles.append((start, host))
    # batch variant: duplicate one level's contribution across a batch axis
    start, host = singles[0]
    vals0 = jnp.asarray(reader.streams[0].values())
    out = scatter_recompose_from_batch(jnp.stack([vals0, vals0]), shape,
                                       levels, start)
    for b in range(2):
        assert np.array_equal(_bits(np.asarray(out[b])), _bits(host))
