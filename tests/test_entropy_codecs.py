"""Plane-codec conformance suite: every registered codec must round-trip
bit-identically on planes of every shape the encoder can produce (and some
it can't), the registry must reject unknown ids, and corrupted payloads
must raise — never decode to garbage.

Property-based via tests/_hypothesis_shim (real hypothesis when installed,
a seeded deterministic sampler otherwise).
"""
import zlib

import numpy as np
import pytest

from repro.bitplane import codecs as C
from repro.bitplane.encoder import encode_level, decode_magnitudes, \
    decode_values
from repro.options import OpenOptions
from repro.store import ChecksumError

from tests._hypothesis_shim import given, settings, strategies as st

ALL_CODECS = sorted(C.registered_codecs())


def _plane_bytes(pattern: str, n: int, density: float, seed: int) -> bytes:
    """Packed plane bytes across the densities that matter: all-zero
    (MSB of smooth data), all-one, bernoulli(density), and adversarial
    bit-alternating planes that defeat run-length coding."""
    rng = np.random.default_rng(seed)
    if pattern == "zeros":
        bits = np.zeros(n * 8, dtype=bool)
    elif pattern == "ones":
        bits = np.ones(n * 8, dtype=bool)
    elif pattern == "random":
        bits = rng.random(n * 8) < density
    elif pattern == "alternating":
        bits = (np.arange(n * 8) % 2).astype(bool)
    else:  # "bursty": zero stretches broken by dense bursts
        bits = np.zeros(n * 8, dtype=bool)
        for _ in range(max(1, n // 64) if n else 0):
            s = int(rng.integers(0, max(1, n * 8 - 32)))
            e = min(s + 32, bits.size)       # planes under 32 bits: clip
            bits[s:e] = rng.random(e - s) < 0.8
    return np.packbits(bits).tobytes()


PATTERNS = ("zeros", "ones", "random", "alternating", "bursty")


# ---------------------------------------------------------- round-trips --


@settings(max_examples=40, deadline=None)
@given(pattern=st.sampled_from(PATTERNS),
       n=st.integers(min_value=0, max_value=2048),
       density=st.floats(min_value=0.0, max_value=1.0),
       seed=st.integers(min_value=0, max_value=2 ** 16))
def test_every_codec_roundtrips_bit_identically(pattern, n, density, seed):
    data = _plane_bytes(pattern, n, density, seed)
    for name in ALL_CODECS:
        codec = C.registered_codecs()[name]
        payload = codec.encode(data)
        assert codec.decode(payload, len(data)) == data, (name, pattern, n)


@settings(max_examples=40, deadline=None)
@given(pattern=st.sampled_from(PATTERNS),
       n=st.integers(min_value=0, max_value=2048),
       density=st.floats(min_value=0.0, max_value=1.0),
       seed=st.integers(min_value=0, max_value=2 ** 16))
def test_cost_model_roundtrips_and_never_beats_raw_plus_tag(pattern, n,
                                                            density, seed):
    data = _plane_bytes(pattern, n, density, seed)
    blob = C.encode_tagged(data)
    assert C.decode_tagged(blob, len(data)) == data
    # raw is always a candidate: a plane never costs more than 1 + len(raw)
    assert len(blob) <= 1 + len(data)
    # the id byte is a registered codec
    if data:
        assert C.get_codec(blob[0]) is not None


@settings(max_examples=25, deadline=None)
@given(n=st.integers(min_value=0, max_value=1024),
       density=st.floats(min_value=0.0, max_value=1.0),
       seed=st.integers(min_value=0, max_value=2 ** 16))
def test_legacy_tags_and_bare_zlib_signs_decode(n, density, seed):
    """v1/v2 dialects: b"R"+raw, b"Z"+zlib planes, untagged zlib signs."""
    data = _plane_bytes("random", n, density, seed)
    assert C.decode_tagged(b"R" + data, len(data)) == data
    assert C.decode_tagged(b"Z" + zlib.compress(data, 1), len(data)) == data
    assert C.decode_sign_blob(zlib.compress(data, 1), len(data)) == data
    assert C.decode_sign_blob(C.encode_tagged(data), len(data)) == data


def test_rans_lane_boundaries_roundtrip():
    """Exact sizes around every lane-count step in RansCodec._lanes_for —
    the interleave layout's off-by-one surface."""
    rng = np.random.default_rng(0)
    for edge in (63, 64, 1 << 8, 1 << 11, 1 << 13, 1 << 16):
        for n in (edge - 1, edge, edge + 1):
            data = rng.integers(0, 7, n, dtype=np.uint8).tobytes()
            assert C.RANS.decode(C.RANS.encode(data), n) == data


# ------------------------------------------------------------- registry --


def test_registry_rejects_unknown_ids():
    for bad in (4, 17, 63, 0x40, 200, 255):
        if bad in {c.codec_id for c in C.registered_codecs().values()}:
            continue
        with pytest.raises(C.CodecError, match="unknown codec"):
            C.get_codec(bad)
        with pytest.raises(C.CodecError):
            C.decode_tagged(bytes([bad]) + b"payload", 7)
    with pytest.raises(C.CodecError, match="empty"):
        C.decode_tagged(b"", 0)


def test_register_rejects_collisions_and_reserved_ids():
    class Dup(C.PlaneCodec):
        codec_id = C.RLE.codec_id
        name = "dup"

    with pytest.raises(ValueError, match="already registered"):
        C.register(Dup())

    class LegacyClash(C.PlaneCodec):
        codec_id = 0x52          # b"R" — must stay un-registrable
        name = "legacy-clash"

    with pytest.raises(ValueError, match="reserved range"):
        C.register(LegacyClash())


def test_default_candidates_knob_roundtrips():
    prev = C.set_default_candidates(["zlib"])
    try:
        assert C.DEFAULT_CANDIDATES == ("zlib",)
        data = np.packbits(np.zeros(512, dtype=bool)).tobytes()
        assert C.encode_tagged(data)[0] in (C.RAW.codec_id,
                                            C.ZLIB.codec_id)
        with pytest.raises(ValueError, match="unknown codec"):
            C.set_default_candidates(["lzma"])
    finally:
        C.set_default_candidates(prev)


# -------------------------------------------------------- corruption fuzz --


@settings(max_examples=30, deadline=None)
@given(pattern=st.sampled_from(PATTERNS),
       n=st.integers(min_value=16, max_value=1024),
       seed=st.integers(min_value=0, max_value=2 ** 16),
       data=st.data())
def test_truncated_payloads_never_return_garbage(pattern, n, seed, data):
    """Any truncation of any codec's payload must raise CodecError — the
    decoder validates lengths/state and can never hand back a wrong-sized
    plane."""
    buf = _plane_bytes(pattern, n, 0.02, seed)
    for name in ALL_CODECS:
        codec = C.registered_codecs()[name]
        payload = codec.encode(buf)
        if not payload:
            continue
        cut = data.draw(st.integers(min_value=0,
                                    max_value=len(payload) - 1),
                        label=f"cut:{name}")
        with pytest.raises(C.CodecError):
            codec.decode(payload[:cut], len(buf))


@settings(max_examples=30, deadline=None)
@given(pattern=st.sampled_from(PATTERNS),
       n=st.integers(min_value=16, max_value=1024),
       seed=st.integers(min_value=0, max_value=2 ** 16),
       data=st.data())
def test_bitflipped_payloads_raise_or_stay_sized(pattern, n, seed, data):
    """Without the store's crc a decoder cannot detect every flipped bit
    (raw provably can't), but it must either raise CodecError or return a
    buffer of exactly the requested size — never a short/long plane that
    would corrupt the magnitude state silently."""
    buf = _plane_bytes(pattern, n, 0.02, seed)
    blob = C.encode_tagged(buf)
    pos = data.draw(st.integers(min_value=1, max_value=len(blob) - 1),
                    label="pos")
    bit = data.draw(st.integers(min_value=0, max_value=7), label="bit")
    corrupt = bytearray(blob)
    corrupt[pos] ^= 1 << bit
    try:
        out = C.decode_tagged(bytes(corrupt), len(buf))
    except C.CodecError:
        return
    assert len(out) == len(buf)


def test_rle_huge_zero_run_raises_before_allocating():
    """Regression: a corrupt varint encoding a petabyte zero run must be
    bounds-checked against out_len BEFORE the run is materialised —
    CodecError, not MemoryError, for a network-delivered payload."""
    payload = bytearray()
    v = 1 << 50
    while v >= 0x80:                      # varint(2^50)
        payload.append((v & 0x7F) | 0x80)
        v >>= 7
    payload.append(v)
    payload.append(0)                     # literal_len = 0
    with pytest.raises(C.CodecError):
        C.RLE.decode(bytes(payload), 512)


def test_raw_plane_decode_is_zero_copy():
    """Raw is ~96% of archived bytes: its decode must return a view into
    the fetched blob, not a per-plane copy."""
    blob = C.encode_tagged(np.random.default_rng(0).integers(
        0, 256, 4096, dtype=np.uint8).tobytes(), density=0.5)
    assert blob[0] == C.RAW.codec_id
    out = C.decode_tagged(blob, 4096)
    assert isinstance(out, memoryview)
    assert out.obj is blob                # view over the original buffer


def test_wrong_codec_id_raises():
    """Re-tagging a payload with a different (registered) codec id must
    fail decode — each payload dialect is self-checking enough that no
    other codec accepts it."""
    rng = np.random.default_rng(1)
    buf = np.packbits(rng.random(8 * 512) < 0.02).tobytes()
    for name in ALL_CODECS:
        codec = C.registered_codecs()[name]
        payload = codec.encode(buf)
        if len(payload) == len(buf):
            continue                      # raw-sized: skip the raw swap
        for other in ALL_CODECS:
            oc = C.registered_codecs()[other]
            if oc.codec_id == codec.codec_id:
                continue
            with pytest.raises(C.CodecError):
                oc.decode(payload, len(buf))


def test_corruption_through_store_raises_integrity_error(tmp_path):
    """The full contract: a truncated or bit-flipped segment, pulled
    through the real store path, surfaces as the store's integrity error
    (crc mismatch or decode failure) — garbage values can never reach the
    reconstruction."""
    from repro.core.refactor import refactor_variables
    from repro.data.synthetic import ge_like_fields
    from repro.store import open_archive, save_archive

    fields = ge_like_fields(n=1 << 10, seed=0)
    vel = {k: fields[k] for k in ("Vx",)}
    arch = refactor_variables(vel, method="hb")
    path = str(tmp_path / "a.prs")
    save_archive(arch, path)

    with open_archive(path) as sa:
        plane_keys = sorted(k for k in sa.fetcher.index if "/p" in k)
        victims = [(k, sa.fetcher.index[k]) for k in plane_keys[:8]]

    rng = np.random.default_rng(3)
    for key, entry in victims:
        with open(path, "rb") as fh:
            original = fh.read()
        corrupt = bytearray(original)
        pos = entry.offset + int(rng.integers(0, entry.size))
        corrupt[pos] ^= 1 << int(rng.integers(0, 8))
        with open(path, "wb") as fh:
            fh.write(bytes(corrupt))
        # verified path: crc catches it before any decode runs
        with open_archive(path) as sa:
            with pytest.raises(ChecksumError):
                sa.fetcher.fetch(key)
        # unverified path (trusted transport): the codec layer must still
        # raise or produce an exactly-sized plane — never a short/long
        # buffer (raw payloads' flipped bits are undetectable without crc)
        with open_archive(path, OpenOptions.unverified()) as sa:
            blob = sa.fetcher.fetch(key)
            want = _plane_len(sa, key)
            try:
                out = C.decode_tagged(blob, want)
            except C.CodecError:
                out = None
            if out is not None:
                assert len(out) == want
        with open(path, "wb") as fh:
            fh.write(original)


def _plane_len(sa, key: str) -> int:
    """Decoded byte length of a bitplane segment: 4 * ceil32(count)."""
    var, group, _ = key.split("/")
    spec = sa.manifest["variables"][var]["groups"][int(group[1:])]
    return 4 * ((spec["count"] + 31) // 32)


# ------------------------------------------------ sign-blob codec routing --


def test_signs_route_through_codec_stage_not_unconditional_zlib():
    """Regression (the old encoder zlib'd signs unconditionally): an
    all-non-negative group's sign plane is all-zero bytes and must collapse
    through the codec stage to a handful of bytes, well under zlib's
    ~11-byte empty-stream floor, while still decoding bit-identically."""
    rng = np.random.default_rng(0)
    vals = np.abs(rng.standard_normal(4096)) + 0.5      # strictly positive
    lbp = encode_level(vals, nbits=32)
    zlib_cost = len(zlib.compress(
        np.packbits(vals < 0).tobytes(), 1))
    assert len(lbp.signs) < zlib_cost
    assert lbp.signs[0] != 0x78           # tagged, not a bare zlib stream
    mag = decode_magnitudes(lbp, lbp.nbits)
    out = decode_values(lbp, mag)
    assert (out >= 0).all()
    np.testing.assert_allclose(out, vals, atol=2.0 ** (lbp.exponent - 31))


def test_mixed_sign_group_roundtrips_through_tagged_signs():
    rng = np.random.default_rng(1)
    vals = rng.standard_normal(2048)
    lbp = encode_level(vals, nbits=40)
    mag = decode_magnitudes(lbp, lbp.nbits)
    out = decode_values(lbp, mag)
    np.testing.assert_array_equal(np.signbit(out)[vals != 0.0],
                                  np.signbit(vals)[vals != 0.0])
    np.testing.assert_allclose(out, vals, atol=2.0 ** (lbp.exponent - 39))
