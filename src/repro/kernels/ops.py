"""Jit'd public wrappers for the Pallas kernels.

On CPU the kernels execute in interpret mode — the kernel body runs in
Python for correctness validation. On a TPU backend ``interpret`` flips to
False automatically and the same BlockSpecs drive Mosaic compilation.

Codec dispatch policy: the pack/unpack wrappers pick geometry per backend —
on TPU the canonical 8-row tiles (VMEM-sized, grid-parallel); in interpret
mode a single whole-array tile, so the traced kernel body appears once in
the XLA graph instead of once per grid step (compile time, not VMEM, is the
binding constraint off-TPU).  ``unpack_bitplanes`` additionally falls back
to a bit-identical vectorized NumPy unpack off-TPU: all codec ops are exact
integer ops, so kernel and fallback produce equal words — asserted by
tests/test_incremental_recompose.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.bitplane_pack import (
    BITS,
    bitplane_pack,
    interpret_default as _interpret,
    pack_planes_traced,
    tile_elems,
)
from repro.kernels.bitplane_unpack import bitplane_unpack
from repro.kernels.hier_level import hier_level_surplus
from repro.kernels.qoi_vtotal import qoi_vtotal_fused

LANES = 128

# -- decode-path dispatch ---------------------------------------------------
#
# Three independent decode implementations must agree bit-for-bit (asserted
# by tests/test_decode_conformance.py):
#
#   * "host"   — the vectorized NumPy byte-plane fallback (pure integer ops);
#   * "kernel" — the ``bitplane_unpack`` Pallas kernel (interpret mode off-
#                TPU), magnitudes finished on host;
#   * "fused"  — unpack + sign application + value scaling traced as ONE jit
#                dispatch (``decode_values_fused``), mirroring the fused
#                encode; magnitudes/values stay device-resident so they can
#                feed recompose without a host round-trip.
#
# "auto" (the default) picks "fused" for groups of at least
# ``FUSED_MIN_COUNT`` coefficients and "host" below it: every path is exact,
# so the cutover is purely a dispatch-overhead / jit-compile-cache tradeoff
# (tiny test groups would pay a trace per shape for nothing).

DECODE_PATHS = ("auto", "fused", "kernel", "host")
FUSED_MIN_COUNT = 4096
_decode_path = "auto"


def decode_path() -> str:
    """The active decode-path policy (see DECODE_PATHS)."""
    return _decode_path


def set_decode_path(path: str) -> str:
    """Select the decode path globally; returns the previous policy so tests
    can restore it.  All paths are bit-identical — this is a dispatch knob,
    not a semantics knob."""
    global _decode_path
    if path not in DECODE_PATHS:
        raise ValueError(f"unknown decode path {path!r}; "
                         f"expected one of {DECODE_PATHS}")
    prev, _decode_path = _decode_path, path
    return prev


def use_fused_decode(count: int) -> bool:
    """Whether a group of ``count`` coefficients decodes through the fused
    device path under the active policy."""
    if _decode_path == "fused":
        return True
    if _decode_path == "auto":
        return count >= FUSED_MIN_COUNT
    return False


def _plane_pad(p: int) -> int:
    """Pad plane counts to the next power of two so the fused decode's jit
    cache sees a bounded set of plane-axis shapes (zero planes OR nothing
    into the magnitudes — padding is exact)."""
    n = 1
    while n < p:
        n <<= 1
    return n


def _pad_to(x: jnp.ndarray, mult: int, value=0):
    n = x.shape[0]
    rem = (-n) % mult
    if rem == 0:
        return x, n
    return jnp.pad(x, (0, rem), constant_values=value), n


def pack_bitplanes(mag: jnp.ndarray, nbits: int = 30,
                   rows: int | None = None) -> jnp.ndarray:
    """Arbitrary-length (N,) int32 -> (nbits, ceil32(N)) packed planes.
    Pads with zeros (zero magnitudes contribute zero bits).  ``rows=None``
    picks the backend-appropriate tile geometry (see module docstring)."""
    mag = jnp.asarray(mag, jnp.int32)
    interp = _interpret()
    if rows is None:
        padded, n, rows = _pack_tiles(mag, interp)
    else:
        padded, n = _pad_to(mag, tile_elems(rows))
    out = bitplane_pack(padded, nbits=nbits, rows=rows, interpret=interp)
    return out[:, : (n + 31) // 32]


def _pack_tiles(x: jnp.ndarray, interp: bool):
    """Pad (N,) for the pack kernel and pick its tile rows: the canonical
    8-row tiles on TPU, one whole-array tile in interpret mode.  Returns
    ``(padded, N, rows)``."""
    if interp:
        padded, n = _pad_to(x, tile_elems(1))
        return padded, n, padded.shape[0] // tile_elems(1)
    padded, n = _pad_to(x, tile_elems(8))
    return padded, n, 8


@functools.partial(jax.jit, static_argnames=("nbits", "rows", "interpret"))
def _encode_planes_fused(c: jnp.ndarray, scale: jnp.ndarray, nbits: int,
                         rows: int, interpret: bool) -> jnp.ndarray:
    """Quantize f64 coefficients to nbits fixed point and pack every plane,
    all in ONE device dispatch (hi/lo uint32 split for nbits > 32)."""
    mag = jnp.floor(jnp.abs(c) * scale)
    mag = jnp.minimum(mag, np.float64(2.0 ** nbits - 1)).astype(jnp.uint64)
    lo = (mag & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    if nbits <= 32:
        return pack_planes_traced(lo, nbits, rows, interpret)
    hi = (mag >> jnp.uint64(32)).astype(jnp.uint32)
    hi_planes = pack_planes_traced(hi, nbits - 32, rows, interpret)
    lo_planes = pack_planes_traced(lo, 32, rows, interpret)
    return jnp.concatenate([hi_planes, lo_planes], axis=0)


def encode_magnitude_planes(c: np.ndarray, scale: float,
                            nbits: int) -> np.ndarray:
    """(N,) float64 coefficients -> (nbits, ceil32(N)) uint32 packed planes
    of mag = min(floor(|c|*scale), 2^nbits - 1), MSB plane first.  The whole
    refactor hot loop — quantization, hi/lo split and per-plane packing —
    runs as a single fused jit dispatch; only zlib stays on the host."""
    c = jnp.asarray(c, jnp.float64)
    interp = _interpret()
    padded, n, rows = _pack_tiles(c, interp)
    out = _encode_planes_fused(padded, jnp.float64(scale), nbits=nbits,
                               rows=rows, interpret=interp)
    return np.asarray(out)[:, : (n + 31) // 32]


def unpack_bitplanes(words, shifts, count: int) -> np.ndarray:
    """(P, ceil32(count)) uint32 packed planes + per-plane left shifts (< 64)
    -> (count,) uint64: OR over planes of (unpacked bits << shift).

    One vectorized call replaces the per-plane unpackbits loop of the legacy
    decoder.  On TPU this drives the ``bitplane_unpack`` Pallas kernel
    (shifts >= 32 via a hi/lo uint32 split); off-TPU a byte-plane NumPy
    accumulation — integer ops only, so both paths agree exactly.  The
    decode-path knob forces one implementation for conformance testing
    ("kernel" runs the Pallas kernel in interpret mode off-TPU).
    """
    words = np.ascontiguousarray(words, dtype=np.uint32)
    shifts = np.asarray(shifts, dtype=np.int64)
    if count == 0 or words.shape[0] == 0:
        return np.zeros(count, dtype=np.uint64)
    if _decode_path == "kernel" or not _interpret():
        return _unpack_kernel_u64(words, shifts, count)
    # Byte-plane accumulation (little-endian hosts): OR each plane into byte
    # column shift//8 of the uint64 output at sub-shift shift%8 — cheap uint8
    # passes, integer-exact by construction.  Bits are inflated per byte
    # column (<= 8 planes at a time), bounding the transient to ~8 planes'
    # bits even for archival-scale fields.
    nwords = words.shape[1]
    out = np.zeros(nwords * 32, dtype=np.uint64)
    out_bytes = out.view(np.uint8).reshape(-1, 8)
    q = shifts >> 3
    r = (shifts & 7).astype(np.uint8)
    for col in np.unique(q):
        sel = q == col
        bits = np.unpackbits(words[sel].view(np.uint8), axis=1,
                             bitorder="little")
        out_bytes[:, col] = np.bitwise_or.reduce(bits << r[sel, None], axis=0)
    return out[:count]


def _unpack_kernel_u64(words: np.ndarray, shifts: np.ndarray,
                       count: int) -> np.ndarray:
    """TPU path: split planes into hi (shift >= 32) / lo words, one kernel
    call each, recombine into uint64 magnitudes.  Each call's plane count is
    padded to a power of two with zero planes (exact no-ops) so the kernel
    compiles for a bounded set of shapes across fetch windows."""
    out = np.zeros(count, dtype=np.uint64)
    hi = shifts >= 32
    for sel, base in ((hi, 32), (~hi, 0)):
        if not np.any(sel):
            continue
        w = words[sel]
        sh = shifts[sel] - base
        p_pad = _plane_pad(w.shape[0])
        if p_pad != w.shape[0]:
            w = np.pad(w, ((0, p_pad - w.shape[0]), (0, 0)))
            sh = np.pad(sh, (0, p_pad - sh.shape[0]))
        grp = bitplane_unpack(jnp.asarray(w), jnp.asarray(sh, jnp.int32))
        out |= np.asarray(grp, dtype=np.uint64)[:count] << np.uint64(base)
    return out


def _decode_fused_body(words, shifts, state, sign_bytes, scale):
    """Traced fused decode: unpack + OR-accumulate + sign + scale.

    words (P, W) uint32, shifts (P,) uint64, state (W*32,) uint64 magnitude
    carry-in, sign_bytes (W*4,) uint8 (packbits big-endian), scale f64 — all
    full-word-length so the jit cache keys only on (P, W).  Every op is
    integer-exact or an exact f64 op (scale is a power of two; sign flip is
    negation), which is what makes this path bit-identical to the host
    decoder.
    """
    nplanes, nwords = words.shape
    bit_idx = jnp.arange(BITS, dtype=jnp.uint32)

    def plane(j, mag):
        bits = (words[j][:, None] >> bit_idx) & jnp.uint32(1)
        return mag | (bits.reshape(nwords * BITS).astype(jnp.uint64)
                      << shifts[j])

    # a loop, not a static unroll: the working set stays one plane's bits
    # whatever the plane-slot count (an unrolled 64-slot decode over an
    # archival-size group does not fit a 16 GB chip)
    mag = jax.lax.fori_loop(0, nplanes, plane, state)
    sbits = (sign_bytes[:, None]
             >> jnp.arange(7, -1, -1, dtype=jnp.uint8)) & jnp.uint8(1)
    signs = sbits.reshape(nwords * 32).astype(bool)
    vals = mag.astype(jnp.float64) * scale
    vals = jnp.where(signs, -vals, vals)
    return mag, vals


@jax.jit
def _decode_fused(words, shifts, state, sign_bytes, scale):
    return _decode_fused_body(words, shifts, state, sign_bytes, scale)


@jax.jit
def _decode_fused_batch(words, shifts, state, sign_bytes, scale):
    """vmapped fused decode over a (B, P, W) stack of same-shape groups —
    one device dispatch per serve-plane tick bucket instead of one per
    reader (see repro.serve.batch)."""
    return jax.vmap(_decode_fused_body)(words, shifts, state, sign_bytes,
                                        scale)


def prepare_fused_decode(words: np.ndarray, shifts, state, sign_bytes,
                         count: int, plane_slots: int = 0):
    """Normalize decode inputs to the fused dispatch's full-word-length,
    plane-padded layout.  Returns ``(words, shifts, state, sign_bytes)``
    ready for ``_decode_fused`` (or for stacking into a batch): planes
    padded to a power of two with zero planes (exact no-ops), state and
    sign bytes padded to W*32 bits.  ``plane_slots`` forces at least that
    many plane slots — the decode batcher pads every item to one uniform
    plane count so same-width groups share a bucket (and a compiled batch
    graph) regardless of how many planes each actually fetched.  The pad
    happens host-side before the device transfer, so extra slots cost
    zero-word no-ops on device, not extra dispatches.
    """
    words = np.ascontiguousarray(words, dtype=np.uint32)
    if words.size == 0:
        # zero-plane flush (e.g. a follow-mode refresh that moved nothing):
        # normalize the degenerate (0,)/(0, 0) layouts to (0, W) so the
        # no-op plane padding below keeps the group's true word width —
        # otherwise the state/sign arrays (and any batch bucket keyed on W)
        # would be mis-shaped
        words = words.reshape(0, (int(count) + 31) // 32)
    nplanes, nwords = words.shape
    p_pad = _plane_pad(max(nplanes, 1, int(plane_slots)))
    if p_pad != nplanes:
        words = np.pad(words, ((0, p_pad - nplanes), (0, 0)))
    sh = np.zeros(p_pad, dtype=np.uint64)
    sh[:nplanes] = np.asarray(shifts, dtype=np.uint64)
    if state is None:
        st = jnp.zeros(nwords * 32, dtype=jnp.uint64)
    else:
        st = jnp.asarray(state, dtype=jnp.uint64)
        if st.shape[0] != nwords * 32:      # host-length carry-in
            st = jnp.pad(st, (0, nwords * 32 - st.shape[0]))
    sb = np.zeros(nwords * 4, dtype=np.uint8)
    raw = np.asarray(sign_bytes, dtype=np.uint8)
    sb[: raw.shape[0]] = raw
    return words, sh, st, sb


def decode_values_fused(words: np.ndarray, shifts, state, sign_bytes,
                        scale: float, count: int):
    """One fused jit dispatch from packed plane words to signed f64 values.

    ``words`` (P, ceil32(count)) uint32, ``shifts`` per-plane left shifts,
    ``state`` an optional uint64 magnitude carry-in ((count,) host array or
    a previous dispatch's full-length device array), ``sign_bytes`` the
    decoded (entropy-stage-inflated) packbits sign plane, ``scale`` =
    2^(E-B).  Returns device arrays ``(mag_full, values)`` where
    ``mag_full`` is the full-word-length magnitude state (feed it back as
    ``state``) and ``values`` is sliced to ``count`` — still on device, so
    it can feed scatter/recompose without a host round-trip.
    """
    w, sh, st, sb = prepare_fused_decode(words, shifts, state, sign_bytes,
                                         count)
    mag, vals = _decode_fused(w, sh, st, sb, jnp.float64(scale))
    return mag, vals[:count]


def level_surplus(x_even: jnp.ndarray, x_odd: jnp.ndarray,
                  rows: int = 8) -> jnp.ndarray:
    """Batched 1D surplus with automatic row padding."""
    b = x_odd.shape[0]
    rem = (-b) % rows
    if rem:
        x_even = jnp.pad(x_even, ((0, rem), (0, 0)))
        x_odd = jnp.pad(x_odd, ((0, rem), (0, 0)))
    out = hier_level_surplus(x_even, x_odd, rows=rows,
                             interpret=_interpret())
    return out[:b]


def vtotal_with_bound(vx: jnp.ndarray, vy: jnp.ndarray, vz: jnp.ndarray,
                      eps: jnp.ndarray, rows: int = 8):
    """Fused Vtotal (value, Thm-2 bound) for flat arrays of any length."""
    n = vx.shape[0]
    vx, _ = _pad_to(vx, rows * LANES)
    vy, _ = _pad_to(vy, rows * LANES)
    vz, _ = _pad_to(vz, rows * LANES)
    val, bound = qoi_vtotal_fused(vx, vy, vz, jnp.asarray(eps), rows=rows,
                                  interpret=_interpret())
    return val[:n], bound[:n]
