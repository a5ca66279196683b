"""Pallas TPU kernel: bitplane unpack — the inverse of ``bitplane_pack``.

Accumulates P packed planes into 32-bit magnitude words in a single pass:

    out[i] = OR_j  bit_i(plane_j) << shift_j

Per-plane shifts are a *dynamic* input (int32 scalars in SMEM) rather than a
static tuple, so one compiled kernel serves every fetch window ``[start, k)``
of the progressive reader — only the plane count and tile geometry are
compile-time constants.  Shifts must be < 32; magnitudes wider than 32 bits
(the archival default is 48) are handled by the caller as a hi/lo uint32
split (see ``ops.unpack_bitplanes``).

Tile layout mirrors the pack kernel: packed words (P, ROWS, 128) per tile,
lane-dense; output (32, ROWS, 128) *bit-position-major* — slab j holds
coefficient 32·w + j of each word w — which the wrapper transposes back to
coefficient order.  Unpacking is a dense shift-and-mask over the 32 bit
positions of each word: no data-dependent control flow and no lane-crossing
reshape.  Words are int32 inside the kernel (bit patterns only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitplane_pack import (
    BITS,
    DEFAULT_ROWS,
    LANES,
    _zero,
    interpret_default,
)


def _kernel(nplanes, words_ref, shift_ref, out_ref):
    out_ref[...] = jnp.zeros(out_ref.shape, jnp.int32)

    def plane(p, carry):
        w = words_ref[p]                                 # (ROWS, 128) int32
        s = shift_ref[p]
        for j in range(BITS):                            # static unroll
            out_ref[j] = out_ref[j] | (((w >> j) & 1) << s)
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(nplanes), plane, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def _unpack(words: jnp.ndarray, shifts: jnp.ndarray, rows: int,
            interpret: bool) -> jnp.ndarray:
    p, w = words.shape
    if w % (rows * LANES):
        raise ValueError(f"W={w} must be a multiple of rows*128="
                         f"{rows * LANES}")
    tiles = w // (rows * LANES)
    words3 = jax.lax.bitcast_convert_type(words, jnp.int32).reshape(
        p, tiles * rows, LANES)
    out = pl.pallas_call(
        functools.partial(_kernel, p),
        grid=(tiles,),
        in_specs=[pl.BlockSpec((p, rows, LANES),
                               lambda i: (_zero(i), i, _zero(i))),
                  pl.BlockSpec((p,), lambda i: (_zero(i),),
                               memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((BITS, rows, LANES),
                               lambda i: (_zero(i), i, _zero(i))),
        out_shape=jax.ShapeDtypeStruct((BITS, tiles * rows, LANES),
                                       jnp.int32),
        interpret=interpret,
    )(words3, shifts.astype(jnp.int32))
    out = jax.lax.bitcast_convert_type(out, jnp.uint32)
    return out.reshape(BITS, w).T.reshape(w * BITS)


def bitplane_unpack(words: jnp.ndarray, shifts: jnp.ndarray,
                    rows: int = DEFAULT_ROWS,
                    interpret: bool | None = None) -> jnp.ndarray:
    """words: (P, W) uint32 packed planes; shifts: (P,) < 32.  Returns
    (W*32,) uint32 = OR_j(bits of plane j << shifts[j]).  W is zero-padded
    to whole tiles internally (a single ``ceil(W/128)``-row tile when that
    is under ``rows``).  ``interpret=None`` auto-detects the backend
    (compile on TPU)."""
    if interpret is None:
        interpret = interpret_default()
    words = jnp.asarray(words, jnp.uint32)
    w = words.shape[1]
    rows = min(rows, -(-w // LANES))
    pad = (-w) % (rows * LANES)
    if pad:
        words = jnp.pad(words, ((0, 0), (0, pad)))
    out = _unpack(words, jnp.asarray(shifts), rows=rows,
                  interpret=bool(interpret))
    return out[: w * BITS]
