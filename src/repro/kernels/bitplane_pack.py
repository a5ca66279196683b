"""Pallas TPU kernel: bitplane extraction + packing (the refactor hot loop).

TPU adaptation of the paper's scalar bit loop (DESIGN.md §3): magnitudes are
32-bit fixed point; plane b of a word is ``(mag >> (B-1-b)) & 1``, and bit j
of packed word w is coefficient ``32·w + j``.

The wrapper lays the magnitudes out *bit-position-major*: a (32, W) view
whose row j holds coefficient 32·w + j of every word w.  A tile is then
(32, ROWS, 128) int32 — 32 lane-dense slabs, one per bit position — and
packing one plane is 32 elementwise shift/and/shift/or steps over (ROWS, 128)
vregs, with no lane-crossing reshape and no reduction (Mosaic implements
neither for unsigned words).  Output tiles are (B, ROWS, 128): 128 packed
words per row, lane-dense.  Words are int32 inside the kernel (bit patterns
only; the caller bitcasts to uint32).  ROWS=8 keeps the working set at
32·8·128·4B (in) + B·8·128·4B (out) « 16 MiB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
BITS = 32                     # coefficients per packed word
DEFAULT_ROWS = 8


def _zero(i):
    """int32 zero for index maps: a bare ``0`` traces as int64 under the
    stack's x64 mode, and Mosaic refuses an index map of mixed widths."""
    return i * 0


def _kernel(nbits: int, mag_ref, out_ref):
    def plane(b, carry):
        sh = nbits - 1 - b
        word = (mag_ref[0] >> sh) & 1                    # (ROWS, 128) int32
        for j in range(1, BITS):                         # static unroll
            word = word | (((mag_ref[j] >> sh) & 1) << j)
        out_ref[b] = word
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(nbits), plane, jnp.int32(0))


def interpret_default() -> bool:
    """True off-TPU: run Pallas kernels through the interpreter.  Single
    source of the backend-dispatch policy for the whole kernels package."""
    return jax.default_backend() != "tpu"


def tile_elems(rows: int) -> int:
    """Coefficients per grid step: ``rows`` rows of 128 packed words."""
    return rows * LANES * BITS


def pack_planes_traced(mag: jnp.ndarray, nbits: int, rows: int,
                       interpret: bool) -> jnp.ndarray:
    """Traceable pack body (no jit wrapper): lets callers fuse the pallas
    call into a larger jitted graph (see ops.encode_magnitude_planes).
    ``mag`` may be any 32-bit integer dtype — only bit extraction happens;
    ``N % tile_elems(rows) == 0``.  Returns (nbits, N // 32) uint32."""
    n = mag.shape[0]
    if n % tile_elems(rows):
        raise ValueError(f"N={n} must be a multiple of rows*128*32="
                         f"{tile_elems(rows)}")
    nwords = n // BITS
    tiles = nwords // (rows * LANES)
    words = jax.lax.bitcast_convert_type(mag, jnp.int32)
    by_bit = words.reshape(nwords, BITS).T.reshape(BITS, tiles * rows, LANES)
    out = pl.pallas_call(
        functools.partial(_kernel, nbits),
        grid=(tiles,),
        in_specs=[pl.BlockSpec((BITS, rows, LANES),
                               lambda i: (_zero(i), i, _zero(i)))],
        out_specs=pl.BlockSpec((nbits, rows, LANES),
                               lambda i: (_zero(i), i, _zero(i))),
        out_shape=jax.ShapeDtypeStruct((nbits, tiles * rows, LANES),
                                       jnp.int32),
        interpret=interpret,
    )(by_bit)
    return jax.lax.bitcast_convert_type(out, jnp.uint32).reshape(nbits, nwords)


@functools.partial(jax.jit, static_argnames=("nbits", "rows", "interpret"))
def _pack(mag: jnp.ndarray, nbits: int, rows: int,
          interpret: bool) -> jnp.ndarray:
    n = mag.shape[0]
    padded = jnp.pad(mag, (0, (-n) % tile_elems(rows)))
    return pack_planes_traced(padded, nbits, rows, interpret)[:, : n // BITS]


def bitplane_pack(mag: jnp.ndarray, nbits: int = 30,
                  rows: int = DEFAULT_ROWS,
                  interpret: bool | None = None) -> jnp.ndarray:
    """mag: (N,) int32 magnitude words (the low 32 bits may be reinterpreted
    sign bits — only bit extraction is performed), N % 32 == 0; zero-padded
    to whole tiles internally.  Returns (nbits, N // 32) uint32 packed
    planes, MSB plane first.  ``interpret=None`` auto-detects the backend so
    direct callers compile on TPU instead of silently interpreting."""
    if mag.shape[0] % BITS:
        raise ValueError(f"N={mag.shape[0]} must be a multiple of {BITS}")
    if interpret is None:
        interpret = interpret_default()
    return _pack(mag, nbits=nbits, rows=rows, interpret=bool(interpret))
