"""Pallas TPU kernel: one hierarchical-surplus lifting level (1D lines).

Computes  d = x_odd - 0.5 * (x_even[:, :-1] + x_even[:, 1:])  for a batch of
lines — the per-level inner loop of decompose_hb applied along one axis.

TPU layout choice (DESIGN.md §3): levels are stored *deinterleaved*
(struct-of-arrays: even/coarse nodes and odd/new nodes in separate dense
buffers) so the kernel sees only contiguous, 128-lane-aligned loads — the
strided gathers of the CPU formulation do not map to TPU vector memory.

Tile: x_even (ROWS, M+1) and x_odd (ROWS, M) in VMEM, rows tiled by the
grid; M is padded to a multiple of 128 by the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitplane_pack import interpret_default

DEFAULT_ROWS = 8


def _kernel(even_ref, odd_ref, out_ref):
    even = even_ref[...]          # (ROWS, M+P) — last P cols are pad
    odd = odd_ref[...]            # (ROWS, M)
    m = odd.shape[1]
    pred = 0.5 * (even[:, :m] + even[:, 1:m + 1])
    out_ref[...] = odd - pred


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def hier_level_surplus(x_even: jnp.ndarray, x_odd: jnp.ndarray,
                       rows: int = DEFAULT_ROWS,
                       interpret: bool | None = None) -> jnp.ndarray:
    """x_even: (B, M+1) coarse nodes, x_odd: (B, M) new nodes, B % rows == 0.
    Returns (B, M) surpluses.  ``interpret=None`` auto-detects the backend
    (compile on TPU)."""
    if interpret is None:
        interpret = interpret_default()
    b, m = x_odd.shape
    if x_even.shape != (b, m + 1):
        raise ValueError(f"even {x_even.shape} vs odd {x_odd.shape}")
    if b % rows:
        raise ValueError(f"batch {b} must be a multiple of rows={rows}")
    tiles = b // rows
    return pl.pallas_call(
        _kernel,
        grid=(tiles,),
        in_specs=[pl.BlockSpec((rows, m + 1), lambda i: (i, 0)),
                  pl.BlockSpec((rows, m), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m), x_odd.dtype),
        interpret=interpret,
    )(x_even, x_odd)
