"""Pallas TPU kernel: fused Vtotal value + error-bound evaluation.

The retrieval hot loop evaluates (value, bound) for every QoI each round
(Alg 2 lines 13-24). For Vtotal = sqrt(Vx²+Vy²+Vz²) the unfused jnp graph
materialises 6+ intermediates; this kernel fuses the whole
Thm 1 -> Thm 4 -> Thm 2 chain into one VMEM pass:

    s       = vx² + vy² + vz²
    eps_s   = Σ_i (2|v_i| ε_i + ε_i²)        (intpow + sum bounds)
    val     = sqrt(max(s, 0))
    bound   = eps_s / (sqrt(max(s - eps_s, 0)) + sqrt(s))   (paper Thm 2)

Per-variable ε are scalars (prefetched to SMEM-like (1,1) blocks); masked
points are handled by the caller zeroing ε at exact points is not needed
here because ε is uniform per variable — the wrapper applies the mask after.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitplane_pack import interpret_default

LANES = 128
DEFAULT_ROWS = 8


def _kernel(vx_ref, vy_ref, vz_ref, eps_ref, val_ref, bound_ref):
    vx, vy, vz = vx_ref[...], vy_ref[...], vz_ref[...]
    ex, ey, ez = eps_ref[0, 0], eps_ref[0, 1], eps_ref[0, 2]
    s = vx * vx + vy * vy + vz * vz
    eps_s = (2.0 * jnp.abs(vx) * ex + ex * ex
             + 2.0 * jnp.abs(vy) * ey + ey * ey
             + 2.0 * jnp.abs(vz) * ez + ez * ez)
    s = jnp.maximum(s, 0.0)
    val = jnp.sqrt(s)
    denom = jnp.sqrt(jnp.maximum(s - eps_s, 0.0)) + val
    safe = jnp.where(denom > 0, denom, 1.0)
    bound = jnp.where(denom > 0, eps_s / safe, jnp.inf)
    val_ref[...] = val
    bound_ref[...] = bound


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def qoi_vtotal_fused(vx: jnp.ndarray, vy: jnp.ndarray, vz: jnp.ndarray,
                     eps: jnp.ndarray, rows: int = DEFAULT_ROWS,
                     interpret: bool | None = None):
    """vx/vy/vz: (N,) with N % (rows*128) == 0; eps: (3,) per-variable bounds.
    Returns (val, bound), each (N,).  ``interpret=None`` auto-detects the
    backend (compile on TPU)."""
    if interpret is None:
        interpret = interpret_default()
    n = vx.shape[0]
    if n % (rows * LANES):
        raise ValueError(f"N={n} must be a multiple of rows*128={rows * LANES}")
    tiles = n // (rows * LANES)
    shape2d = (tiles * rows, LANES)
    eps2d = eps.reshape(1, 3).astype(vx.dtype)
    val, bound = pl.pallas_call(
        _kernel,
        grid=(tiles,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((1, 3), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                   pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct(shape2d, vx.dtype),
                   jax.ShapeDtypeStruct(shape2d, vx.dtype)],
        interpret=interpret,
    )(vx.reshape(shape2d), vy.reshape(shape2d), vz.reshape(shape2d), eps2d)
    return val.reshape(n), bound.reshape(n)
