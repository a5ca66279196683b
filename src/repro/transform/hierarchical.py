"""PMGARD-HB multilevel decomposition (paper §V-B).

Hierarchical-basis (HB) surplus transform: at each level, "new" nodes (those
not on the next-coarser grid) store their *interpolation surplus*
``x - I(coarse x)``; coarse-node values are left untouched. Because coarse
values never change, (a) every level's surplus depends only on the original
data — the transform is embarrassingly parallel across levels (the TPU-native
win over MGARD's sequential L² projection), and (b) the L-inf reconstruction
error composes exactly as the *sum of per-level coefficient bounds*:

    |x - x̂|_inf  <=  Σ_l  e_l                                   (HB bound)

since a node's error is its own surplus error plus a convex (multilinear)
interpolation of strictly-coarser node errors. This is the tight bound the
paper exploits to fix PMGARD's over-retrieval (Fig 3).

Grids are padded per-dimension to 2^k + 1 (edge-replicate); the padded
surpluses are ~0 and compress away.

The recompose carries the recomposed coarse grid from step to step and reads
each level's coefficients as a strided slice: ``cur = where(mask, view +
interp_up(cur), interp_up(cur))``.  Every interleave is a stack and a reshape
and every placement static slices, so the device programs hold no scatter (a
TPU runs one close to serially).  The result is bitwise the in-place
write-back ``c[::s] = where(mask, view + pred, view)``: each value is the
same operation on the same operands (see ``_recompose_steps``).
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import repro._x64  # noqa: F401  (f64 for the compression stack)

Array = jnp.ndarray


# ---------------------------------------------------------------------------
# Grid geometry
# ---------------------------------------------------------------------------


def _pad_dim(n: int) -> int:
    """Smallest 2^k + 1 >= n (k >= 0)."""
    if n <= 2:
        return 2 if n == 1 else 3  # degenerate dims get a tiny valid grid
    k = int(np.ceil(np.log2(n - 1)))
    return (1 << k) + 1


def pad_to_grid(x: np.ndarray) -> Tuple[np.ndarray, Tuple[int, ...]]:
    """Edge-replicate pad every dim to 2^k + 1. Returns (padded, orig_shape)."""
    orig = x.shape
    target = tuple(_pad_dim(n) for n in orig)
    pads = tuple((0, t - n) for t, n in zip(target, orig))
    return np.pad(x, pads, mode="edge"), orig


def unpad(x: np.ndarray, orig_shape: Tuple[int, ...]) -> np.ndarray:
    return x[tuple(slice(0, n) for n in orig_shape)]


def grid_levels(shape: Tuple[int, ...], max_levels: int = 32) -> int:
    """Number of detail levels supported by a padded (2^k+1, ...) grid."""
    ks = []
    for n in shape:
        k = int(np.round(np.log2(n - 1))) if n > 2 else 0
        ks.append(k)
    return min(min(ks), max_levels)


def level_map(shape: Tuple[int, ...], levels: int) -> np.ndarray:
    """Per-node detail level: l in [0, levels) for detail nodes (finest = 0),
    ``levels`` for base-grid nodes. Level of node i = min over dims of the
    2-adic valuation of its coordinates, clipped to the base grid."""
    val = np.full(shape, levels, dtype=np.int32)
    for ax, n in enumerate(shape):
        idx = np.arange(n)
        v2 = np.full(n, levels, dtype=np.int32)
        nz = idx != 0
        v2[nz] = np.minimum(_v2(idx[nz]), levels)
        sl = [None] * len(shape)
        sl[ax] = slice(None)
        val = np.minimum(val, v2[tuple(sl)])
    return val


def _v2(idx: np.ndarray) -> np.ndarray:
    """2-adic valuation of positive ints, vectorised."""
    out = np.zeros_like(idx)
    x = idx.copy()
    while np.any(x % 2 == 0):
        even = x % 2 == 0
        out[even] += 1
        x[even] //= 2
    return out.astype(np.int32)


# ---------------------------------------------------------------------------
# Multilinear upsampling (coarse grid -> fine grid prediction)
# ---------------------------------------------------------------------------


def _interleave(even: Array, odd: Array, ax: int) -> Array:
    """``out[2i] = even[i]``, ``out[2i+1] = odd[i]`` along ``ax`` (``even``
    one longer than ``odd``), built from a stack and a reshape: every value
    is moved, none is combined, so signed zeros and NaNs pass unchanged
    (a strided ``.at[].set`` lowers to a TPU scatter)."""
    n = even.shape[ax]
    head = jax.lax.slice_in_dim(even, 0, n - 1, axis=ax)
    last = jax.lax.slice_in_dim(even, n - 1, n, axis=ax)
    pairs = jnp.stack([head, odd], axis=ax + 1)
    pairs = pairs.reshape(even.shape[:ax] + (2 * (n - 1),)
                          + even.shape[ax + 1:])
    return jax.lax.concatenate([pairs, last], ax)


def _up_axis(c: Array, ax: int) -> Array:
    """Linear-interpolate a (2m+1 -> from m+1) refinement along one axis."""
    n = c.shape[ax]
    lo = jax.lax.slice_in_dim(c, 0, n - 1, axis=ax)
    hi = jax.lax.slice_in_dim(c, 1, n, axis=ax)
    return _interleave(c, 0.5 * (lo + hi), ax)


def interp_up(coarse: Array) -> Array:
    """Multilinear prediction of the fine grid from the coarse grid."""
    out = coarse
    for ax in range(coarse.ndim):
        out = _up_axis(out, ax)
    return out


def _new_node_mask(shape: Tuple[int, ...]) -> np.ndarray:
    """Nodes of the fine view NOT on the 2-strided coarse grid."""
    m = np.zeros(shape, dtype=bool)
    for ax, n in enumerate(shape):
        odd = (np.arange(n) % 2).astype(bool)
        sl = [None] * len(shape)
        sl[ax] = slice(None)
        m |= odd[tuple(sl)]
    return m


def _view_slices(ndim: int, stride: int):
    return tuple(slice(None, None, stride) for _ in range(ndim))


# ---------------------------------------------------------------------------
# HB decompose / recompose (pure jnp; per-level shapes are static)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(1,))
def decompose_hb(x: Array, levels: int) -> Array:
    """In-place-layout HB transform: detail nodes hold surpluses, base nodes
    hold original values. Levels are independent (no cross-level coupling)."""
    for l in range(levels):
        s = 1 << l
        view = x[_view_slices(x.ndim, s)]
        pred = interp_up(view[_view_slices(x.ndim, 2)])
        mask = jnp.asarray(_new_node_mask(view.shape))
        x = x.at[_view_slices(x.ndim, s)].set(jnp.where(mask, view - pred, view))
    return x


def _strided(c: Array, s: int) -> Array:
    """The stride-``s`` view of ``c`` as one strided ``lax.slice`` (jnp's
    ``[::s]`` lowers to gathers on TPU)."""
    return jax.lax.slice(c, (0,) * c.ndim, c.shape, (s,) * c.ndim)


def _recompose_steps(cur: Array, views: List[Array]) -> Array:
    """Recompose steps coarse -> fine in carry form.  ``cur`` is the
    recomposed grid one level coarser than ``views[0]``; ``views`` are the
    coefficient field's strided views, coarsest first.  Each step predicts
    the finer grid and adds the view's surpluses at its new nodes:

        cur = where(mask, view + interp_up(cur), interp_up(cur))

    This is bitwise the in-place write-back ``c[::s] = where(mask, view +
    pred, view)``: at the coarse nodes the write-back keeps the already
    recomposed values, which ``interp_up`` copies exactly, and at the new
    nodes both add the same two operands.  ``view + pred`` stays an add even
    where the coefficients are zero, because ``0.0 + -0.0`` is ``+0.0``."""
    for view in views:
        pred = interp_up(cur)
        mask = jnp.asarray(_new_node_mask(view.shape))
        cur = jnp.where(mask, view + pred, pred)
    return cur


def _recompose_field(c: Array, start: int) -> Array:
    """Steps start..0 of a whole coefficient field (none for start < 0).
    Each stride-2^l view is one stride-2 slice of the next finer view: a
    single slice of stride 128 over a 129-wide axis (a 129^3 grid's base
    step) runs out of the v5e's scoped vmem when it compiles."""
    if start < 0:
        return c
    views = [c]
    for _ in range(start + 1):
        views.append(_strided(views[-1], 2))
    return _recompose_steps(views[-1], views[-2::-1])


@functools.partial(jax.jit, static_argnums=(1,))
def recompose_hb(c: Array, levels: int) -> Array:
    """Inverse of decompose_hb; must run coarse -> fine."""
    return _recompose_field(c, levels - 1)


@functools.partial(jax.jit, static_argnums=(1, 2))
def recompose_hb_from(c: Array, levels: int, start: int) -> Array:
    """Partial recompose: only steps start..0.  For a coefficient field
    supported on levels <= start (zero on all strictly-coarser grids) this
    is *bitwise* identical to the full recompose — the skipped coarse steps
    see an all-zero view and are exact no-ops — while costing only the fine
    half of the step ladder.  This is what makes per-level incremental
    reconstruction (core/refactor.py) both cheap and reproducible."""
    return _recompose_field(c, min(start, levels - 1))


def _view_shape(shape: Tuple[int, ...], s: int) -> Tuple[int, ...]:
    return tuple(-(-n // s) for n in shape)


def _place_new_nodes(vals: Array, shape: Tuple[int, ...]) -> Array:
    """Rows of ``vals`` (B, count) hold, in C order, the new nodes
    (``_new_node_mask``) of a grid of odd extents ``shape``; returns
    (B, *shape) with them in place and zeros at the coarse nodes.  Along
    the first axis the new nodes alternate between an even plane's own new
    nodes and a whole odd plane, so static slices and reshapes split them,
    the even planes recurse on the remaining axes, and one interleave per
    axis puts them back together."""
    b = vals.shape[0]
    m, rest = shape[0] // 2, shape[1:]
    full = int(np.prod(rest, dtype=np.int64))
    sub = full - int(np.prod([(n + 1) // 2 for n in rest], dtype=np.int64))
    head = vals[:, :m * (sub + full)].reshape(b, m, sub + full)
    odd = head[:, :, sub:].reshape((b, m) + rest)
    if rest:
        last = vals[:, None, m * (sub + full):]
        even = jnp.concatenate([head[:, :, :sub], last], axis=1)
        even = _place_new_nodes(even.reshape(b * (m + 1), sub), rest)
        even = even.reshape((b, m + 1) + rest)
    else:
        even = jnp.zeros((b, m + 1), vals.dtype)
    return _interleave(even, odd, 1)


def _zero_view(dtype, shape: Tuple[int, ...]) -> Array:
    """A zero coefficient view the compiler cannot see to be zero: it folds
    ``x + 0.0`` into ``x``, which would keep a ``-0.0`` that the sum with a
    stored zero field turns into ``+0.0``."""
    zero = jax.lax.optimization_barrier(jnp.zeros((), dtype))
    return jnp.broadcast_to(zero, shape)


def _recompose_group(vals: Array, shape: Tuple[int, ...], levels: int,
                     start: int) -> Array:
    """Partial recompose of one coefficient group, placed without a scatter.
    A detail group ``l`` (``start == l``) is, in C order, exactly the new
    nodes of the stride-2^l view; the base group (``start == levels - 1``)
    is the whole stride-2^levels grid that step ``start`` refines.  The
    field is zero everywhere else, so every other view is zero — bitwise
    what ``recompose_hb_from`` computes on the scattered field."""
    start = min(start, levels - 1)
    if start < 0:                      # no levels: the group is the field
        return vals.reshape(shape)
    coarse = _view_shape(shape, 2 << start)
    fine = [_view_shape(shape, 1 << l) for l in range(start, -1, -1)]
    finer = [_zero_view(vals.dtype, f) for f in fine[1:]]
    n_coarse = int(np.prod(coarse))
    if vals.shape[0] == n_coarse:      # the base group
        return _recompose_steps(vals.reshape(coarse),
                                [_zero_view(vals.dtype, fine[0])] + finer)
    if vals.shape[0] != int(np.prod(fine[0])) - n_coarse:
        raise ValueError(f"{vals.shape[0]} values are no coefficient group "
                         f"of {shape} from step {start}")
    placed = _place_new_nodes(vals[None], fine[0])[0]
    return _recompose_steps(_zero_view(vals.dtype, coarse), [placed] + finer)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def scatter_recompose_from(vals: Array, shape: Tuple[int, ...], levels: int,
                           start: int) -> Array:
    """One level's coefficient values, partially recomposed onto the padded
    grid — the device-resident form of the reader's per-level contribution
    (core/refactor.py::_compute_contrib).  ``vals`` are the decoded
    coefficients (straight off the fused decode, no host round-trip) in the
    group's C order, placed by static slices and interleaves, so the result
    is bit-identical to the host scatter + ``recompose_hb_from`` pair."""
    return _recompose_group(vals, shape, levels, start)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def scatter_recompose_from_batch(vals: Array, shape: Tuple[int, ...],
                                 levels: int, start: int) -> Array:
    """vmapped ``scatter_recompose_from`` over a leading batch axis: one
    dispatch recomposes the same-shaped contribution of B readers (the serve
    plane's batched tick).  vmap only adds the batch dimension — each slice
    runs the identical elementwise graph, so results match the per-reader
    dispatch bit-for-bit."""
    return jax.vmap(
        lambda v: scatter_recompose_from(v, shape, levels, start))(vals)


def hb_error_bound(level_bounds: List[float]) -> float:
    """HB L-inf bound: Σ_l e_l (+ base bound, passed as last entry)."""
    return float(np.sum(level_bounds))


# ---------------------------------------------------------------------------
# Interpolation-predicted (`ip`) representation
# ---------------------------------------------------------------------------
#
# The `ip` method closes the prediction loop that HB leaves open: instead of
# coding each level's interpolation surplus against the ORIGINAL data, it
# codes the residual against the decoder's own truncated reconstruction of
# all coarser groups.  Each group g records `pred_planes` (kp_g) — the plane
# depth the encoder folded into its prediction.  The decoder's per-group
# contribution is then
#
#     C_g = recompose_hb_from(scatter(T_g), levels, start=g)      (truncated
#     C_g.ravel()[idx_g] += v̂_g - T_g                              + tail)
#
# with T_g = trunc(v̂_g, 2^{E_g - kp_g}).  Truncation to a power-of-two
# quantum is EXACT in f64 (magnitudes are < 2^53 integer multiples of the
# quantum), and for fetched depth k <= kp it is the identity, so the tail is
# zero and C_g degenerates to the plain HB contribution.  When every group
# is fetched at k_g >= kp_g the decoder's prediction replays the encoder's
# bit-for-bit and per-node errors no longer sum across levels:
#
#     |x - x̂|_inf  <=  max_g e_g            (matched regime — the ip win)
#
# Under-fetched groups (k < kp) perturb the prediction of strictly finer
# groups by at most δ_g = 2^{E-k} - 2^{E-kp}; multilinear interpolation is a
# convex combination, so δ propagates without amplification and the exact
# composition is `ip_error_bound` below — always <= the HB sum.


def trunc_to_quantum(v: np.ndarray, quantum: float) -> np.ndarray:
    """sign(v)·floor(|v|/q)·q — truncate toward zero to multiples of the
    power-of-two quantum ``q``.  Exact in f64: |v| is an integer multiple
    m·q with m < 2^53, the division recovers m exactly, and m·q is exact."""
    v = np.asarray(v, dtype=np.float64)
    if quantum == 0.0:
        return v
    return np.sign(v) * np.floor(np.abs(v) / quantum) * quantum


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def scatter_recompose_ip_from(idx: Array, vals: Array,
                              shape: Tuple[int, ...], levels: int,
                              start: int, quantum: Array) -> Array:
    """`ip` counterpart of ``scatter_recompose_from``: truncate the decoded
    values to the group's prediction quantum, place + partially recompose
    the truncated part (the closed-loop prediction seed for finer groups),
    then scatter-add the truncation tail back at the group's own nodes
    ``idx``.  ``quantum`` is a traced operand (2^{E-kp}, or 0.0 for no
    truncation) so one compiled graph serves every group of a given
    geometry."""
    q = jnp.asarray(quantum, dtype=vals.dtype)
    safe = jnp.where(q == 0.0, jnp.asarray(1.0, vals.dtype), q)
    t = jnp.where(q == 0.0, vals,
                  jnp.sign(vals) * jnp.floor(jnp.abs(vals) / safe) * safe)
    out = _recompose_group(t, shape, levels, start)
    return out.reshape(-1).at[idx].add(vals - t).reshape(shape)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def scatter_recompose_ip_from_batch(idx: Array, vals: Array,
                                    shape: Tuple[int, ...], levels: int,
                                    start: int, quantum: Array) -> Array:
    """vmapped ``scatter_recompose_ip_from`` over a leading batch axis —
    the serve plane's batched tick for `ip` readers.  ``quantum`` carries
    one entry per batch item."""
    return jax.vmap(
        lambda i, v, q: scatter_recompose_ip_from(i, v, shape, levels,
                                                  start, q)
    )(idx, vals, quantum)


def ip_error_bound(level_bounds: List[float],
                   mismatches: List[float]) -> float:
    """`ip` L-inf bound.  Lists are finest-first (index 0 = finest detail,
    last entry = base group), matching the reader's stream order.  Walking
    coarse -> fine with a running prediction-mismatch accumulator m:

        bound = max_g (e_g + m_g),   m_g = Σ_{g' coarser than g} δ_{g'}

    where e_g is the group's own plane bound and δ_g its truncation-depth
    mismatch (0 once fetched depth reaches the recorded ``pred_planes``).
    Always <= hb_error_bound(level_bounds) and monotone under deeper
    fetches."""
    out = 0.0
    m = 0.0
    for e, d in zip(reversed(level_bounds), reversed(mismatches)):
        out = max(out, float(e) + m)
        m += float(d)
    return float(out)
