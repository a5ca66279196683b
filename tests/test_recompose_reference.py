"""The HB recompose programs against a plain-NumPy strided recompose, bit
for bit.

The reference below imports nothing of the program: it walks the levels
coarse to fine and writes each level back into a strided view of the field
in place, ``c[::s] = where(mask, view + pred, view)``, with the prediction
built by index assignment.  The programs build the same values without a
scatter (strided slices, interleaves, a carried coarse grid), so the two
must agree in every bit: signed zeros included, and on data whose sums
round.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.transform.hierarchical import (recompose_hb, recompose_hb_from,
                                          scatter_recompose_from,
                                          scatter_recompose_from_batch,
                                          scatter_recompose_ip_from)

# (padded grid, levels): 1-D, 2-D and 3-D grids with degenerate extents
# (2 allows no level, 3 one), fewer levels than the grid allows, and
# isabel.ladder's padded 25x125x125 grid at its 5 levels.
GRIDS = [
    ((2,), 0), ((3,), 1), ((17,), 4), ((17,), 2),
    ((3, 3), 1), ((2, 9), 0), ((9, 3), 1), ((17, 33), 2), ((33, 17), 4),
    ((3, 5, 9), 1), ((2, 9, 9), 0), ((9, 3, 5), 1), ((9, 17, 9), 3),
    ((33, 129, 129), 5),
]
PROGRAMS = ["recompose_hb", "recompose_hb_from", "scatter_recompose_from",
            "scatter_recompose_from_batch", "scatter_recompose_ip_from"]


# ------------------------------------------------------------ reference --


def _axis_slice(ndim, ax, sl):
    return tuple(sl if i == ax else slice(None) for i in range(ndim))


def ref_interp_up(coarse):
    """Multilinear refinement m+1 -> 2m+1 along every axis in turn."""
    out = np.asarray(coarse, dtype=np.float64)
    for ax in range(out.ndim):
        n = out.shape[ax]
        shape = list(out.shape)
        shape[ax] = 2 * n - 1
        fine = np.empty(shape)
        lo = out[_axis_slice(out.ndim, ax, slice(0, n - 1))]
        hi = out[_axis_slice(out.ndim, ax, slice(1, n))]
        fine[_axis_slice(out.ndim, ax, slice(0, None, 2))] = out
        fine[_axis_slice(out.ndim, ax, slice(1, None, 2))] = 0.5 * (lo + hi)
        out = fine
    return out


def ref_new_nodes(shape):
    """Nodes with at least one odd coordinate."""
    odd = np.zeros(shape, dtype=bool)
    for ax, n in enumerate(shape):
        odd |= (np.arange(n) % 2 == 1).reshape(
            [n if i == ax else 1 for i in range(len(shape))])
    return odd


def ref_recompose_from(coeffs, levels, start):
    """Steps min(start, levels-1) .. 0, each written back into the strided
    view of the field in place."""
    c = np.array(coeffs, dtype=np.float64)
    for l in range(min(start, levels - 1), -1, -1):
        view = c[(slice(None, None, 1 << l),) * c.ndim]   # a view into c
        pred = ref_interp_up(view[(slice(None, None, 2),) * c.ndim])
        new = ref_new_nodes(view.shape)
        view[new] = view[new] + pred[new]
    return c


def ref_groups(shape, levels):
    """Flat node indices of each coefficient group, finest first: a node's
    group is the least 2-adic valuation of its coordinates, at most
    ``levels`` (coordinate 0 counts as ``levels``)."""
    group = np.full(shape, levels)
    for ax, n in enumerate(shape):
        v = np.full(n, levels)
        for i in range(1, n):
            v[i] = min((i & -i).bit_length() - 1, levels)
        group = np.minimum(group, v.reshape(
            [n if k == ax else 1 for k in range(len(shape))]))
    flat = group.ravel()
    return [np.flatnonzero(flat == l) for l in range(levels + 1)]


def ref_trunc(v, q):
    if q == 0.0:
        return v
    return np.sign(v) * np.floor(np.abs(v) / q) * q


# ------------------------------------------------------------------ data --


def _coefficients(shape, seed):
    """Magnitudes over twelve decades, so sums round, with a fifth of the
    nodes -0.0 and a tenth +0.0."""
    rng = np.random.default_rng(seed)
    c = rng.standard_normal(shape) * 10.0 ** rng.integers(-6, 6, shape)
    c[rng.random(shape) < 0.2] = -0.0
    c[rng.random(shape) < 0.1] = 0.0
    return c


def _bits(a):
    return np.asarray(a, dtype=np.float64).view(np.uint64)


def _assert_bits(got, want, what):
    got, want = _bits(got), _bits(want)
    assert got.shape == want.shape, what
    bad = np.flatnonzero(got != want)
    assert bad.size == 0, (what, bad[:5])


def _group_field(shape, idx, vals):
    flat = np.zeros(int(np.prod(shape)))
    flat[idx] = vals
    return flat.reshape(shape)


# ----------------------------------------------------------------- tests --


def test_reference_rounds_and_keeps_signed_zeros():
    """The data reaches what the comparison is for: the reference's sums
    round, and its output holds both -0.0 and +0.0."""
    c = _coefficients((9, 17, 9), 0)
    out = ref_recompose_from(c, 3, 2)
    lo, hi = c[:-1:2], c[2::2]
    assert np.any((lo + hi) - lo != hi)
    zeros = out == 0.0
    assert np.any(zeros & np.signbit(out)) and np.any(zeros & ~np.signbit(out))


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("shape,levels", GRIDS,
                         ids=["x".join(map(str, g)) + f"-L{l}"
                              for g, l in GRIDS])
def test_recompose_matches_numpy_bitwise(shape, levels, program):
    coeffs = _coefficients(shape, seed=int(np.prod(shape)) + levels)
    if program == "recompose_hb":
        _assert_bits(recompose_hb(jnp.asarray(coeffs), levels),
                     ref_recompose_from(coeffs, levels, levels - 1), program)
        return
    if program == "recompose_hb_from":
        for start in range(levels + 2):
            _assert_bits(recompose_hb_from(jnp.asarray(coeffs), levels, start),
                         ref_recompose_from(coeffs, levels, start), start)
        return
    flat = coeffs.ravel()
    for l, idx in enumerate(ref_groups(shape, levels)):
        vals = flat[idx]
        start = min(l, levels - 1)
        want = ref_recompose_from(_group_field(shape, idx, vals), levels, start)
        if program == "scatter_recompose_from":
            got = scatter_recompose_from(jnp.asarray(vals), shape, levels,
                                         start)
            _assert_bits(got, want, l)
        elif program == "scatter_recompose_from_batch":
            want_neg = ref_recompose_from(_group_field(shape, idx, -vals),
                                          levels, start)
            got = scatter_recompose_from_batch(
                jnp.stack([jnp.asarray(vals), jnp.asarray(-vals)]), shape,
                levels, start)
            _assert_bits(got[0], want, (l, 0))
            _assert_bits(got[1], want_neg, (l, 1))
        else:
            for q in (0.0, 2.0 ** -3, 2.0 ** 4):
                t = ref_trunc(vals, q)
                want = ref_recompose_from(_group_field(shape, idx, t), levels,
                                          start)
                want.ravel()[idx] += vals - t
                got = scatter_recompose_ip_from(jnp.asarray(idx),
                                                jnp.asarray(vals), shape,
                                                levels, start, q)
                _assert_bits(got, want, (l, q))
