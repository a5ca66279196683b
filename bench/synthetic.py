"""Seeded field generators of the benchmark's configurations.

A copy of the program's ``repro.data.synthetic.smooth_field`` and
``ge_like_fields``, kept here so that the data a cell measures on cannot
change with the program.  The same seed always gives the same fields.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np


def smooth_field(shape: Tuple[int, ...], seed: int, octaves: int = 5,
                 lo: float = -1.0, hi: float = 1.0,
                 roughness: float = 0.55) -> np.ndarray:
    """Sum of random low-frequency separable cosines, plus a little noise,
    scaled onto [lo, hi]: a multi-scale field whose spectrum decays, so
    multilevel coefficients shrink level by level as in simulation output."""
    rng = np.random.default_rng(seed)
    coords = [np.linspace(0.0, 1.0, n) for n in shape]
    out = np.zeros(shape, dtype=np.float64)
    amp = 1.0
    for o in range(octaves):
        freq = 2.0 ** o
        term = amp * np.ones(shape)
        for ax, c in enumerate(coords):
            phase = rng.uniform(0, 2 * np.pi)
            f = freq * rng.uniform(0.6, 1.4)
            wave = np.cos(2 * np.pi * f * c + phase)
            sl = [None] * len(shape)
            sl[ax] = slice(None)
            term = term * wave[tuple(sl)]
        out += term
        amp *= roughness
    out += 0.002 * rng.standard_normal(shape)
    omin, omax = out.min(), out.max()
    return lo + (hi - lo) * (out - omin) / (omax - omin)


def ranged_fields(shape: Sequence[int], seed: int,
                  ranges: Dict[str, Sequence[float]]) -> Dict[str, np.ndarray]:
    """One smooth field per name, each on its own [lo, hi]; field ``i`` (in
    the order given) is drawn from seed ``seed + i + 1``."""
    return {name: smooth_field(tuple(shape), seed + i + 1, lo=lo, hi=hi)
            for i, (name, (lo, hi)) in enumerate(ranges.items())}


def zero_wall(fields: Dict[str, np.ndarray], names: Sequence[str],
              seed: int, fraction: float) -> None:
    """Set one contiguous run of ``fraction`` of the (1-D) nodes to exactly
    zero in each named field: a no-slip wall, where the program's outlier
    mask keeps the values exact."""
    n = len(next(iter(fields.values())))
    n_zero = int(fraction * n)
    if not n_zero:
        return
    start = int(np.random.default_rng(seed + 1000).integers(0, n - n_zero))
    for name in names:
        fields[name][start:start + n_zero] = 0.0
