"""The S3D combustion case-study QoIs: products of species concentrations.

S3D (SDRBench, https://sdrbench.github.io/) stores molar concentrations of
the species of an H2/O2 flame as float64 fields.  arXiv:2411.05333 takes
four rate-of-progress products of them as QoIs: x1·x3, x0·x4, x1·x5 and
x3·x4.  Each is one ``Prod`` of two variables, so its bound is Thm 5's.
"""
from __future__ import annotations

from typing import Dict

from repro.core.qoi import Expr, Prod, Var

PRODUCTS = (("x1", "x3"), ("x0", "x4"), ("x1", "x5"), ("x3", "x4"))


def all_qois() -> Dict[str, Expr]:
    """The four products keyed by the names of their factors, ``x1x3``
    and so on, in the paper's order."""
    return {a + b: Prod(Var(a), Var(b)) for a, b in PRODUCTS}
