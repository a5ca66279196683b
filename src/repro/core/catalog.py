"""The QoI catalogs the program knows, and the part an archive can answer.

A server offers each catalog QoI whose variables its archive holds: the
six GE QoIs on a GE archive, VTOT alone on a velocity archive, the four
species products on an S3D archive.  QoI names are global across
catalogs (no two catalogs share one), so a request names a QoI without
naming its catalog.
"""
from __future__ import annotations

from typing import Dict, Iterable

from repro.core import ge, s3d
from repro.core.qoi import Expr

KNOWN: Dict[str, Expr] = {**ge.all_qois(), **s3d.all_qois()}


def offered(variables: Iterable[str]) -> Dict[str, Expr]:
    """The known QoIs computable from ``variables`` alone."""
    have = frozenset(variables)
    return {name: expr for name, expr in KNOWN.items()
            if expr.variables() <= have}
