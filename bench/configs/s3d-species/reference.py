"""Plain float64 NumPy species products, the reference that S3D answers are
held to."""
from __future__ import annotations

from typing import Dict

import numpy as np

VARIABLES = {"x1x3": ("x1", "x3"), "x0x4": ("x0", "x4"),
             "x1x5": ("x1", "x5"), "x3x4": ("x3", "x4")}


def qoi(name: str, fields: Dict[str, np.ndarray]) -> np.ndarray:
    """The product of two species' molar concentrations, a rate-of-progress
    intermediate (arXiv:2411.05333, S3D case)."""
    a, b = (np.asarray(fields[v], np.float64) for v in VARIABLES[name])
    return a * b
