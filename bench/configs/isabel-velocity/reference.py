"""Plain float64 NumPy VTOT, the reference that ISABEL answers are held to."""
from __future__ import annotations

from typing import Dict

import numpy as np

VARIABLES = {"VTOT": ("Vx", "Vy", "Vz")}


def qoi(name: str, fields: Dict[str, np.ndarray]) -> np.ndarray:
    """VTOT = sqrt(Vx^2 + Vy^2 + Vz^2) (arXiv:2411.05333, Eq. 1)."""
    if name != "VTOT":
        raise KeyError(name)
    vx, vy, vz = (np.asarray(fields[v], np.float64) for v in VARIABLES[name])
    return np.sqrt(vx * vx + vy * vy + vz * vz)
