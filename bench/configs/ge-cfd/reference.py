"""Plain float64 NumPy GE QoIs, paper Eq. 1-6, that GE answers are held to.

Constants as arXiv:2411.05333 Sec. III-A states them.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

R = 287.1          # specific gas constant of air, J/(kg K)
GAMMA = 1.4        # heat capacity ratio
MU_R = 1.716e-5    # Sutherland reference viscosity, Pa s
T_R = 273.15       # Sutherland reference temperature, K
S = 110.4          # Sutherland temperature, K

VARIABLES = {
    "VTOT": ("Vx", "Vy", "Vz"),
    "T": ("P", "D"),
    "C": ("P", "D"),
    "Mach": ("Vx", "Vy", "Vz", "P", "D"),
    "PT": ("Vx", "Vy", "Vz", "P", "D"),
    "mu": ("P", "D"),
}


def qoi(name: str, fields: Dict[str, np.ndarray]) -> np.ndarray:
    """QoI ``name`` evaluated pointwise on ``fields`` in float64."""
    f = {k: np.asarray(fields[k], np.float64) for k in VARIABLES[name]}
    if name == "VTOT":                                   # Eq. 1
        return np.sqrt(f["Vx"] ** 2 + f["Vy"] ** 2 + f["Vz"] ** 2)
    t = f["P"] / (f["D"] * R)                            # Eq. 2
    if name == "T":
        return t
    if name == "C":                                      # Eq. 3
        return np.sqrt(GAMMA * R * t)
    if name == "mu":                                     # Eq. 6
        return MU_R * (t / T_R) ** 1.5 * (T_R + S) / (t + S)
    mach = np.sqrt(f["Vx"] ** 2 + f["Vy"] ** 2 + f["Vz"] ** 2) \
        / np.sqrt(GAMMA * R * t)                         # Eq. 4
    if name == "Mach":
        return mach
    if name == "PT":                                     # Eq. 5
        return f["P"] * (1.0 + GAMMA / 2.0 * mach ** 2) ** 3.5
    raise KeyError(name)
