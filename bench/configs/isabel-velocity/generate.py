"""Hurricane ISABEL velocity fields at the configuration's grid, from a seed."""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench.synthetic import ranged_fields


def generate(manifest: dict, seed: int) -> Dict[str, np.ndarray]:
    """Vx, Vy, Vz (ISABEL's Uf, Vf, Wf) on ``manifest["shape"]``: float32
    values, as SDRBench stores them, widened to the float64 the program
    takes."""
    ranges = {name: f["range"] for name, f in manifest["fields"].items()}
    fields = ranged_fields(manifest["shape"], seed, ranges)
    return {k: v.astype(np.float32).astype(np.float64)
            for k, v in fields.items()}
