"""GE CFD fields on a linearised mesh, from a seed."""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench.synthetic import ranged_fields, zero_wall


def generate(manifest: dict, seed: int) -> Dict[str, np.ndarray]:
    """Vx, Vy, Vz, P, D on ``manifest["nodes"]`` nodes, with one contiguous
    wall run where the velocity is exactly zero."""
    ranges = {name: f["range"] for name, f in manifest["fields"].items()}
    fields = ranged_fields((manifest["nodes"],), seed, ranges)
    wall = manifest["wall"]
    zero_wall(fields, wall["fields"], seed, wall["fraction"])
    return fields
