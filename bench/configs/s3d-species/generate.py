"""S3D species molar concentrations at the configuration's grid, from a
seed."""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench.synthetic import ranged_fields


def generate(manifest: dict, seed: int) -> Dict[str, np.ndarray]:
    """One float64 field per species of ``manifest["fields"]`` on
    ``manifest["shape"]``: 10 to the power of a smooth seeded field that
    runs over the decades of the species' ``range``, so every value is
    positive and each field spans decades.  Field ``i`` (in the manifest's
    order) is drawn from seed ``seed + i + 1``."""
    exponents = ranged_fields(
        manifest["shape"], seed,
        {name: np.log10(spec["range"])
         for name, spec in manifest["fields"].items()})
    return {name: np.power(10.0, e) for name, e in exponents.items()}
