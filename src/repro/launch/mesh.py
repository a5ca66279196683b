"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module must never
touch jax device state (the dry-run sets the fake-device XLA flag before
anything else touches jax).

Hardware model (roofline constants for TPU v5e): 197 TFLOP/s bf16/chip,
819 GB/s HBM/chip, ~50 GB/s/link ICI.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax

# v5e roofline constants (per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link

def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis of type Auto."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         **kwargs)


def make_production_mesh(*, multi_pod: bool = False):
    shape: Tuple[int, ...] = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for(n_devices: int, model_parallel: int = 1):
    """Small-mesh helper for tests/examples on real local devices."""
    data = n_devices // model_parallel
    return make_mesh((data, model_parallel), ("data", "model"))
