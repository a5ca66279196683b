"""The main path's device programs compile for a TPU v5e at Hurricane ISABEL
size and fit its memory — checked here without a chip.

Each test compiles for one chip of a described ``v5e:2x2`` topology (the
TPU compiler is installed even where no TPU is attached): what Mosaic or
XLA would refuse on the chip fails here.  Sizes are ISABEL's SDRBench
100x500x500 field padded by the transform to 129x513x513, whose finest
coefficient group is the largest array the decode path sees; the
recompose programs are compiled at the benchmark cells' grids as well.  The
topology is described inside a fixture, never at import, so every xdist
worker collects the same tests and only the worker running this file loads
the TPU library.
"""
import os
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitplane_pack, bitplane_unpack, ops
from repro.serve.batch import Ticket, _item_bytes
from repro.transform.hierarchical import (level_map, recompose_hb_from,
                                          scatter_recompose_from,
                                          scatter_recompose_from_batch)

PADDED = (129, 513, 513)
LEVELS = 7
FINEST = int(np.prod(PADDED)) - 65 * 257 * 257     # level-0 group count
HBM_BYTES = int(15.75 * 2 ** 30)                   # usable HBM of one v5e
SMOKE_BATCH = 2                                    # chip_smoke.py clients
LADDER = (33, 129, 129)          # isabel.ladder's padded 25x125x125 grid
LADDER_LEVELS = 5
S3D = (129, 129, 129)            # s3d.ladder's padded 125^3 grid
S3D_LEVELS = 7


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                           # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def _pad(n, mult):
    return -(-n // mult) * mult


def test_pack_kernel_compiles(one_chip):
    n = _pad(FINEST, bitplane_pack.tile_elems(8))
    compiled = jax.jit(
        lambda m: bitplane_pack.pack_planes_traced(m, 32, 8, False)
    ).lower(_sds(one_chip, (n,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def test_fused_encode_compiles_at_48_bits(one_chip):
    n = _pad(FINEST, bitplane_pack.tile_elems(8))
    compiled = ops._encode_planes_fused.lower(
        _sds(one_chip, (n,), jnp.float64), _sds(one_chip, (), jnp.float64),
        nbits=48, rows=8, interpret=False).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2   # hi + lo
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("planes,words,rows", [
    (32, 8 * 1024, 8),      # full 8-row tiles
    (16, 128, 1),           # the one-tile geometry small groups take
])
def test_unpack_kernel_compiles(one_chip, planes, words, rows):
    compiled = bitplane_unpack._unpack.lower(
        _sds(one_chip, (planes, words), jnp.uint32),
        _sds(one_chip, (planes,), jnp.int32),
        rows=rows, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _decode_args(sharding, batch=None, slots=64):
    w = (FINEST + 31) // 32
    lead = () if batch is None else (batch,)
    return (_sds(sharding, lead + (slots, w), jnp.uint32),
            _sds(sharding, lead + (slots,), jnp.uint64),
            _sds(sharding, lead + (w * 32,), jnp.uint64),
            _sds(sharding, lead + (w * 4,), jnp.uint8),
            _sds(sharding, lead, jnp.float64))


def test_fused_decode_64_slots_fits_hbm(one_chip):
    compiled = ops._decode_fused.lower(*_decode_args(one_chip)).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def _nbytes(shape, dtype):
    return SimpleNamespace(nbytes=int(np.prod(shape)) * np.dtype(dtype).itemsize)


def test_batched_decode_fits_the_batchers_budget(one_chip):
    """The smoke's batch of finest groups compiles, fits, and stays under
    what ``DecodeBatcher`` budgets for it — the estimate that splits
    buckets must not undercount what the compiler allocates."""
    compiled = ops._decode_fused_batch.lower(
        *_decode_args(one_chip, batch=SMOKE_BATCH)).compile()
    used = _device_bytes(compiled)
    w = (FINEST + 31) // 32
    item = Ticket(None, "decode", None,
                  (_nbytes((64, w), np.uint32), None,
                   _nbytes((w * 32,), np.uint64),
                   _nbytes((w * 4,), np.uint8)))
    assert used <= SMOKE_BATCH * _item_bytes(item)
    assert SMOKE_BATCH * _item_bytes(item) <= HBM_BYTES // 3


def test_batched_recompose_fits_the_batchers_budget(one_chip):
    count = int(np.sum(level_map(PADDED, LEVELS) == 0))
    assert count == FINEST
    compiled = scatter_recompose_from_batch.lower(
        _sds(one_chip, (SMOKE_BATCH, count), jnp.float64),
        PADDED, LEVELS, 0).compile()
    used = _device_bytes(compiled)
    item = Ticket(None, "recompose", None,
                  (None, _nbytes((count,), np.float64), PADDED))
    assert used <= SMOKE_BATCH * _item_bytes(item)
    assert SMOKE_BATCH * _item_bytes(item) <= HBM_BYTES // 3


@pytest.mark.parametrize("program,start", [
    ("scatter_recompose_from", 0), ("scatter_recompose_from", 1),
    ("scatter_recompose_from", 2),     # the device route of groups 0-2
    ("scatter_recompose_from_batch", 0), ("scatter_recompose_from_batch", 1),
    ("scatter_recompose_from_batch", 2),   # ... batched across clients
    ("recompose_hb_from", 3), ("recompose_hb_from", 4),   # the host route
])
def test_hb_recompose_holds_no_scatter(one_chip, program, start):
    """The recompose programs that isabel.ladder runs place and interleave
    by slices and reshapes: a TPU scatter runs close to serially, and one
    per level made these programs the device's largest cost."""
    if program == "recompose_hb_from":
        compiled = recompose_hb_from.lower(
            _sds(one_chip, LADDER, jnp.float64), LADDER_LEVELS,
            start).compile()
    else:
        count = int(np.sum(level_map(LADDER, LADDER_LEVELS) == start))
        batched = program == "scatter_recompose_from_batch"
        batch = (SMOKE_BATCH,) if batched else ()
        fn = scatter_recompose_from_batch if batched else \
            scatter_recompose_from
        compiled = fn.lower(
            _sds(one_chip, batch + (count,), jnp.float64), LADDER,
            LADDER_LEVELS, start).compile()
    assert "scatter(" not in compiled.as_text()


@pytest.mark.parametrize("program,start", [
    ("scatter_recompose_from", 3),      # the device route of groups 0-3
    ("scatter_recompose_from_batch", 0), ("scatter_recompose_from_batch", 1),
    ("scatter_recompose_from_batch", 2), ("scatter_recompose_from_batch", 3),
    ("recompose_hb_from", 4), ("recompose_hb_from", 5),
    ("recompose_hb_from", 6),           # the host route, base group included
])
def test_s3d_recompose_compiles_without_scatter(one_chip, program, start):
    """s3d.ladder's cubic grid has two more levels than isabel.ladder's:
    its recompose programs compile at every start the reader takes there,
    and hold no scatter either.  Groups 0-3 hold at least
    ``ops.FUSED_MIN_COUNT`` coefficients, so they take the device route."""
    counts = [int(np.sum(level_map(S3D, S3D_LEVELS) == s))
              for s in range(S3D_LEVELS)]
    assert [c >= ops.FUSED_MIN_COUNT for c in counts] == \
        [True] * 4 + [False] * (S3D_LEVELS - 4)
    if program == "recompose_hb_from":
        compiled = recompose_hb_from.lower(
            _sds(one_chip, S3D, jnp.float64), S3D_LEVELS, start).compile()
    else:
        batched = program == "scatter_recompose_from_batch"
        batch = (SMOKE_BATCH,) if batched else ()
        fn = scatter_recompose_from_batch if batched else \
            scatter_recompose_from
        compiled = fn.lower(
            _sds(one_chip, batch + (counts[start],), jnp.float64), S3D,
            S3D_LEVELS, start).compile()
    assert "scatter(" not in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES
