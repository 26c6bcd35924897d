# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Real-width compiles of the main-path kernels for a described v5e.

The TPU compiler is installed here, so each case lowers and *compiles*
its kernel or plan for a ``v5e:2x2`` topology that is described, not
attached (the ``on-chip-measurement`` guide, section 2).  This catches
what interpret mode cannot — tiling-misaligned blocks, scoped-VMEM
overuse, 64-bit types in a Mosaic kernel, a program over the chip's HBM
— without chip time.  Everything is compiled as on the chip: x64 off,
at the widths ``chip_smoke.py`` runs.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every pytest worker
imports every test file.  Keep all such compiles in this one file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from legate_sparse_tpu.ops import bsr, pallas_dia
from legate_sparse_tpu.ops import spmv as spmv_ops

BAND_ROWS = 1 << 24          # chip_smoke phase a
SPGEMM_ROWS = 1 << 22        # chip_smoke phase c
OFFSETS = tuple(range(-5, 6))
GRID = 2048                  # chip_smoke --four-chips CG grid


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip_config(topo):
    """x64 off (as on the chip) and the persistent compile cache off:
    a described-chip compile is written to the cache but can never be
    read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache

    x64 = jax.config.jax_enable_x64
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_x64", x64)
    jax.config.update("jax_enable_compilation_cache", cache)
    compilation_cache.reset_cache()


def _band_tile(masked: bool, dtype=jnp.float32) -> int:
    tile = pallas_dia.supported(OFFSETS, dtype, masked)
    assert tile is not None
    return tile


def _band(n, masked, dtype, S):
    tile = _band_tile(masked, dtype)
    rows_pad = -(-n // tile) * tile
    rd = S((len(OFFSETS), rows_pad // 128, 128), dtype)
    rm = S(rd.shape, jnp.int8) if masked else None
    return tile, rd, rm


def _spmv(masked, dtype=jnp.float32):
    def build(S):
        n = BAND_ROWS
        tile, rd, rm = _band(n, masked, dtype, S)
        if masked:
            return (lambda r, m, x: pallas_dia.pallas_dia_spmv(
                r, m, x, OFFSETS, (n, n), tile)), (rd, rm, S((n,), dtype))
        return (lambda r, x: pallas_dia.pallas_dia_spmv(
            r, None, x, OFFSETS, (n, n), tile)), (rd, S((n,), dtype))
    return build


def _fori_loop(S):
    """The kernel chained in a jitted fori_loop: the composition that
    crashed the worker in the earlier on-chip bench runs."""
    n = BAND_ROWS
    tile, rd, _ = _band(n, False, jnp.float32, S)

    def loop(r, x):
        return jax.lax.fori_loop(
            0, 8, lambda i, v: pallas_dia.pallas_dia_spmv(
                r, None, v, OFFSETS, (n, n), tile), x)
    return loop, (rd, S((n,), jnp.float32))


class _Pack:
    """The fields of ``PackedBand`` that ``_spmm_tile`` reads."""

    def __init__(self, tile, masked):
        self.offsets = OFFSETS
        self.tile = tile
        self.rdata = np.zeros(1, np.float32)
        self.rmask = 1 if masked else None


def _spmm(k):
    def build(S):
        n = SPGEMM_ROWS
        tile, rd, rm = _band(n, True, jnp.float32, S)
        mt = pallas_dia._spmm_tile(_Pack(tile, True), k)
        assert mt is not None
        return (lambda r, m, X: pallas_dia.pallas_dia_spmm(
            r, m, X, OFFSETS, (n, n), mt)), (rd, rm,
                                             S((n, k), jnp.float32))
    return build


def _spgemm(S):
    n = SPGEMM_ROWS
    offs_c = tuple(sorted({a + b for a in OFFSETS for b in OFFSETS}))
    tile = pallas_dia._spgemm_tile(OFFSETS, 11, 11, len(offs_c),
                                   np.float32)
    assert tile is not None
    band = S((len(OFFSETS), n), jnp.float32)
    return (lambda a, b: pallas_dia.pallas_dia_spgemm(
        a, b, OFFSETS, OFFSETS, offs_c, (n, n), (n, n), tile)), (band, band)


def _bsr(k):
    def build(S):
        nb, nbc = bsr.MAX_BLOCKS, 1 << 14
        blocks = (S((nb, 128, 128), jnp.float32), S((nb,), jnp.int32),
                  S((nb,), jnp.int32))
        if k is None:
            return (lambda b, r, c, x: bsr.bsr_spmv_pallas(
                b, r, c, x, nbc, nbc)), (*blocks,
                                         S((nbc, 1, 128), jnp.float32))
        return (lambda b, r, c, x: bsr.bsr_spmm_pallas(
            b, r, c, x, nbc, nbc)), (*blocks,
                                     S((nbc, k, 128), jnp.float32))
    return build


def _rowids(S):
    n = SPGEMM_ROWS
    nnz = n * len(OFFSETS)
    return (lambda d, i, r, x: spmv_ops.csr_spmv_rowids(d, i, r, x, n)), (
        S((nnz,), jnp.float32), S((nnz,), jnp.int32),
        S((nnz,), jnp.int32), S((n,), jnp.float32))


def _dist_1d(topo):
    """The banded 1d-row dist SpMV plan body (halo ppermute + the
    per-shard Mosaic kernel) for the 5-point grid on 4 chips."""
    from legate_sparse_tpu.parallel.dist_csr import _dia_spmv_pallas_fn

    mesh = Mesh(np.asarray(topo.devices[:4]), ("rows",))
    R, rows = 4, GRID * GRID
    rps, halo = rows // R, GRID
    offsets = (-GRID, -1, 0, 1, GRID)
    tile = pallas_dia.supported(tuple(o + halo for o in offsets),
                                jnp.float32, True)
    rps_pad = -(-rps // tile) * tile
    spec = NamedSharding(mesh, P("rows", None, None))
    band = (len(offsets), rps_pad)
    args = (jax.ShapeDtypeStruct((R, *band), jnp.float32, sharding=spec),
            jax.ShapeDtypeStruct((R, *band), jnp.int8, sharding=spec),
            jax.ShapeDtypeStruct((rows,), jnp.float32,
                                 sharding=NamedSharding(mesh, P("rows"))))
    return _dia_spmv_pallas_fn(mesh, offsets, halo, rps, tile, False), args


def _dist_2d(topo):
    """The 2d-block dist SpMV plan body on a 2x2 grid of the 4 chips."""
    from legate_sparse_tpu.parallel.dist_csr import _block_spmv_2d_fn

    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(2, 2),
                ("rows", "cols"))
    rows = GRID * GRID
    cap = -(-(5 * rows) // 2 // 128) * 128     # nnz per diagonal block
    blk = NamedSharding(mesh, P("rows", "cols", None))

    def S(dtype):
        return jax.ShapeDtypeStruct((2, 2, cap), dtype, sharding=blk)

    args = (S(jnp.float32), S(jnp.int32), S(jnp.int32),
            jax.ShapeDtypeStruct((2, 2), jnp.int32,
                                 sharding=NamedSharding(
                                     mesh, P("rows", "cols"))),
            jax.ShapeDtypeStruct((rows,), jnp.float32,
                                 sharding=NamedSharding(
                                     mesh, P(("rows", "cols")))))
    return _block_spmv_2d_fn(mesh, (2, 2), rows // 2, False), args


ONE_CHIP = {
    "dia-spmv-f32": _spmv(False),
    "dia-spmv-f32-masked": _spmv(True),
    "dia-spmv-fori-loop": _fori_loop,
    "dia-spmv-bf16": _spmv(False, jnp.bfloat16),
    "dia-spmm-k16": _spmm(16),
    "dia-spmm-k128": _spmm(128),
    "dia-spgemm": _spgemm,
    "bsr-spmv": _bsr(None),
    "bsr-spmm-k16": _bsr(16),
    "csr-rowids": _rowids,
}
FOUR_CHIPS = {"dist-spmv-1d-row": _dist_1d, "dist-spmv-2d-block": _dist_2d}


@pytest.mark.parametrize("case", [*ONE_CHIP, *FOUR_CHIPS])
def test_compiles_for_v5e(case, chip_config):
    topo = chip_config
    if case in ONE_CHIP:
        one_chip = SingleDeviceSharding(topo.devices[0])

        def S(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        fn, args = ONE_CHIP[case](S)
        fn = jax.jit(fn)
    else:
        fn, args = FOUR_CHIPS[case](topo)
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    if case.startswith(("dia-", "bsr-", "dist-spmv-1d")):
        assert "tpu_custom_call" in text      # the Mosaic kernel is there
    mem = compiled.memory_analysis()
    if mem is not None:
        used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
        assert used < 16 << 30                # one v5e's HBM
