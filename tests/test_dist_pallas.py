# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Distributed banded SpMV through the per-shard Mosaic kernel
(LEGATE_SPARSE_TPU_PALLAS_DIST=interpret on the CPU mesh)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import legate_sparse_tpu as sparse
from legate_sparse_tpu.parallel import shard_csr, dist_spmv
from legate_sparse_tpu.parallel.dist_csr import shard_vector
from legate_sparse_tpu.parallel.mesh import make_row_mesh


@pytest.fixture
def mesh():
    devs = jax.devices("cpu")
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return make_row_mesh(devs[:8])


def _poisson(n_grid, dtype=np.float32):
    n = n_grid * n_grid
    return sparse.diags(
        [-1.0, -1.0, 4.0, -1.0, -1.0],
        [-n_grid, -1, 0, 1, n_grid],
        shape=(n, n), format="csr", dtype=dtype,
    )


@pytest.mark.slow
def test_dist_dia_spmv_pallas_matches(mesh, monkeypatch):
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIST", "interpret")
    A = _poisson(16)
    n = A.shape[0]
    dA = shard_csr(A, mesh=mesh)
    assert dA.dia_data is not None and dA.halo >= 0, "need banded halo mode"
    x = np.linspace(-1.0, 1.0, n).astype(np.float32)
    xs = shard_vector(x, mesh, dA.rows_padded)
    y = np.asarray(dist_spmv(dA, xs))[:n]
    y_ref = A.toscipy() @ x
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)


def test_dist_prepack_built_and_routes_match(mesh, monkeypatch):
    """shard_csr pre-blocks the Mosaic layout once (pdia_*); the Pallas
    route over it matches the XLA shifted-add branch exactly."""
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIST", "interpret")
    A = _poisson(16)
    n = A.shape[0]
    dA = shard_csr(A, mesh=mesh)
    assert dA.pdia_tile > 0 and dA.pdia_data is not None
    assert not hasattr(dA, "pdia_mask")
    assert dA.pdia_data.dtype == jnp.float32
    assert dA.pdia_data.shape[1] == len(dA.dia_offsets)
    x = np.linspace(-2.0, 2.0, n).astype(np.float32)
    xs = shard_vector(x, mesh, dA.rows_padded)
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIST", "0")
    y_xla = np.asarray(dist_spmv(dA, xs))[:n]
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIST", "interpret")
    y_pl = np.asarray(dist_spmv(dA, xs))[:n]
    np.testing.assert_allclose(y_pl, y_xla, rtol=1e-6, atol=1e-6)


def test_dist_prepack_on_builders(mesh, monkeypatch):
    """dist_diags (the memory-lean path) and the banded dist_spgemm
    product also carry the prepack — not just shard_csr."""
    from legate_sparse_tpu.parallel import dist_poisson2d
    from legate_sparse_tpu.parallel.dist_spgemm import dist_spgemm

    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIST", "interpret")
    dA = dist_poisson2d(16, mesh=mesh, dtype=np.float32,
                        materialize_ell=False)
    assert dA.pdia_tile > 0, "dist_diags lost the prepack"
    n = dA.shape[0]
    x = np.linspace(-1.0, 1.0, n).astype(np.float32)
    xs = shard_vector(x, mesh, dA.rows_padded)
    y = np.asarray(dist_spmv(dA, xs))[:n]
    import scipy.sparse as sp

    # True 2-D Poisson: (i, i+1) coupling is zero across grid-row
    # boundaries (unlike the plain 5-diagonal band in _poisson).
    N = 16
    main = np.full(n, 4.0)
    off1 = np.full(n - 1, -1.0)
    off1[np.arange(1, N) * N - 1] = 0.0
    offn = np.full(n - N, -1.0)
    Aref = sp.diags([main, off1, off1, offn, offn],
                    [0, 1, -1, N, -N]).tocsr()
    np.testing.assert_allclose(y, Aref @ x, rtol=1e-5, atol=1e-5)

    dB = shard_csr(_poisson(16), mesh=mesh)
    C = dist_spgemm(dB, dB)
    if C.dia_data is not None:
        assert C.pdia_tile > 0, "banded dist_spgemm product lost prepack"


@pytest.mark.tpu
def test_dist_prepack_kernel_on_chip(monkeypatch):
    """The pre-blocked Mosaic dist kernel lowers and runs on a real
    chip inside shard_map (1-device mesh; ring halo wraps to self and
    must stay masked)."""
    if jax.devices()[0].platform != "tpu":
        pytest.skip("no TPU")
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIST", "1")
    A = _poisson(32)
    n = A.shape[0]
    dA = shard_csr(A, mesh=make_row_mesh(jax.devices()[:1]))
    assert dA.pdia_tile > 0
    x = np.linspace(-1.0, 1.0, n).astype(np.float32)
    xs = shard_vector(x, dA.mesh, dA.rows_padded)
    y = np.asarray(dist_spmv(dA, xs))[:n]
    np.testing.assert_allclose(y, A.toscipy() @ x, rtol=1e-4, atol=1e-4)


def _holey_poisson(n_grid, rng):
    """The 5-point band with 20% of its slots dropped (holes) and
    explicit +0.0, -0.0 and NaN entries among the rest; halves only, so
    every product and sum is exact.  Returns (A, entries)."""
    import scipy.sparse as sp

    n = n_grid * n_grid
    entries = {}
    for off in (-n_grid, -1, 0, 1, n_grid):
        for i in range(max(0, -off), min(n, n - off)):
            if rng.random() > 0.2:
                entries[(i, i + off)] = float(rng.integers(-4, 5) * 0.5)
    entries[(40, 40)] = 0.0
    entries[(41, 41)] = -0.0
    entries[(42, 42)] = np.nan
    keys = sorted(entries)
    A_sp = sp.csr_matrix(
        (np.array([entries[k] for k in keys], np.float32),
         ([k[0] for k in keys], [k[1] for k in keys])), shape=(n, n))
    return sparse.csr_array(A_sp), entries


@pytest.mark.parametrize("band", ["exact", "holey"])
def test_dist_dia_spmv_pallas_ieee_nonfinite(band, mesh, monkeypatch, rng):
    # inf in a halo region another shard's rows never reference must
    # not leak NaN through the ring-wrapped exchange.
    from legate_sparse_tpu import obs
    from legate_sparse_tpu.ops import spmv as spmv_ops

    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIST", "interpret")
    if band == "exact":
        A = _poisson(16)
    else:
        A, entries = _holey_poisson(16, rng)
    n = A.shape[0]
    holes_before = obs.counters.get("pallas.pack.hole_slots")
    dA = shard_csr(A, mesh=mesh)
    assert dA.dia_data is not None and dA.halo >= 0
    x = np.ones(n, np.float32)
    x[0] = np.inf  # wraps to the LAST shard's halo via the ring
    if band == "exact":
        xs = shard_vector(x, mesh, dA.rows_padded)
        y = np.asarray(dist_spmv(dA, xs))[:n]
        y_ref = A.toscipy() @ x
        # Rows referencing column 0 see inf; the last rows (whose ring
        # halo holds the wrapped inf) must NOT.
        np.testing.assert_array_equal(np.isinf(y), np.isinf(y_ref))
        assert np.isfinite(y[-1])
        return
    assert dA.dia_mask is not None and dA.pdia_tile > 0
    in_band = sum(n - abs(o) for o in (-16, -1, 0, 1, 16))
    assert (obs.counters.get("pallas.pack.hole_slots") - holes_before
            == in_band - len(entries))
    x[[40, 41]] = np.inf                      # explicit +0.0 / -0.0 rows
    hole_cols = sorted(c for (r, c) in
                       ((r, r + o) for r in range(60, 200, 7)
                        for o in (-1, 1)) if (r, c) not in entries)
    x[hole_cols[:6]] = np.nan
    x[-1] = np.nan   # wraps to the FIRST shard's halo via the ring
    xs = shard_vector(x, mesh, dA.rows_padded)
    y = np.asarray(dist_spmv(dA, xs))[:n]
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIST", "0")
    y_xla = np.asarray(dist_spmv(dA, xs))[:n]
    y_csr = np.asarray(spmv_ops.csr_spmv_rowids(
        A._data, A._indices, A._get_row_ids(), jnp.asarray(x), n))
    bits = lambda a: np.asarray(a, np.float32).view(np.uint32)
    np.testing.assert_array_equal(bits(y), bits(y_xla))
    np.testing.assert_array_equal(bits(y), bits(y_csr))
    # 0 * inf at the explicit +0.0 and -0.0 entries, as in CSR.
    assert np.isnan(y[40]) and np.isnan(y[41]) and np.isnan(y[42])
    assert np.isfinite(y[n // 2])
