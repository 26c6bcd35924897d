# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""TPU smoke lane: the core kernels compile and match scipy on a real chip.

The rest of the suite pins the cpu platform for determinism
(``conftest.py``); this file is the continuously-runnable evidence that
SpMV / SpGEMM / CG compile and run on the accelerator — the role of the
reference's on-hardware ``legate --gpus 1`` test invocation.

Invocation (documented driver contract)::

    LEGATE_SPARSE_TPU_TEST_PLATFORM=tpu python -m pytest -m tpu tests/ -q

Under the default (cpu-pinned) suite these tests skip.
"""

import numpy as np
import pytest

import jax

import legate_sparse_tpu as sparse
from legate_sparse_tpu import linalg

pytestmark = pytest.mark.tpu


@pytest.fixture(scope="module")
def accel():
    """Skip unless the default platform is an accelerator."""
    platform = jax.devices()[0].platform
    if platform == "cpu":
        pytest.skip(
            "no accelerator platform (set LEGATE_SPARSE_TPU_TEST_PLATFORM"
            "=tpu to run the smoke lane on a real chip)"
        )
    return platform


def _poisson(n_grid, dtype=np.float32):
    n = n_grid * n_grid
    return sparse.diags(
        [-1.0, -1.0, 4.0, -1.0, -1.0],
        [-n_grid, -1, 0, 1, n_grid],
        shape=(n, n), format="csr", dtype=dtype,
    )


def test_spmv_matches_scipy(accel):
    A = _poisson(16)
    x = np.linspace(-1.0, 1.0, A.shape[0]).astype(np.float32)
    y = np.asarray(A @ x)
    y_ref = A.toscipy() @ x
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)


def test_spgemm_matches_scipy(accel):
    A = _poisson(8)
    C = A @ A
    C_ref = (A.toscipy() @ A.toscipy()).tocsr()
    C_sp = C.toscipy()
    np.testing.assert_allclose(C_sp.toarray(), C_ref.toarray(),
                               rtol=1e-5, atol=1e-5)


def test_pallas_dia_kernel_on_chip(accel):
    """The Mosaic DIA kernel lowers, runs, and matches scipy on the
    real chip (not interpret mode)."""
    from legate_sparse_tpu.ops import pallas_dia

    A = _poisson(32)
    dia = A._get_dia()
    assert dia is not None
    dia_data, offsets, mask = dia
    packed = pallas_dia.pack_band(dia_data, offsets, A.shape, mask=mask)
    assert packed is not None
    x = np.linspace(-1.0, 1.0, A.shape[0]).astype(np.float32)
    y = np.asarray(pallas_dia.pallas_dia_spmv(
        packed.rdata, x, packed.offsets, packed.shape,
        packed.tile, interpret=False,
    ))
    y_ref = A.toscipy() @ x
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)


def test_pallas_dia_spmm_on_chip(accel):
    from legate_sparse_tpu.ops import pallas_dia

    A = _poisson(32)
    packed = A._get_dia_pack()
    assert packed is not None
    X = np.linspace(-1.0, 1.0, A.shape[0] * 4).reshape(
        A.shape[0], 4).astype(np.float32)
    tile = pallas_dia._spmm_tile(packed, 4)
    assert tile is not None
    Y = np.asarray(pallas_dia.pallas_dia_spmm(
        packed.rdata, X, packed.offsets, packed.shape,
        tile, interpret=False,
    ))
    np.testing.assert_allclose(Y, A.toscipy() @ X, rtol=1e-5, atol=1e-5)


def test_fused_xla_band_fallback_on_chip(accel):
    """The fused XLA band SpMV (dia_spmv_fused, the path with the
    Pallas kernel off) runs on-chip and matches scipy."""
    from legate_sparse_tpu.ops import dia_ops

    A = _poisson(32)
    dia = A._get_dia()
    dia_data, offsets, mask = dia
    dpad, mpad = dia_ops.pad_dia(dia_data, offsets, A.shape, mask=mask,
                                 with_mask=mask is not None)
    x = np.linspace(-1.0, 1.0, A.shape[0]).astype(np.float32)
    y = np.asarray(dia_ops.dia_spmv_fused(dpad, mpad, x, offsets,
                                          A.shape))
    np.testing.assert_allclose(y, A.toscipy() @ x, rtol=1e-5, atol=1e-5)


def test_bf16_band_on_chip(accel):
    """bf16 band storage (the bench's TPU-native extension metric)
    dispatches and lands within bf16 tolerance of the f64 reference."""
    import jax.numpy as jnp

    A = _poisson(24, dtype=jnp.bfloat16)
    x = np.linspace(-1.0, 1.0, A.shape[0]).astype(np.float32)
    y = np.asarray(A @ x.astype(jnp.bfloat16)).astype(np.float32)
    y_ref = _poisson(24, dtype=np.float32).toscipy() @ x
    # bf16 has ~3 significant digits; the operator has entries in
    # [-4, 4] and row sums of 0-4.
    np.testing.assert_allclose(y, y_ref, rtol=0.05, atol=0.05)


def test_cg_converges(accel):
    A = _poisson(16)
    b = np.ones(A.shape[0], dtype=np.float32)
    x, info = linalg.cg(A, b, rtol=1e-5, maxiter=2000)
    res = np.linalg.norm(np.asarray(A @ np.asarray(x)) - b)
    assert res < 1e-2 * np.linalg.norm(b)


def test_eigsh_on_chip(accel):
    # The Lanczos scan (matvec chain + reorthogonalization) on chip.
    A = _poisson(16)
    w, _ = linalg.eigsh(A, k=3, which="SA", tol=1e-4)
    import scipy.sparse.linalg as ssl

    w_ref = ssl.eigsh(A.toscipy().astype(np.float64), k=3, which="SA",
                      return_eigenvectors=False)
    np.testing.assert_allclose(np.sort(w), np.sort(w_ref), rtol=1e-3)


def test_minres_on_chip(accel):
    A = _poisson(16)
    b = np.ones(A.shape[0], dtype=np.float32)
    x, _ = linalg.minres(A, b, rtol=1e-5, maxiter=2000)
    res = np.linalg.norm(np.asarray(A @ np.asarray(x)) - b)
    assert res < 1e-2 * np.linalg.norm(b)


def test_expm_multiply_on_chip(accel):
    # Taylor fori_loop chain (SpMV per term) on chip.
    A = _poisson(12)
    L = A * np.float32(-0.05)    # decaying semigroup
    b = np.ones(L.shape[0], dtype=np.float32)
    got = linalg.expm_multiply(L, b)
    import scipy.sparse.linalg as ssl

    ref = ssl.expm_multiply(L.toscipy().astype(np.float64),
                            b.astype(np.float64))
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-4,
                               atol=1e-5)


def test_connected_components_on_chip(accel):
    # Label-propagation while_loop (scatter-min sweeps) on chip.
    rows = np.array([0, 1, 3, 4])
    cols = np.array([1, 0, 4, 3])
    A = sparse.csr_array((np.ones(4, np.float32), (rows, cols)),
                         shape=(6, 6))
    k, labels = sparse.csgraph.connected_components(A, directed=False)
    assert k == 4
    assert labels[0] == labels[1] and labels[3] == labels[4]
