# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""CG solver tests (mirrors reference ``test_cg_solve.py``)."""

import jax
import numpy as np
import pytest

import legate_sparse_tpu as sparse
import legate_sparse_tpu.linalg as linalg
from legate_sparse_tpu import parallel
from utils_test.gen import spd_system

SOLVERS = ["linalg", "dist"]


def _solver(kind, A):
    """``(b, **kw) -> (x, iters)``: ``linalg.cg`` on ``A``, or
    ``dist_cg`` on ``A`` sharded over the CPU mesh."""
    if kind == "linalg":
        return lambda b, **kw: linalg.cg(A, b, **kw)
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    dA = parallel.shard_csr(A, mesh=parallel.make_row_mesh())
    return lambda b, **kw: parallel.dist_cg(dA, b, **kw)


def test_cg_solve():
    N = 1000
    A_dense, x = spd_system(N, 0.1, 471014)
    assert np.all(np.linalg.eigvals(A_dense) > 0)
    A = sparse.csr_array(A_dense)
    y = A @ x
    x_pred, iters = linalg.cg(A, y, tol=1e-8)
    np.testing.assert_allclose(
        np.asarray(A @ x_pred), np.asarray(y), rtol=1e-8, atol=0.0
    )
    assert iters > 0


@pytest.mark.parametrize("kind", SOLVERS)
def test_cg_solve_with_callback(kind):
    N = 300
    A_dense, x = spd_system(N, 0.1, 471014)
    A = sparse.csr_array(A_dense)
    assert A.dtype == np.float64
    y = A @ x
    residuals = []

    def callback(xk):
        residuals.append(y - A @ xk)

    solve = _solver(kind, A)
    x_pred, iters = solve(y, tol=1e-8, callback=callback)
    np.testing.assert_allclose(
        np.asarray(A @ x_pred), np.asarray(y), rtol=1e-8, atol=0.0
    )
    assert len(residuals) == iters
    # The callback path steps the loop path's iteration.
    x_loop, iters_loop = solve(y, tol=1e-8)
    assert int(iters) == int(iters_loop)
    np.testing.assert_allclose(np.asarray(x_pred), np.asarray(x_loop),
                               rtol=0.0, atol=1e-10)


@pytest.mark.parametrize("observed", [False, True],
                         ids=["loop", "callback"])
@pytest.mark.parametrize("kind", SOLVERS)
def test_cg_from_exact_solution_stays_finite(kind, observed):
    """Starting at the solution, r0 = 0 exactly: rho and p.Ap are 0,
    and every path must carry x0 through to the convergence check
    (once 0/0 = NaN on the callback path)."""
    n = 64
    A = sparse.diags([-np.ones(n - 1), np.full(n, 2.0), -np.ones(n - 1)],
                     [-1, 0, 1], shape=(n, n), format="csr",
                     dtype=np.float32)
    xs = (np.arange(n) % 7 - 3).astype(np.float32)
    b = np.asarray(A @ xs)          # integers: exact in any order
    solve = _solver(kind, A)
    seen = []
    x, iters = solve(b, x0=xs, maxiter=60,
                     callback=seen.append if observed else None)
    x = np.asarray(x)
    assert np.all(np.isfinite(x))
    np.testing.assert_allclose(x, xs, rtol=0.0, atol=1e-6)
    _, iters_loop = solve(b, x0=xs, maxiter=60)
    assert int(iters) == int(iters_loop) == 25
    assert len(seen) == (int(iters) if observed else 0)


def test_cg_solve_linear_operator():
    N = 300
    A_dense, x = spd_system(N, 0.1, 7)
    A = sparse.csr_array(A_dense)
    y = A @ x
    op = linalg.LinearOperator(A.shape, matvec=lambda v: A @ v,
                               dtype=A.dtype)
    x_pred, _ = linalg.cg(op, y, tol=1e-8)
    np.testing.assert_allclose(
        np.asarray(A @ x_pred), np.asarray(y), rtol=1e-8, atol=0.0
    )


def test_cg_solve_preconditioned():
    N = 300
    A_dense, x = spd_system(N, 0.1, 99)
    A = sparse.csr_array(A_dense)
    y = A @ x
    dinv = 1.0 / np.asarray(A.diagonal())
    M = linalg.LinearOperator(
        A.shape, matvec=lambda v: dinv * v, dtype=A.dtype
    )
    x_pred, iters_pre = linalg.cg(A, y, tol=1e-10, M=M)
    np.testing.assert_allclose(
        np.asarray(A @ x_pred), np.asarray(y), rtol=1e-8, atol=1e-8
    )


def test_cg_x0():
    N = 200
    A_dense, x = spd_system(N, 0.2, 31)
    A = sparse.csr_array(A_dense)
    y = A @ x
    x_pred, iters = linalg.cg(A, y, x0=np.asarray(x), tol=1e-8,
                              conv_test_iters=1)
    # Starting at the exact solution must converge immediately.
    assert iters <= 2
