# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Round-6 satellite fixes.

- Explicit-sigma ``eigsh``/``eigs`` get the same
  ``ArpackNoConvergence`` -> host-fallback ladder the SM routes
  already had (ADVICE r5 low): a sigma near an eigenvalue stagnates
  the inexact iterative inverse where scipy's exact ``splu``
  factorization succeeds — the user should get scipy's answer, not a
  raise.
- ``lobpcg``'s Lanczos-backed routes seed with the FULL orthogonalized
  X block (one combined start vector), not just ``X[:, 0]`` (ADVICE
  r5 low).
"""


import numpy as np
import pytest

import legate_sparse_tpu as sparse
import legate_sparse_tpu.linalg as linalg


# ---------------------------------------------- explicit-sigma ladders --
def _laplacian_1d(n=64, dtype=np.float64):
    """Tridiagonal 1-D Laplacian: eigenvalues 2 - 2 cos(k pi / (n+1)).
    A sigma 1e-9 above the smallest one makes (A - sigma I) condition
    ~1e9 — the inexact inner Krylov solve stagnates at its probe —
    while scipy's exact ``splu`` factorization handles it exactly."""
    main = np.full(n, 2.0, dtype=dtype)
    off = np.full(n - 1, -1.0, dtype=dtype)
    A = sparse.diags([main, off, off], [0, 1, -1], shape=(n, n),
                     format="csr", dtype=dtype)
    lam = np.sort(2.0 - 2.0 * np.cos(
        np.arange(1, n + 1) * np.pi / (n + 1)))
    return A, lam


def test_eigsh_explicit_sigma_near_eigenvalue_falls_back():
    from legate_sparse_tpu.obs import counters

    A, lam = _laplacian_1d()
    sigma = lam[0] + 1e-9
    before = counters.get("scipy_fallback.linalg.eigsh")
    w = linalg.eigsh(A, k=3, sigma=sigma, which="LM",
                     return_eigenvectors=False)
    # Nearest to sigma: the three smallest (ascending, scipy order).
    np.testing.assert_allclose(np.sort(np.asarray(w)), lam[:3],
                               atol=1e-8)
    assert counters.get("scipy_fallback.linalg.eigsh") == before + 1


def test_eigs_explicit_sigma_near_eigenvalue_falls_back():
    from legate_sparse_tpu.obs import counters

    A, lam = _laplacian_1d()
    sigma = lam[0] + 1e-9
    before = counters.get("scipy_fallback.linalg.eigs")
    w = linalg.eigs(A, k=3, sigma=sigma, which="LM",
                    return_eigenvectors=False)
    np.testing.assert_allclose(np.sort(np.real(np.asarray(w))),
                               lam[:3], atol=1e-7)
    assert np.iscomplexobj(np.asarray(w))   # scipy contract preserved
    assert counters.get("scipy_fallback.linalg.eigs") == before + 1


def test_eigsh_explicit_sigma_clean_stays_native():
    """A well-separated sigma must keep the native device route (the
    ladder is a fallback, not a rewrite)."""
    from legate_sparse_tpu.obs import counters

    A = sparse.diags([np.arange(1.0, 25.0)], [0], shape=(24, 24),
                     format="csr", dtype=np.float64)
    before = counters.get("scipy_fallback.linalg.eigsh")
    w, X = linalg.eigsh(A, k=2, sigma=2.5, which="LM")
    np.testing.assert_allclose(np.asarray(w), [2.0, 3.0], atol=1e-6)
    assert counters.get("scipy_fallback.linalg.eigsh") == before


# ------------------------------------------------- lobpcg block seed --
@pytest.mark.slow
def test_lobpcg_generalized_block_seed_survives_bad_first_column():
    """X[:, 0] an exact eigenvector of the WRONG end of the spectrum:
    the old single-column seed handed Lanczos an immediate breakdown
    start; the block seed must still find the largest pairs."""
    n = 60
    d = np.arange(1.0, n + 1.0)
    A = sparse.diags([d], [0], shape=(n, n), format="csr",
                     dtype=np.float64)
    B = sparse.diags([np.ones(n)], [0], shape=(n, n), format="csr",
                     dtype=np.float64)
    rng = np.random.default_rng(9)
    X = np.zeros((n, 2))
    X[0, 0] = 1.0                      # eigenvector of the SMALLEST
    X[:, 1] = rng.standard_normal(n)
    w, V = linalg.lobpcg(A, X, B=B, largest=True, tol=1e-9)
    np.testing.assert_allclose(np.asarray(w), [n, n - 1], atol=1e-6)
    for i, lam in enumerate(np.asarray(w)):
        v = np.asarray(V)[:, i]
        resid = np.linalg.norm(d * v - lam * v)
        assert resid < 1e-5 * max(abs(lam), 1.0)


def test_lobpcg_complex_block_seed():
    """Complex-Hermitian route through the native Lanczos: same block
    seeding."""
    n = 40
    d = np.arange(1.0, n + 1.0)
    A_d = np.diag(d).astype(np.complex64)
    A = sparse.csr_array(A_d)
    rng = np.random.default_rng(21)
    X = np.zeros((n, 2), dtype=np.complex64)
    X[0, 0] = 1.0
    X[:, 1] = (rng.standard_normal(n)
               + 1j * rng.standard_normal(n)).astype(np.complex64)
    w, V = linalg.lobpcg(A, X, largest=True, tol=1e-5)
    np.testing.assert_allclose(np.asarray(w), [n, n - 1], atol=1e-3)


# --------------------------------------------- roofline itemization --
def test_cpu_roofline_items_are_measured_and_named():
    """The sub-0.7 itemization path (bench contract: a bare ratio is
    not evidence) must keep producing its named, measured terms — it
    only fires on sub-roofline boxes, so the bench JSON alone cannot
    guard it."""
    import jax.numpy as jnp

    import bench

    n = 1 << 14
    A = bench._banded_config(sparse, n, 11)
    x = jnp.full((n,), 1.0, dtype=jnp.float32)
    _ = A @ x      # warm structure caches
    items = bench._cpu_roofline_items(sparse, A, x, dt_ms=1.0,
                                      bw_ms=0.5, compute_ms=0.1)
    for key in ("measured_ms", "bound_bw_ms", "bound_compute_ms",
                "shifted_add_ms", "mask_ms", "pad_alloc_ms",
                "segment_sum_n", "segment_sum_ms",
                "shifted_add_seg_ms"):
        assert key in items, key
    assert items["segment_sum_ms"] > 0
    assert items["shifted_add_ms"] > 0
