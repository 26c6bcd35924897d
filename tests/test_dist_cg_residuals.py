# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""The two residual comparisons the four-chip Poisson benchmark cell
holds ``dist_cg`` to: the 2-norm and the max-norm of ``b - A x``
against a float64 scipy operator, under the limits of the cell's
configuration.  With the halo exchange dropped, the max-norm comparison
fails where the 2-norm one may still pass."""

import json
import os

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from legate_sparse_tpu import parallel

pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs four devices")

_CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs",
    "poisson2d-4096-x4.json")


def _limits():
    with open(_CONFIG) as f:
        return json.load(f)["limits"]


def _poisson(nx):
    """(dist operator over 4 shards, float64 scipy operator) of the
    5-point Dirichlet Poisson operator on (nx-2)^2 interior unknowns,
    the x-coupling zeroed across grid rows; dx = dy = 1 / (nx - 1)."""
    m = nx - 2
    n = m * m
    a = float(nx - 1) ** 2
    c = -4.0 * a

    def off1(i):
        return jnp.where((i + 1) % m == 0, 0.0, a)

    dA = parallel.dist_diags(
        [c, off1, off1, a, a], [0, 1, -1, m, -m], shape=(n, n),
        mesh=parallel.make_row_mesh(4), dtype=np.float32,
        materialize_ell=False)
    xc = np.full(n - 1, a)
    xc[m - 1::m] = 0.0
    A = sp.diags([np.full(n - m, a), xc, np.full(n, c), xc,
                  np.full(n - m, a)], [-m, -1, 0, 1, m], format="csr")
    return dA, A


def _residuals(A, b, x):
    """(||b - A x||_2 / ||b||_2, ||b - A x||_inf / ||b||_inf) in
    float64."""
    b = np.asarray(b, np.float64)
    r = b - A @ np.asarray(x, np.float64)
    return (float(np.linalg.norm(r) / np.linalg.norm(b)),
            float(np.abs(r).max() / np.abs(b).max()))


def _rhs(n, seed):
    return np.random.default_rng(seed).standard_normal(n).astype(
        np.float32)


def test_dist_cg_meets_both_residual_limits():
    lim = _limits()
    dA, A = _poisson(34)
    for seed in (3, 4):
        b = _rhs(A.shape[0], seed)
        x, _ = parallel.dist_cg(dA, b, rtol=1e-5, maxiter=40000)
        two, inf = _residuals(A, b, x)
        assert two <= lim["true_residual_max"], (seed, two)
        assert inf <= lim["residual_inf_max"], (seed, inf)


def test_dropped_halo_fails_the_max_norm(monkeypatch):
    """Every halo ``ppermute`` answering zeros: each shard's rows miss
    their neighbours' coupling.  The max-norm residual sees it; the
    2-norm one, averaged over the rows no exchange touches, may not.
    The grid is one no other test traces, and the caches are cleared
    around the patch, so no compiled program keeps the real exchange
    or takes the dropped one."""
    lim = _limits()
    dA, A = _poisson(70)
    b = _rhs(A.shape[0], 5)
    jax.clear_caches()
    monkeypatch.setattr(jax.lax, "ppermute",
                        lambda x, axis_name, perm: jnp.zeros_like(x))
    try:
        x, _ = parallel.dist_cg(dA, b, rtol=1e-5, maxiter=40000)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    two, inf = _residuals(A, b, x)
    assert inf > lim["residual_inf_max"], (two, inf)
    assert two < inf
