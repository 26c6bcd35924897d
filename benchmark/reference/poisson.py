"""The 2-D Poisson operator of nv-legate/legate-sparse
``examples/pde.py``: the centered second-order Laplacian with Dirichlet
boundaries on the (nx-2)(ny-2) interior unknowns, five diagonals, the
x-coupling zeroed where consecutive unknowns cross a grid row."""

from __future__ import annotations

import numpy as np


def coefficients(cfg: dict) -> tuple:
    """(a, g, c, m, n): x- and y-coupling, centre, grid row length and
    unknowns, from ``nx``, ``ny`` and the domain of the configuration."""
    nx, ny = int(cfg["nx"]), int(cfg["ny"])
    dx = (cfg["xmax"] - cfg["xmin"]) / (nx - 1)
    dy = (cfg["ymax"] - cfg["ymin"]) / (ny - 1)
    a = 1.0 / dx ** 2
    g = 1.0 / dy ** 2
    c = -2.0 * a - 2.0 * g
    m = nx - 2
    return a, g, c, m, m * (ny - 2)


def diagonals(cfg: dict, dtype=np.float64) -> tuple:
    """(diagonals, offsets, n) in scipy ``diags`` form, as
    ``d2_mat_dirichlet_2d`` of ``examples/pde.py`` builds them."""
    a, g, c, m, n = coefficients(cfg)
    diag_a = np.full(n - 1, a, dtype=dtype)
    diag_a[m - 1::m] = 0.0
    diag_g = np.full(n - m, g, dtype=dtype)
    diag_c = np.full(n, c, dtype=dtype)
    return ([diag_g, diag_a, diag_c, diag_a, diag_g],
            [-m, -1, 0, 1, m], n)


def scipy_operator(cfg: dict):
    """The operator in float64 CSR, built by scipy alone (explicit
    zeros dropped, as ``dia -> csr`` drops them)."""
    import scipy.sparse as sp

    diags, offsets, n = diagonals(cfg)
    A = sp.diags(diags, offsets, shape=(n, n), format="csr")
    A.eliminate_zeros()
    return A


def nnz(cfg: dict) -> int:
    """Stored values: 5n less one x-coupling per grid row on each side
    (the row's end, or the band's end) and the m y-couplings missing
    at each edge of the grid."""
    a, g, c, m, n = coefficients(cfg)
    return 5 * n - 2 * (n // m) - 2 * m


def matvec(x, cfg: dict, dtype):
    """``A x`` as a plain 5-point stencil on a flat vector, in
    ``dtype`` (jax.numpy)."""
    import jax.numpy as jnp

    a, g, c, m, n = coefficients(cfg)
    x = x.astype(dtype)
    zero1 = jnp.zeros((1,), dtype)
    zerom = jnp.zeros((m,), dtype)
    col = jnp.arange(n) % m
    left = jnp.where(col == 0, 0, jnp.concatenate([zero1, x[:-1]]))
    right = jnp.where(col == m - 1, 0, jnp.concatenate([x[1:], zero1]))
    down = jnp.concatenate([zerom, x[:-m]])
    up = jnp.concatenate([x[m:], zerom])
    return (jnp.asarray(c, dtype) * x
            + jnp.asarray(a, dtype) * (left + right)
            + jnp.asarray(g, dtype) * (down + up)).astype(dtype)
