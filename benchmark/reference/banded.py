"""The banded operator of nv-legate/legate-sparse
``examples/spmv_microbenchmark.py``: consecutive diagonals centred on
the main one, every in-range slot stored (``examples/common.py``
``banded_matrix``), with values drawn from the run's seed in place of
the example's ones."""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

#: The seed's stream for the operator's values (``Run.rng``'s form).
VALUES_STREAM = 5


def values(cfg: dict, seed: int) -> np.ndarray:
    """(diagonals, rows) float32 with ``vals[d, i] = A[i, i + off_d]``,
    uniform on [0.5, 1.5), and +0.0 where ``i + off_d`` falls outside
    the matrix."""
    n, offsets = int(cfg["rows"]), cfg["offsets"]
    rng = np.random.default_rng([int(seed), VALUES_STREAM])
    vals = rng.random((len(offsets), n), dtype=np.float32)
    vals += np.float32(0.5)
    for d, off in enumerate(offsets):
        if off > 0:
            vals[d, n - off:] = 0.0
        elif off < 0:
            vals[d, :-off] = 0.0
    return vals


def nnz(cfg: dict) -> int:
    """Stored values: every in-range slot of every diagonal."""
    return sum(int(cfg["rows"]) - abs(o) for o in cfg["offsets"])


def scipy_operator(cfg: dict, vals: np.ndarray):
    """The operator in float64, built by ``scipy.sparse.diags`` from
    the per-diagonal values (DIA storage, whose product scipy computes
    diagonal by diagonal)."""
    import scipy.sparse as sp

    n, offsets = int(cfg["rows"]), cfg["offsets"]
    diags = [vals[d, max(0, -k): n - max(0, k)].astype(np.float64)
             for d, k in enumerate(offsets)]
    return sp.diags(diags, offsets, shape=(n, n), format="dia")


def matvec(x, vals, offsets: tuple, dtype):
    """``A x`` as a sum of shifted, zero-filled slices of x, each times
    its diagonal's values, in ``dtype`` (jax.numpy)."""
    x = x.astype(dtype)
    y = jnp.zeros(x.shape, dtype)
    for d, off in enumerate(offsets):
        pad = jnp.zeros((abs(off),), dtype)
        xs = (jnp.concatenate([x[off:], pad]) if off >= 0
              else jnp.concatenate([pad, x[:off]]))
        y = y + vals[d].astype(dtype) * xs
    return y


@partial(jax.jit, static_argnames=("offsets",))
def _spmv_bf16(x, vals, offsets):
    return matvec(x, vals, offsets, jnp.bfloat16).astype(jnp.float32)


def spmv_bf16(cfg: dict, vals, x):
    """The control: ``A x`` in bfloat16, values and x rounded to it."""
    return _spmv_bf16(x, vals, tuple(cfg["offsets"]))
