"""What the op modules share: the operator built through the library's
public constructors, vectors drawn on the device from the seed, and a
seeded sample of the window's records."""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import harness
from benchmark.reference import poisson

#: Index of the warm-up operation's input, apart from the window's.
WARM = 1 << 30


def key(run, stream: int):
    return jax.random.fold_in(jax.random.key(harness.jax_seed(run.seed)),
                              stream)


def poisson_csr(cfg: dict):
    """The operator as ``examples/pde.py`` builds it: ``diags`` of the
    five diagonals, CSR out."""
    import legate_sparse_tpu as sparse

    dtype = np.dtype(cfg["dtype"])
    diags, offsets, n = poisson.diagonals(cfg, dtype)
    return sparse.diags(diags, offsets, shape=(n, n), format="csr",
                        dtype=dtype)


def _draw(k, i, n: int, dtype, dist: str):
    k = jax.random.fold_in(k, i)
    if dist == "standard_normal":
        return jax.random.normal(k, (n,), dtype)
    if dist == "uniform":
        return jax.random.uniform(k, (n,), dtype)
    raise ValueError(f"unknown distribution {dist!r}")


def vector_maker(n: int, dtype, dist: str, sharding=None):
    """A compiled ``f(key, i)`` that draws the ``i``-th vector."""
    return jax.jit(partial(_draw, n=n, dtype=jnp.dtype(dtype), dist=dist),
                   out_shardings=sharding)


def vectors(k, count: int, n: int, dtype, dist: str) -> list:
    """``count`` vectors drawn in one compiled call."""
    def many(k):
        return tuple(_draw(k, j, n, jnp.dtype(dtype), dist)
                     for j in range(count))

    return list(jax.jit(many)(k))


def sample(records: list, rng, k: int) -> list:
    """Up to ``k`` of the records, drawn from ``rng``; the longest
    operation is always among them."""
    if len(records) <= k:
        return list(records)
    longest = max(range(len(records)),
                  key=lambda j: records[j]["t1"] - records[j]["t0"])
    rest = [j for j in range(len(records)) if j != longest]
    pick = rng.choice(rest, size=k - 1, replace=False)
    return [records[j] for j in sorted([longest, *pick])]
