"""The Graph500 Kronecker edge generator (the specification's
``kronecker_generator.m``), on the device: ``edgefactor * 2**scale``
edge tuples by ``scale`` levels of quadrant descent with initiator
probabilities A, B, C (D = 1 - A - B - C), then a random relabelling of
the vertices and a random order of the tuples."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("scale", "edgefactor", "A", "B", "C"))
def edges(key, scale: int, edgefactor: int, A: float, B: float,
          C: float):
    """(rows, cols) int32 device arrays of the tuples (u, v)."""
    n = 1 << scale
    m = edgefactor * n
    ab = A + B
    c_norm = C / (1.0 - ab)
    a_norm = A / ab
    keys = jax.random.split(key, 2 * scale + 2)
    rows = jnp.zeros((m,), jnp.int32)
    cols = jnp.zeros((m,), jnp.int32)
    for ib in range(scale):
        ii_bit = jax.random.uniform(keys[2 * ib], (m,)) > ab
        jj_bit = jax.random.uniform(keys[2 * ib + 1], (m,)) > jnp.where(
            ii_bit, c_norm, a_norm)
        rows = rows + (ii_bit.astype(jnp.int32) << ib)
        cols = cols + (jj_bit.astype(jnp.int32) << ib)
    perm = jax.random.permutation(keys[-2], n).astype(jnp.int32)
    order = jax.random.permutation(keys[-1], m)
    return perm[rows][order], perm[cols][order]


def edges_for(cfg: dict, key):
    return edges(key, int(cfg["scale"]), int(cfg["edgefactor"]),
                 float(cfg["A"]), float(cfg["B"]), float(cfg["C"]))
