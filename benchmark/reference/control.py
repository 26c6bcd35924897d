"""Controls: the plain reference put in the program's place, one
precision below what the configuration states (bfloat16 for float32).
A comparison that these pass could not tell a sound run from one that
had cut its precision."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference import poisson


def _dot(u, v):
    return jnp.vdot(u, v, preferred_element_type=jnp.float32)


# The convergence test every 25 iterations, as the library's CG.
CHECK_EVERY = 25


@partial(jax.jit, static_argnames=("cfg_items", "maxiter"))
def _cg_bf16(b, rtol, cfg_items, maxiter: int):
    cfg = dict(cfg_items)
    dt = jnp.bfloat16
    b = b.astype(dt)

    def mv(v):
        return poisson.matvec(v, cfg, dt)

    atol2 = (rtol ** 2) * _dot(b, b)
    x = jnp.zeros_like(b)
    r = b
    p = r
    rho = _dot(r, r)

    def cond(s):
        x, r, p, rho, it, done = s
        return jnp.logical_and(it < maxiter, jnp.logical_not(done))

    def body(s):
        x, r, p, rho, it, done = s
        q = mv(p)
        alpha = rho / _dot(p, q)
        x = (x + (alpha * p).astype(dt)).astype(dt)
        r = (r - (alpha * q).astype(dt)).astype(dt)
        rho_new = _dot(r, r)
        p = (r + ((rho_new / rho) * p).astype(dt)).astype(dt)
        it = it + 1
        done = jnp.logical_and(it % CHECK_EVERY == 0, rho_new < atol2)
        return x, r, p, rho_new, it, done

    s = jax.lax.while_loop(cond, body, (x, r, p, rho, 0, False))
    return s[0].astype(jnp.float32), s[4]


def _items(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if k in ("nx", "ny", "xmin", "xmax", "ymin",
                                 "ymax")))


def cg_bf16(cfg: dict, b, rtol: float, maxiter: int):
    """Unpreconditioned CG on the Poisson stencil with bfloat16 vectors
    and coefficients (dot products accumulated in float32)."""
    return _cg_bf16(b, float(rtol), _items(cfg), int(maxiter))


@partial(jax.jit, static_argnames=("cfg_items",))
def _spmv_bf16(x, cfg_items):
    return poisson.matvec(x, dict(cfg_items), jnp.bfloat16).astype(
        jnp.float32)


def spmv_bf16(cfg: dict, x):
    """``A x`` on the Poisson stencil in bfloat16."""
    return _spmv_bf16(x, _items(cfg))
