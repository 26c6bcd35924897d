# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Honest device timing for detached-dispatch backends.

On a detached-dispatch backend ``jax.block_until_ready`` can return as
soon as the dispatch is acknowledged — NOT when the device finishes —
so classic warmup + block timing reports fantasy numbers (measured on
an earlier remote setup: a 200 MB triad "finishing" in 25 us, 10x the
chip's HBM bandwidth).  The only sync trusted everywhere is a host
fetch of a result scalar, whose round trip can cost tens of ms, so
per-op timing is useless there too.

The methodology here: run the op chained inside ONE jitted
``lax.fori_loop`` at two different trip counts, fetch a scalar from
each result (true sync), and divide the time difference by the trip
count difference.  Fixed costs (dispatch RPC, fetch RPC, compile-cache
lookup) cancel; what remains is true device time per iteration.

Chaining (each iteration consumes the previous result) also defeats
any result caching / elision across iterations.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Callable

import jax


def triad_gbs(log2_lanes: int = 26, k_lo: int = 3,
              k_hi: int = 18) -> float:
    """One measured STREAM-triad bandwidth sample (GB/s): x' = a*x + y
    over ``2**log2_lanes`` f32 lanes (the default 2^26 = 512 MB working
    set cannot hide in VMEM/LLC).  Callers wanting a *trustworthy*
    denominator should take several samples interleaved with their
    workload phases and use the median — on shared CPU boxes single
    samples vary run-to-run by 25%+ (BENCH_r05's 66 vs 29 GB/s pair),
    and a wild denominator poisons every roofline fraction computed
    from it."""
    import jax.numpy as jnp

    n = 1 << log2_lanes
    x = jnp.ones((n,), dtype=jnp.float32)
    y = jnp.full((n,), 1e-9, dtype=jnp.float32)
    ms = loop_ms_per_iter(lambda v: 1.0000001 * v + y, x,
                          k_lo=k_lo, k_hi=k_hi)
    return 3 * 4 * n / (ms * 1e-3) / 1e9


def fixed_cost_s(x0, repeats: int = 3) -> float:
    """Measured fixed cost of one dispatch + scalar-fetch round trip
    (the constant both ends of the two-point measurement share):
    microseconds on a local backend, up to ~1 s on a remote one."""
    import jax.numpy as jnp

    @jax.jit
    def probe(x):
        return jnp.ravel(x)[0] * 1.0

    float(probe(x0))  # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        float(probe(x0))
        best = min(best, time.perf_counter() - t0)
    return best


def loop_ms_per_iter(step: Callable, x0, k_lo: int = 5, k_hi: int = None,
                     repeats: int = 2, deadline_s: float = None,
                     k_cap: int = 4000) -> float:
    """True device ms per ``step`` application (see module docstring).

    ``step``: jax-traceable x -> x (magnitude-preserving so hundreds of
    chained applications neither overflow nor denormalize).

    Every distinct trip count is a separate XLA compile — seconds to
    tens of seconds at large shapes — so beyond
    the caller's first guess the trip counts are chosen from MEASURED
    cost estimates instead of blind x4 escalation: normally at most
    three loop compiles run (plus one trivial fixed-cost probe).
    ``k_hi`` is the first high trial (caller's domain knowledge; None
    picks it from the fixed-cost estimate); ``k_cap`` bounds every
    trip count (pass a small cap to bound total work on a kernel that
    might fault the worker); ``deadline_s`` (wall clock for this call)
    stops escalation early.
    """
    import jax.numpy as jnp

    t_start = time.perf_counter()

    @partial(jax.jit, static_argnames=("k",))
    def loop(x, k: int):
        out = jax.lax.fori_loop(0, k, lambda i, v: step(v), x)
        return jnp.ravel(out)[0]

    def timed(k: int) -> float:
        float(loop(x0, k))  # compile + warm
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            float(loop(x0, k))
            best = min(best, time.perf_counter() - t0)
        return best

    def left() -> float:
        if deadline_s is None:
            return float("inf")
        return deadline_s - (time.perf_counter() - t_start)

    fixed = fixed_cost_s(x0)
    t_lo = timed(k_lo)
    # Delta target sized so the loop-body difference dominates
    # fixed-cost jitter; per-iter upper bound from the low point alone.
    per_iter_est = max(t_lo - fixed, 0.25 * t_lo) / k_lo
    delta_target = max(4.0 * fixed, 0.4, 0.5 * t_lo)
    if k_hi is None:
        k_hi = k_lo + int(delta_target / max(per_iter_est, 1e-9)) + 1
    k_hi = min(k_cap, max(3 * k_lo, k_hi))
    while True:
        t_hi = timed(k_hi)
        good = t_hi >= t_lo + max(2.0 * fixed, 0.2 * t_lo)
        if good or k_hi >= k_cap:
            break
        if left() < 3 * t_hi + 30:
            # Not enough wall budget for another compile+run cycle:
            # use what we have if it resolves at all, else fail loudly.
            break
        # Re-aim from the measured points (one jump, not x4 blind).
        per_iter = ((t_hi - t_lo) / (k_hi - k_lo)
                    if t_hi > t_lo else per_iter_est / 8)
        k_next = k_lo + int(delta_target / max(per_iter, 1e-9)) + 1
        k_hi = min(k_cap, max(k_next, 2 * k_hi))
    if not good:
        # t_hi <= t_lo, or above it by less than the noise floor: a
        # silent clamp (or a noise-dominated slope) would report fantasy
        # bandwidth in the driver-contract JSON; fail loudly instead
        # (callers guard each phase and record the error).
        raise RuntimeError(
            f"unresolvable timing: {k_hi} iters ({t_hi:.4f}s) not "
            f"measurably slower than {k_lo} ({t_lo:.4f}s; "
            f"noise floor {max(2.0 * fixed, 0.2 * t_lo):.4f}s)"
        )
    return (t_hi - t_lo) / (k_hi - k_lo) * 1e3
