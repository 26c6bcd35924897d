"""``"op": "cg"``: repeated ``legate_sparse_tpu.linalg.cg(A, b_i)`` to
the configuration's ``rtol``, one caller, back to back.

Each solve gets a fresh right-hand side drawn on the device from the
seed, and ends when its solution is on the host.  The check computes
each sampled solution's true residual in float64 with the benchmark's
own scipy operator."""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

import jax

from benchmark.ops import common
from benchmark.reference import checks, control, poisson


def setup(run) -> None:
    cfg, tr = run.cell.config, run.cell.traffic
    A = common.poisson_csr(cfg)
    n = A.shape[0]
    run.state = SimpleNamespace(
        A=A, n=n, nnz=int(A.nnz), itemsize=np.dtype(cfg["dtype"]).itemsize,
        key=common.key(run, 0),
        rhs=common.vector_maker(n, cfg["dtype"], tr["rhs"]))
    step(run, common.WARM)


def solve(run, b):
    """One solve of the timed path (the control's in its place)."""
    from legate_sparse_tpu import linalg

    cfg, tr = run.cell.config, run.cell.traffic
    if run.control:
        return control.cg_bf16(cfg, b, cfg["rtol"], tr["maxiter"])
    return linalg.cg(run.state.A, b, rtol=cfg["rtol"],
                     maxiter=tr["maxiter"])


def step(run, i: int) -> dict:
    return timed_solve(run, i, solve)


def timed_solve(run, i: int, solve_fn) -> dict:
    """Draw the ``i``-th right-hand side, then solve; the solve is
    timed until its solution is on the host."""
    st = run.state
    with jax.profiler.TraceAnnotation("rhs"):
        b = st.rhs(st.key, i)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("solve"):
        x, iters = solve_fn(run, b)
        x = np.asarray(x)
        iters = int(iters)
    return {"t0": t0, "t1": time.perf_counter(), "i": i, "iters": iters,
            "x": x}


def finish(run) -> None:
    pass


def release(run) -> None:
    run.state.A = None


def check(run) -> list:
    cfg, tr = run.cell.config, run.cell.traffic
    st = run.state
    A_ref = poisson.scipy_operator(cfg)
    picked = common.sample(run.records, run.rng(7), tr["checked"])
    worst = max(checks.true_residual(
        A_ref, np.asarray(st.rhs(st.key, r["i"])), r["x"]) for r in picked)
    return [("true_residual_max", worst,
             cfg["limits"]["true_residual_max"])]
