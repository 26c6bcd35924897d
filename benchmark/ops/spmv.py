"""``"op": "spmv"``: the reference SpMV microbenchmark's loop, eager
``A @ x`` with x resident on the device, in batches that each end in
``block_until_ready``.

A batch makes ``calls`` calls over as many distinct resident vectors,
so that every call's output can be checked.  The check compares a
seeded sample of outputs (from early batches and from the last one)
with the benchmark's own float64 scipy product."""

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
    calls = int(tr["calls"])
    rng = run.rng(3)
    early = {(int(b), int(rng.integers(calls)))
             for b in rng.choice(tr["early_batches"], size=tr["early_checked"],
                                 replace=False)}
    run.state = SimpleNamespace(
        A=A, n=n, nnz=int(A.nnz), itemsize=np.dtype(cfg["dtype"]).itemsize,
        calls=calls, key=common.key(run, 0),
        X=common.vectors(common.key(run, 0), calls, n, cfg["dtype"],
                         tr["x"]),
        early=early, last_rng=run.rng(4), kept=[], last=None)
    step(run, -1)
    run.state.kept, run.state.last = [], None


def matvec(run, x):
    """One call of the timed path (the control's in its place)."""
    if run.control:
        return control.spmv_bf16(run.cell.config, x)
    return run.state.A @ x


def step(run, i: int) -> dict:
    st = run.state
    ys = []
    dispatch = 0.0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("batch"):
        for x in st.X:
            a = time.perf_counter()
            ys.append(matvec(run, x))
            dispatch += time.perf_counter() - a
        jax.block_until_ready(ys)
    t1 = time.perf_counter()
    st.kept.extend((i, k, ys[k]) for b, k in st.early if b == i)
    k = int(st.last_rng.integers(st.calls))
    st.last = (i, k, ys[k])
    return {"t0": t0, "t1": t1, "i": i, "calls": st.calls,
            "dispatch_s": dispatch}


def finish(run) -> None:
    st = run.state
    st.kept.append(st.last)
    st.outputs = [(k, np.asarray(y)) for _, k, y in st.kept]
    st.kept, st.last = [], None


def release(run) -> None:
    run.state.A = None


def check(run) -> list:
    cfg = run.cell.config
    st = run.state
    A_ref = poisson.scipy_operator(cfg)
    worst = max(checks.rel_err(y, A_ref @ np.asarray(st.X[k], np.float64))
                for k, y in st.outputs)
    return [("max_rel_err", worst, cfg["limits"]["max_rel_err"])]
