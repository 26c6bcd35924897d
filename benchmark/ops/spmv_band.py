"""``"op": "spmv_band"``: the reference SpMV microbenchmark's loop on
its own operator, a band of consecutive diagonals whose CSR is built
directly from ``(data, indices, indptr)`` as ``examples/common.py``
``banded_matrix`` builds it; eager ``A @ x`` with x resident on the
device, in batches that each end in ``block_until_ready``.

As ``ops/spmv.py``: a batch makes ``calls`` calls over as many
distinct resident vectors, and the check compares a seeded sample of
outputs (from early batches and from the last one) with the
benchmark's own float64 scipy product."""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

import jax

from benchmark.ops import common, spmv
from benchmark.reference import banded, checks


def banded_csr(cfg: dict, vals: np.ndarray):
    """The operator through ``csr_array((data, indices, indptr))``:
    numpy column arithmetic, int32 indices, int64 indptr."""
    import legate_sparse_tpu as sparse

    n, offsets = int(cfg["rows"]), cfg["offsets"]
    nd = len(offsets)
    cols = (np.tile(np.asarray(offsets, np.int32), n)
            + np.repeat(np.arange(n, dtype=np.int32), nd))
    keep = (cols >= 0) & (cols < n)
    counts = keep.reshape(n, nd).sum(axis=1)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    data = vals.T.ravel()[keep]
    return sparse.csr_array((data, cols[keep], indptr), shape=(n, n))


def setup(run) -> None:
    cfg, tr = run.cell.config, run.cell.traffic
    vals = banded.values(cfg, run.seed)
    A = banded_csr(cfg, vals)
    n = A.shape[0]
    calls = int(tr["calls"])
    rng = run.rng(3)
    early = {(int(b), int(rng.integers(calls)))
             for b in rng.choice(tr["early_batches"], size=tr["early_checked"],
                                 replace=False)}
    run.state = SimpleNamespace(
        A=A, vals=vals, n=n, nnz=int(A.nnz),
        itemsize=np.dtype(cfg["dtype"]).itemsize,
        D=jax.device_put(vals) if run.control else None,
        calls=calls,
        X=common.vectors(common.key(run, 0), calls, n, cfg["dtype"],
                         tr["x"]),
        early=early, last_rng=run.rng(4), kept=[], last=None)
    step(run, -1)
    run.state.kept, run.state.last = [], None


def matvec(run, x):
    """One call of the timed path (the control's in its place)."""
    if run.control:
        return banded.spmv_bf16(run.cell.config, run.state.D, x)
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


finish = spmv.finish


def release(run) -> None:
    run.state.A = run.state.D = None


def check(run) -> list:
    cfg = run.cell.config
    st = run.state
    A_ref = banded.scipy_operator(cfg, st.vals)
    worst = max(checks.rel_err(y, A_ref @ np.asarray(st.X[k], np.float64))
                for k, y in st.outputs)
    return [("max_rel_err", worst, cfg["limits"]["max_rel_err"])]
