"""``"op": "bfs"``: Graph500 kernel 2, one
``legate_sparse_tpu.graph.bfs(G, root)`` call per search, one root at a
time.

The edge tuples are made on the device by the benchmark's copy of the
Graph500 Kronecker generator, from the configuration's ``graph_seed``
(one graph, searched from many roots), and handed to the public
``csr_array`` COO constructor.  The run's seed draws the roots among
the vertices with an edge other than a self-loop.  The check compares
every search's levels with ``scipy.sparse.csgraph`` on the same
tuples."""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

import jax
import jax.numpy as jnp

from benchmark.ops import common
from benchmark.reference import checks, kronecker


@jax.jit
def _degrees(rows, cols, n_like):
    n = n_like.shape[0]
    loop = rows == cols
    w = jnp.where(loop, 0, 1).astype(jnp.int32)
    return (jnp.zeros(n, jnp.int32).at[rows].add(w).at[cols].add(w))


def setup(run) -> None:
    import legate_sparse_tpu as sparse

    cfg, tr = run.cell.config, run.cell.traffic
    rows, cols = kronecker.edges_for(
        cfg, jax.random.key(int(cfg["graph_seed"])))
    n = 1 << int(cfg["scale"])
    G = sparse.csr_array(
        (jnp.ones(rows.shape, jnp.dtype(cfg["dtype"])), (rows, cols)),
        shape=(n, n))
    deg = np.asarray(_degrees(rows, cols, jnp.zeros(n, jnp.int8)))
    roots = run.rng(5).choice(np.flatnonzero(deg > 0),
                              size=int(tr["roots"]) + 1, replace=False)
    run.state = SimpleNamespace(G=G, n=n, rows=rows, cols=cols,
                                roots=roots[1:], ref=None)
    if run.control:
        run.state.ref = checks.undirected_graph(np.asarray(rows),
                                                np.asarray(cols), n)
    _search(run, int(roots[0]))


def _search(run, root: int) -> np.ndarray:
    """One search of the timed path; the control stops it one level
    short of the reference's depth."""
    from legate_sparse_tpu import graph

    st = run.state
    cap = None
    if run.control:
        cap = max(int(checks.bfs_levels(st.ref, root).max()) - 1, 0)
    return np.asarray(graph.bfs(st.G, root, directed=False,
                                max_iters=cap))


def step(run, i: int) -> dict:
    st = run.state
    root = int(st.roots[i % len(st.roots)])
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("search"):
        levels = _search(run, root)
    return {"t0": t0, "t1": time.perf_counter(), "i": i, "root": root,
            "levels": levels}


def finish(run) -> None:
    st = run.state
    st.rows_h = np.asarray(st.rows)
    st.cols_h = np.asarray(st.cols)
    for r in run.records:
        r["edges"] = checks.edges_in_component(r["levels"], st.rows_h)


def release(run) -> None:
    st = run.state
    st.G = st.rows = st.cols = None


def check(run) -> list:
    st = run.state
    G = checks.undirected_graph(st.rows_h, st.cols_h, st.n)
    bad = sum(checks.level_mismatches(r["levels"],
                                      checks.bfs_levels(G, r["root"]))
              for r in run.records)
    return [("level_mismatches", bad,
             run.cell.config["limits"]["level_mismatches"])]
