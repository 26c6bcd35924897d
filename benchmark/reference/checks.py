"""The comparisons that decide ``correct``: numpy and scipy on the
host, in float64, on data the benchmark made itself."""

from __future__ import annotations

import numpy as np


def true_residual(A_ref, b, x) -> float:
    """||b - A x|| / ||b|| in float64."""
    b = np.asarray(b, dtype=np.float64)
    x = np.asarray(x, dtype=np.float64)
    if x.shape != b.shape or not np.isfinite(x).all():
        return float("inf")
    return float(np.linalg.norm(b - A_ref @ x) / np.linalg.norm(b))


def rel_err(y, ref) -> float:
    """max |y - ref| / max |ref|, in float64."""
    y = np.asarray(y, dtype=np.float64)
    ref = np.asarray(ref, dtype=np.float64)
    if y.shape != ref.shape or not np.isfinite(y).all():
        return float("inf")
    return float(np.abs(y - ref).max() / max(np.abs(ref).max(), 1e-300))


def undirected_graph(rows, cols, n: int):
    """scipy CSR of the undirected graph of the edge tuples: both
    orientations of every tuple, repeats merged."""
    import scipy.sparse as sp

    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    r = np.concatenate([rows, cols])
    c = np.concatenate([cols, rows])
    G = sp.csr_matrix((np.ones(r.size, dtype=np.int8), (r, c)),
                      shape=(n, n))
    G.sum_duplicates()
    return G


def bfs_levels(G, root: int) -> np.ndarray:
    """Hop distance from ``root`` (-1 where unreached), from
    ``scipy.sparse.csgraph.breadth_first_order``'s tree."""
    from scipy.sparse import csgraph

    order, pred = csgraph.breadth_first_order(
        G, int(root), directed=True, return_predecessors=True)
    lv = np.full(G.shape[0], -1, dtype=np.int64)
    lv[root] = 0
    v, p = order[1:], pred[order[1:]]
    todo = np.ones(v.size, dtype=bool)
    d = 0
    while todo.any():
        d += 1
        hit = todo & (lv[p] == d - 1)
        lv[v[hit]] = d
        todo &= ~hit
    return lv


def level_mismatches(levels, ref) -> int:
    levels = np.asarray(levels).astype(np.int64)
    if levels.shape != ref.shape:
        return int(ref.size)
    return int((levels != ref).sum())


def edges_in_component(levels, rows) -> int:
    """Graph500's count for TEPS: the input edge tuples (u, v) whose u
    the search reached (then v was reached too), self-loops and repeats
    included."""
    return int(np.count_nonzero(np.asarray(levels)[rows] >= 0))
