# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Distributed graph algorithms as iterated semiring ``dist_spmv``.

Every algorithm here is the same program shape: build the *push
operator* (the transposed — or symmetrized — adjacency, so one
semiring SpMV advances information along edge direction), shard it
over the mesh, and iterate ``y = A_T (x)`` under the algorithm's
semiring with a host-side convergence loop that fetches exactly one
scalar per cycle (the solver modules' one-fetch-per-cycle cadence):

- :func:`bfs` — or-and frontier push; level = the sweep that first
  reaches a vertex;
- :func:`sssp` — Bellman-Ford min-plus relaxation;
- :func:`connected_components` — min-label propagation, which is
  min-plus over the zero-weighted symmetrized structure;
- :func:`pagerank` — damped plus-times power iteration on the
  column-normalized transpose, convergence checked every
  ``conv_test_iters`` iterations.

The push operator of :func:`bfs`, :func:`sssp` and
:func:`connected_components` is built once per graph and reused until
the graph is mutated: a package ``csr_array`` keeps the sharded
operator in its ``_graph_ops`` dict, keyed by every input of the build
(direction, weighting, mesh, resolved layout and the settings the
build reads), and every in-place mutator of the array clears it.  Any
other input (scipy, dense, another format) is converted into a fresh
array, so it is built on every call.  :func:`pagerank` builds its own
column-normalized operator on every call.

Multi-source BFS/SSSP batch their frontiers as one (rows, S) operand
through ``dist_spmm(..., semiring=)`` — the distributed arm of the
PR-8 stacked ``multi_matvec`` packing — so S sources cost one
collective schedule per sweep, not S.  (2-d-block layouts are
SpMV-only, so batched sources fall back to a per-source loop there.)

Counters: ``graph.<alg>.runs`` / ``graph.<alg>.iters`` plus the
``graph.dist_spmv.<semiring>`` family rows from the dispatch layer;
all under the ``graph.*`` prefix (docs/OBSERVABILITY.md).  Spans: a
``graph.<alg>`` span covers the whole call, ``graph.build`` the
operator build inside it (attribute ``cached``: True when the graph's
stored operator served the call, counted in ``graph.build.hits``; a
build counts in ``graph.build.misses``), ``graph.sweep`` each BFS
sweep; the edge fetch, each BFS sweep's scalar and the returned
levels count under ``transfer.host_sync.*``.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import jax.numpy as jnp

from .. import obs as _obs
from ..obs import latency as _latency


def _edge_arrays(csgraph, directed: bool, unweighted: bool):
    """Host edge list (rows, cols, w, n) via the csgraph boundary
    helper (stored zeros ARE edges; ``directed=False`` appends the
    reversed copies)."""
    from ..csgraph import _graph_edges

    rows, cols, w, n = _graph_edges(csgraph, directed, unweighted)
    # rows, cols and w come from the device: three blocking fetches.
    _obs.inc("transfer.host_sync.graph_edges", 3)
    return (np.asarray(rows, dtype=np.int64),
            np.asarray(cols, dtype=np.int64),
            np.asarray(w), n)


def _csr_from_edges(rows, cols, vals, n: int):
    """Package csr_array from a host edge list, deduplicated by
    (row, col) keeping the MINIMUM value — symmetrization can stage
    both stored copies of an undirected edge, and a duplicate must not
    sum (min/or algebra wants one representative; min is the right one
    for every caller here)."""
    from ..csr import csr_array

    key = rows * n + cols
    order = np.lexsort((vals, key))
    key, rows, cols, vals = (key[order], rows[order], cols[order],
                             vals[order])
    first = np.ones(key.shape[0], dtype=bool)
    first[1:] = key[1:] != key[:-1]
    rows, cols, vals = rows[first], cols[first], vals[first]
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    return csr_array((vals, cols.astype(np.int64), indptr),
                     shape=(n, n))


def _push_operator(csgraph, directed: bool, unweighted: bool,
                   zero_weights: bool = False):
    """The transposed traversal operator A_T as a package csr_array:
    ``y = A_T (x)`` under the algorithm semiring pushes x along edge
    direction (row u -> col v contributes x[u] to y[v]).
    ``zero_weights`` replaces every weight with 0 (the min-plus
    encoding of label propagation: min over neighbors' labels)."""
    rows, cols, w, n = _edge_arrays(csgraph, directed, unweighted)
    if zero_weights:
        # int32 zeros keep min-plus label propagation in integer
        # algebra end-to-end (no float round-trip for the labels).
        w = np.zeros(w.shape, dtype=np.int32)
    return _csr_from_edges(cols, rows, w, n), n


def _shard_operator(op, mesh, layout):
    from ..parallel import dist_csr as _dc

    return _dc.shard_csr(op, mesh=mesh, layout=layout)


def _build_key(directed: bool, unweighted: bool, zero_weights: bool,
               mesh, layout) -> tuple:
    """Every input of a build besides the graph itself: the arguments,
    the resolved layout, and the settings the edge weights and
    ``shard_csr`` read (value dtype, ELL budget, precise images, and
    the band budgets of its DIA blocks)."""
    from ..parallel.mesh import resolve_layout
    from ..runtime import runtime
    from ..settings import settings

    return (directed, unweighted, zero_weights, mesh,
            resolve_layout(layout), runtime.default_float,
            settings.ell_max_expand, settings.precise_images,
            settings.dia_max_expand, settings.dia_max_diags)


def _build(csgraph, directed: bool, unweighted: bool, mesh, layout,
           zero_weights: bool = False):
    """``(dA, n, dtype)``: the sharded push operator, its vertex count
    and its value dtype, under one ``graph.build`` span.  A package
    ``csr_array`` serves a stored operator under the same key; anything
    else, or a miss, builds it (edge fetch, host symmetrize and
    lexsort, CSR, shard upload) and stores it on a ``csr_array``."""
    from ..csr import csr_array

    cacheable = (isinstance(csgraph, csr_array)
                 and csr_array._can_build_cache(
                     csgraph._data, csgraph._indices, csgraph._indptr))
    key = _build_key(directed, unweighted, zero_weights, mesh, layout)
    built = (csgraph._graph_ops or {}).get(key) if cacheable else None
    with _obs.span("graph.build", cached=built is not None):
        if built is not None:
            _obs.inc("graph.build.hits")
            return built
        _obs.inc("graph.build.misses")
        op, n = _push_operator(csgraph, directed, unweighted,
                               zero_weights=zero_weights)
        built = (_shard_operator(op, mesh, layout), n, op.dtype)
        if cacheable:
            if csgraph._graph_ops is None:
                csgraph._graph_ops = {}
            csgraph._graph_ops[key] = built
        return built


def _shard_vec(v, dA):
    from ..parallel import dist_csr as _dc

    return _dc.shard_vector(jnp.asarray(v), dA.mesh, dA.rows_padded,
                            layout=dA.layout)


def _shard_mat(V, dA):
    from ..parallel import dist_csr as _dc

    return _dc.shard_dense(jnp.asarray(V), dA.mesh, dA.rows_padded)


def _max_iters(n: int, max_iters: Optional[int]) -> int:
    from ..settings import settings

    if max_iters is not None:
        return int(max_iters)
    cap = settings.graph_max_iters
    return int(cap) if cap > 0 else n + 1


def bfs(csgraph, source=0, *, directed: bool = True, mesh=None,
        layout=None, max_iters: Optional[int] = None):
    """Distributed BFS levels by or-and frontier push.

    Returns the int32 level array (hop distance from the source; -1
    unreachable): shape (n,) for a scalar ``source``, (S, n) for a
    sequence (batched frontiers — one ``dist_spmm`` sweep relaxes all
    S sources).  Each sweep fetches one scalar ("any new vertex?").
    Differential twin: ``scipy.sparse.csgraph.breadth_first_order`` /
    unweighted ``dijkstra`` (tests/test_graph.py).
    """
    from ..parallel import dist_csr as _dc

    sources = np.atleast_1d(np.asarray(source, dtype=np.int64))
    scalar = np.ndim(source) == 0
    with _latency.timer("lat.graph.bfs"), \
            _obs.span("graph.bfs", sources=int(sources.size)) as sp:
        dA, n, _ = _build(csgraph, directed, True, mesh, layout)
        if np.any((sources < 0) | (sources >= n)):
            raise ValueError(f"bfs: source out of range for n={n}")
        cap = _max_iters(n, max_iters)
        _obs.inc("graph.bfs.runs")
        if sp is not None:
            sp.set(n=n, layout=dA.layout)
        if sources.size == 1 or dA.grid is not None:
            outs = [_bfs_one(dA, int(s), n, cap) for s in sources]
            if sp is not None:
                sp.set(batched=False)
            return outs[0] if scalar else np.stack(outs)
        F0 = np.zeros((n, sources.size), dtype=bool)
        F0[sources, np.arange(sources.size)] = True
        L0 = np.full((n, sources.size), -1, dtype=np.int32)
        L0[sources, np.arange(sources.size)] = 0
        levels, it = _sweeps(
            lambda v: _dc.dist_spmm(dA, v, semiring="or-and"),
            _shard_mat(F0, dA), _shard_mat(L0, dA), cap)
        if sp is not None:
            sp.set(iters=it, batched=True)
        return _fetch_levels(levels, n).T


def _bfs_one(dA, s: int, n: int, cap: int) -> np.ndarray:
    from ..parallel import dist_csr as _dc

    f0 = np.zeros(n, dtype=bool)
    f0[s] = True
    l0 = np.full(n, -1, dtype=np.int32)
    l0[s] = 0
    levels, _ = _sweeps(
        lambda v: _dc.dist_spmv(dA, v, semiring="or-and"),
        _shard_vec(f0, dA), _shard_vec(l0, dA), cap)
    return _fetch_levels(levels, n)


def _sweeps(spmv, f, levels, cap: int):
    """``(levels, iters)``: frontier pushes from frontier ``f`` until a
    sweep finds no new vertex or ``cap`` sweeps ran.  Each loop turn is
    one ``graph.sweep`` span holding one semiring SpMV and its one
    counted scalar fetch."""
    visited = f
    it = 0
    while it < cap:
        with _obs.span("graph.sweep"):
            new = jnp.logical_and(spmv(f), jnp.logical_not(visited))
            _obs.inc("transfer.host_sync.bfs_sweep")
            if not bool(jnp.any(new)):
                break
            it += 1
            levels = jnp.where(new, jnp.int32(it), levels)
            visited = jnp.logical_or(visited, new)
            f = new
    _obs.inc("graph.bfs.iters", it)
    return levels, it


def _fetch_levels(levels, n: int) -> np.ndarray:
    _obs.inc("transfer.host_sync.bfs_levels")
    return np.asarray(levels)[:n]


def sssp(csgraph, source=0, *, directed: bool = True,
         unweighted: bool = False, mesh=None, layout=None,
         max_iters: Optional[int] = None):
    """Distributed single/multi-source shortest paths by Bellman-Ford
    min-plus relaxation (correct for negative edge weights; raises
    :class:`~..csgraph.NegativeCycleError` on a reachable negative
    cycle, matching the csgraph module).

    Returns float distances, inf unreachable: (n,) for a scalar
    source, (S, n) for a sequence (batched through the semiring
    ``dist_spmm`` on 1-d layouts).  Differential twin:
    ``scipy.sparse.csgraph.dijkstra`` on non-negative weights.
    """
    from ..csgraph import NegativeCycleError
    from ..parallel import dist_csr as _dc

    sources = np.atleast_1d(np.asarray(source, dtype=np.int64))
    scalar = np.ndim(source) == 0
    with _latency.timer("lat.graph.sssp"), \
            _obs.span("graph.sssp", sources=int(sources.size)) as sp:
        dA, n, fdt = _build(csgraph, directed, unweighted, mesh, layout)
        if np.any((sources < 0) | (sources >= n)):
            raise ValueError(f"sssp: source out of range for n={n}")
        # Bellman-Ford terminates in n-1 relaxations on cycle-free
        # distances; improvement at the n-th proves a negative cycle,
        # so the cap is the detector, not a budget.
        cap = n if max_iters is None else _max_iters(n, max_iters)
        _obs.inc("graph.sssp.runs")
        if sp is not None:
            sp.set(n=n, layout=dA.layout)
        batched = sources.size > 1 and dA.grid is None
        if batched:
            D0 = np.full((n, sources.size), np.inf, dtype=fdt)
            D0[sources, np.arange(sources.size)] = 0.0
            dist = _shard_mat(D0, dA)
            spmv = lambda v: _dc.dist_spmm(dA, v, semiring="min-plus")
        else:
            if sources.size > 1:
                outs = [sssp(csgraph, int(s), directed=directed,
                             unweighted=unweighted, mesh=mesh,
                             layout=layout, max_iters=max_iters)
                        for s in sources]
                return np.stack(outs)
            d0 = np.full(n, np.inf, dtype=fdt)
            d0[int(sources[0])] = 0.0
            dist = _shard_vec(d0, dA)
            spmv = lambda v: _dc.dist_spmv(dA, v, semiring="min-plus")
        it = 0
        while True:
            relaxed = spmv(dist)
            new = jnp.minimum(dist, relaxed)
            changed = bool(jnp.any(new < dist))
            if not changed:
                break
            it += 1
            dist = new
            if it >= cap:
                raise NegativeCycleError(
                    "sssp: still relaxing after n sweeps — "
                    "reachable negative cycle")
        _obs.inc("graph.sssp.iters", it)
        if sp is not None:
            sp.set(iters=it, batched=batched)
        out = np.asarray(dist)[:n]
        return out.T if batched else (out if scalar else out[None, :])


def connected_components(csgraph, *, mesh=None, layout=None,
                         max_iters: Optional[int] = None):
    """Distributed (weak) connected components by min-label
    propagation — min-plus SpMV over the ZERO-weighted symmetrized
    structure: ``min_j (0 + label[j])`` over neighbors j is exactly
    "adopt the smallest label you can see", iterated to fixpoint in
    O(diameter) sweeps.

    Returns ``(n_components, labels)`` with labels relabeled to
    0..n_components-1 in order of first appearance (scipy's
    convention; the differential test compares partitions up to
    relabeling anyway).
    """
    from ..parallel import dist_csr as _dc

    with _latency.timer("lat.graph.cc"), _obs.span("graph.cc") as sp:
        dA, n, _ = _build(csgraph, False, True, mesh, layout,
                          zero_weights=True)
        cap = _max_iters(n, max_iters)
        _obs.inc("graph.cc.runs")
        if sp is not None:
            sp.set(n=n, layout=dA.layout)
        labels = _shard_vec(np.arange(n, dtype=np.int32), dA)
        it = 0
        while it < cap:
            relaxed = _dc.dist_spmv(dA, labels, semiring="min-plus")
            new = jnp.minimum(labels, relaxed.astype(labels.dtype))
            if not bool(jnp.any(new < labels)):
                break
            it += 1
            labels = new
        _obs.inc("graph.cc.iters", it)
        if sp is not None:
            sp.set(iters=it)
        lab = np.asarray(labels)[:n]
    _, relabeled = np.unique(lab, return_inverse=True)
    return int(relabeled.max()) + 1 if n else 0, \
        relabeled.astype(np.int32)


def pagerank(csgraph, *, alpha: float = 0.85, tol: float = 1e-6,
             max_iters: int = 100,
             conv_test_iters: Optional[int] = None, mesh=None,
             layout=None):
    """Distributed PageRank by damped plus-times power iteration on
    the column-normalized transpose M (M[v, u] = 1/outdeg(u) per edge
    u -> v):

        r <- alpha * (M r + dangling_mass / n) + (1 - alpha) / n

    Dangling mass (rank held by zero-out-degree vertices) is a
    device-side dot against the dangling indicator — no extra fetch.
    Convergence (max |r_k - r_{k-cycle}|) is fetched once every
    ``conv_test_iters`` iterations (default
    ``LEGATE_SPARSE_TPU_GRAPH_CONV_ITERS``) — the solver modules'
    one-fetch-per-cycle cadence, which also makes the iteration count
    deterministic at cycle granularity for the bench golden.

    Returns the (n,) rank vector (sums to 1 over real vertices).
    Differential twin: a dense numpy power iteration of the same
    update (tests/test_graph.py).
    """
    from ..parallel import dist_csr as _dc
    from ..settings import settings

    with _latency.timer("lat.graph.pagerank"), \
            _obs.span("graph.pagerank") as sp:
        with _obs.span("graph.build"):
            rows, cols, w, n = _edge_arrays(csgraph, directed=True,
                                            unweighted=True)
            if n == 0:
                return np.zeros(0)
            # Dedupe (row, col) BEFORE the degree count:
            # ``_csr_from_edges`` keeps one representative per
            # coordinate, so a multigraph edge list (e.g. raw R-MAT
            # output) must not inflate outdeg or M's column sums drop
            # below 1 and rank mass leaks every iteration.
            uniq = np.unique(rows * n + cols)
            rows, cols = uniq // n, uniq % n
            outdeg = np.bincount(rows, minlength=n).astype(np.float64)
            inv_out = np.zeros(n)
            nz = outdeg > 0
            inv_out[nz] = 1.0 / outdeg[nz]
            M = _csr_from_edges(cols, rows, inv_out[rows], n)
            dM = _shard_operator(M, mesh, layout)
        fdt = M.dtype
        cycle = int(conv_test_iters or settings.graph_conv_iters)
        r = _shard_vec(np.full(n, 1.0 / n, dtype=fdt), dM)
        dang = _shard_vec((~nz).astype(fdt), dM)
        # Real-row mask: rows_padded > n tail rows must stay exactly 0
        # or the teleport term would leak rank mass into padding.
        mask = _shard_vec(np.ones(n, dtype=fdt), dM)
        inv_n = 1.0 / n
        _obs.inc("graph.pagerank.runs")
        if sp is not None:
            sp.set(n=n, layout=dM.layout)
        it = 0
        while it < max_iters:
            r_prev = r
            for _ in range(cycle):
                y = _dc.dist_spmv(dM, r)
                dm = jnp.vdot(dang, r)
                r = mask * (alpha * (y + dm * inv_n)
                            + (1.0 - alpha) * inv_n)
                it += 1
                if it >= max_iters:
                    break
            delta = float(jnp.max(jnp.abs(r - r_prev)))
            if delta < tol:
                break
        _obs.inc("graph.pagerank.iters", it)
        if sp is not None:
            sp.set(iters=it)
        return np.asarray(r)[:n]
