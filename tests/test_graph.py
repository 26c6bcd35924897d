# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Graph subsystem differential suite (legate_sparse_tpu.graph).

Runs on the virtual 8-device CPU mesh (conftest).  Distributed
BFS / SSSP / connected-components / PageRank are checked against their
scipy.sparse.csgraph twins (PageRank against a dense numpy power
iteration) on BOTH distributed layouts, and the comm ledger deltas are
compared against the static ``semiring_spmv_comm_volumes`` prediction.
The plus-times semiring kernels are pinned bitwise against their
specialized siblings — the autotuner races them under that pair, so
the verdicts must transfer.
"""

import numpy as np
import pytest
import scipy.sparse as sp
import scipy.sparse.csgraph as scsg

import jax
import jax.numpy as jnp

import legate_sparse_tpu as sparse
from legate_sparse_tpu import graph, obs
from legate_sparse_tpu.graph import (
    MIN_PLUS, OR_AND, PLUS_TIMES, SEMIRINGS, resolve,
)
from legate_sparse_tpu.obs import counters, trace
from legate_sparse_tpu.ops import spmv as spv
from legate_sparse_tpu.parallel import shard_csr
from legate_sparse_tpu.parallel.dist_csr import (
    dist_spmv, semiring_spmv_comm_volumes, shard_vector,
)

R = len(jax.devices())
needs_mesh = pytest.mark.skipif(R < 2, reason="needs a multi-device mesh")

LAYOUTS = ("1d-row", "2d-block")


@pytest.fixture(autouse=True)
def _obs_isolation():
    was = trace.enabled()
    obs.reset_all()
    trace.disable()
    yield
    obs.reset_all()
    if was:
        trace.enable()


def _graph_csr(n=64, density=0.06, seed=0):
    rng = np.random.default_rng(seed)
    S = sp.random(n, n, density=density, random_state=rng)
    S.data[:] = rng.uniform(0.5, 2.0, S.data.shape)
    return S.tocsr()


# ----------------------------------------------------------- catalog --
def test_semiring_catalog():
    assert set(SEMIRINGS) == {"plus-times", "min-plus", "max-times",
                              "or-and"}
    assert resolve("min-plus") is MIN_PLUS
    assert resolve(OR_AND) is OR_AND
    with pytest.raises(ValueError, match="plus-times"):
        resolve("tropical")
    f32 = np.dtype(np.float32)
    assert PLUS_TIMES.identity(f32) == 0.0
    assert MIN_PLUS.identity(f32) == np.inf
    assert SEMIRINGS["max-times"].identity(f32) == -np.inf
    assert bool(OR_AND.identity(np.dtype(bool))) is False
    # additive identity == multiplicative annihilator, all entries
    for sr in SEMIRINGS.values():
        assert sr.annihilator(f32) == sr.identity(f32)


# ----------------------------------------------- kernels (1 device) --
def test_semiring_kernels_plus_times_bitwise():
    # Under plus-times every semiring kernel must be bit-identical to
    # its specialized sibling — the autotuner transfers its verdicts
    # on that basis (autotune/registry.py).
    A = sparse.csr_array(_graph_csr(96, 0.08, 3))
    x = jnp.asarray(np.random.default_rng(1).uniform(0, 1, 96).astype(
        np.asarray(A.data).dtype))
    rid = A._get_row_ids()
    nnz = jnp.asarray(A.data.shape[0], dtype=jnp.int32)
    ref = spv.csr_spmv_rowids(A.data, A.indices, rid, x, A.shape[0])
    got = spv.csr_semiring_spmv_rowids_masked(
        A.data, A.indices, rid, nnz, x, A.shape[0], "sum", "times")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    ell = A._get_ell()
    if ell is not None:
        ref_e = spv.ell_spmv(ell[0], ell[1], ell[2], x)
        got_e = spv.ell_semiring_spmv(ell[0], ell[1], ell[2], x,
                                      "sum", "times")
        np.testing.assert_array_equal(np.asarray(got_e),
                                      np.asarray(ref_e))


@pytest.mark.slow
def test_semiring_kernels_differential_dense():
    # min-plus / max-times / or-and vs dense references over the
    # STORED structure (stored zeros are edges), incl. empty rows.
    Sc = _graph_csr(72, 0.07, 5)
    A = sparse.csr_array(Sc)
    dense = Sc.toarray()
    mask = np.zeros_like(dense, dtype=bool)
    mask[Sc.nonzero()] = True
    rng = np.random.default_rng(2)
    x = rng.uniform(0, 1, 72).astype(np.asarray(A.data).dtype)
    ref_mp = np.where(mask, dense + x[None, :], np.inf).min(axis=1)
    ref_mt = np.where(mask, dense * x[None, :], -np.inf).max(axis=1)
    f = x > 0.5
    ref_oa = (mask & f[None, :]).any(axis=1)
    got_mp = graph.matvec(A, jnp.asarray(x), semiring="min-plus")
    got_mt = graph.matvec(A, jnp.asarray(x), semiring="max-times")
    got_oa = graph.matvec(A, jnp.asarray(f), semiring="or-and")
    np.testing.assert_allclose(np.asarray(got_mp), ref_mp)
    np.testing.assert_allclose(np.asarray(got_mt), ref_mt)
    assert got_oa.dtype == jnp.bool_.dtype
    np.testing.assert_array_equal(np.asarray(got_oa), ref_oa)
    # explicit kernel routing by registry label
    for label in ("semiring-csr", "semiring-ell", "semiring-sliced-ell"):
        if label == "semiring-ell" and A._get_ell() is None:
            continue
        if (label == "semiring-sliced-ell"
                and A._get_sliced_ell() is None):
            continue
        got = graph.matvec(A, jnp.asarray(x), semiring="min-plus",
                           kernel=label)
        np.testing.assert_allclose(np.asarray(got), ref_mp, rtol=1e-6)
    with pytest.raises(ValueError):
        graph.matvec(A, jnp.asarray(x), kernel="no-such-kernel")


# ------------------------------------------------- distributed SpMV --
@needs_mesh
@pytest.mark.parametrize("layout", LAYOUTS)
def test_dist_semiring_spmv_differential(layout):
    Sc = _graph_csr(64, 0.08, 7)
    A = sparse.csr_array(Sc)
    dense = Sc.toarray()
    mask = np.zeros_like(dense, dtype=bool)
    mask[Sc.nonzero()] = True
    rng = np.random.default_rng(3)
    x = rng.uniform(0, 1, 64).astype(np.asarray(A.data).dtype)
    dA = shard_csr(A, layout=layout)
    dx = shard_vector(jnp.asarray(x), dA.mesh, dA.rows_padded,
                      layout=dA.layout)
    y = np.asarray(dist_spmv(dA, dx, semiring="min-plus"))[:64]
    ref = np.where(mask, dense + x[None, :], np.inf).min(axis=1)
    np.testing.assert_allclose(y, ref)
    f = x > 0.5
    df = shard_vector(jnp.asarray(f), dA.mesh, dA.rows_padded,
                      layout=dA.layout)
    yb = np.asarray(dist_spmv(dA, df, semiring="or-and"))[:64]
    np.testing.assert_array_equal(yb, (mask & f[None, :]).any(axis=1))


@needs_mesh
@pytest.mark.parametrize("layout", LAYOUTS)
def test_dist_semiring_comm_counters_match_prediction(layout):
    # Ledger delta over K calls must match the static per-call
    # prediction within 1% (acceptance criterion; equality expected —
    # both sides are models, but the counter path goes through
    # record/merge plumbing the prediction does not).
    A = sparse.csr_array(_graph_csr(64, 0.08, 11))
    dA = shard_csr(A, layout=layout)
    x = jnp.asarray(np.linspace(0, 1, 64).astype(
        np.asarray(A.data).dtype))
    dx = shard_vector(x, dA.mesh, dA.rows_padded, layout=dA.layout)
    item = np.asarray(A.data).dtype.itemsize
    vols = semiring_spmv_comm_volumes(dA, item, item, "pmin")
    assert vols, "expected at least one collective on a multi-shard mesh"
    if layout == "2d-block":
        assert "pmin" in vols  # the semiring add all-reduce is priced
    obs.reset_all()
    K = 3
    for _ in range(K):
        dist_spmv(dA, dx, semiring="min-plus").block_until_ready()
    snap = counters.snapshot()
    for kind, nbytes in vols.items():
        got = snap.get(f"comm.dist_spmv.{kind}_bytes", 0)
        assert abs(got - K * nbytes) <= 0.01 * K * nbytes, (
            kind, got, K * nbytes)
        assert snap.get(f"comm.dist_spmv.{kind}") == K, kind
    assert snap.get("graph.dist_spmv.min-plus") == K


# -------------------------------------------------------- algorithms --
@needs_mesh
@pytest.mark.parametrize("layout", LAYOUTS)
def test_bfs_matches_scipy(layout):
    Sc = _graph_csr(64, 0.05, 21)
    A = sparse.csr_array(Sc)
    lv = graph.bfs(A, 0, layout=layout)
    order, preds = scsg.breadth_first_order(
        Sc, 0, directed=True, return_predecessors=True)
    ref = np.full(64, -1)
    ref[0] = 0
    for v in order[1:]:
        ref[v] = ref[preds[v]] + 1
    np.testing.assert_array_equal(lv, ref)


@needs_mesh
@pytest.mark.parametrize("layout", LAYOUTS)
def test_sssp_matches_dijkstra(layout):
    Sc = _graph_csr(64, 0.06, 23)
    A = sparse.csr_array(Sc)
    d = graph.sssp(A, 2, layout=layout)
    np.testing.assert_allclose(
        d, scsg.dijkstra(Sc, directed=True, indices=2), rtol=1e-6)


@needs_mesh
@pytest.mark.parametrize("layout", LAYOUTS)
def test_connected_components_matches_scipy(layout):
    # Disconnected graph: two random blocks + isolated vertices.
    rng = np.random.default_rng(31)
    B1 = sp.random(20, 20, density=0.15, random_state=rng)
    B2 = sp.random(30, 30, density=0.12, random_state=rng)
    Sc = sp.block_diag([B1, B2, sp.csr_array((14, 14))]).tocsr()
    A = sparse.csr_array(Sc)
    nc, lab = graph.connected_components(A, layout=layout)
    rnc, rlab = scsg.connected_components(Sc, directed=False)
    assert nc == rnc
    # identical partitions: the label pairing must be a bijection
    assert len(set(zip(lab.tolist(), rlab.tolist()))) == nc


@needs_mesh
@pytest.mark.parametrize("layout", LAYOUTS)
def test_pagerank_matches_dense_numpy(layout):
    Sc = _graph_csr(48, 0.08, 41)
    A = sparse.csr_array(Sc)
    pr = graph.pagerank(A, layout=layout, tol=1e-12, max_iters=200)
    n = 48
    M = np.zeros((n, n))
    outdeg = np.asarray(Sc.astype(bool).sum(axis=1)).ravel()
    for i, j in zip(*Sc.nonzero()):
        M[j, i] = 1.0 / outdeg[i]
    dang = (outdeg == 0).astype(float)
    r = np.full(n, 1.0 / n)
    for _ in range(200):
        r = 0.85 * (M @ r + (dang @ r) / n) + 0.15 / n
    np.testing.assert_allclose(pr, r, atol=1e-8)
    np.testing.assert_allclose(pr.sum(), 1.0, atol=1e-6)


@needs_mesh
def test_pagerank_multigraph_edges_conserve_mass():
    """A duplicated edge list (raw R-MAT COO semantics) must not
    inflate the degree count: M dedupes per coordinate, so outdeg has
    to dedupe too or column sums fall below 1 and rank mass leaks.
    Rank over a multigraph == rank over its simple graph, sum == 1."""
    from legate_sparse_tpu import gallery

    A = gallery.rmat(6, nnz_per_row=4,
                     rng=np.random.default_rng(7), directed=True)
    pr = graph.pagerank(A, tol=1e-12, max_iters=300)
    np.testing.assert_allclose(pr.sum(), 1.0, atol=1e-6)
    Sc = A.toscipy().tocsr().copy()  # canonicalizes: duplicates merge
    Sc.sum_duplicates()
    pr_simple = graph.pagerank(sparse.csr_array(Sc), tol=1e-12,
                               max_iters=300)
    np.testing.assert_allclose(pr, pr_simple, atol=1e-8)


@needs_mesh
def test_batched_multi_source_matches_per_source():
    Sc = _graph_csr(64, 0.05, 51)
    A = sparse.csr_array(Sc)
    srcs = [0, 7, 13]
    lvb = graph.bfs(A, srcs, layout="1d-row")
    assert lvb.shape == (3, 64)
    for i, s in enumerate(srcs):
        np.testing.assert_array_equal(lvb[i],
                                      graph.bfs(A, s, layout="1d-row"))
    db = graph.sssp(A, srcs[:2], layout="1d-row")
    for i, s in enumerate(srcs[:2]):
        np.testing.assert_allclose(
            db[i], scsg.dijkstra(Sc, directed=True, indices=s),
            rtol=1e-6)


@needs_mesh
def test_algorithm_comm_counters_match_prediction():
    # End-to-end: BFS's ledger delta == (iters + 1) x the static
    # per-sweep prediction, within 1% (the +1 is the terminating sweep
    # that finds no new vertex).
    Sc = _graph_csr(64, 0.05, 61)
    A = sparse.csr_array(Sc)
    obs.reset_all()
    graph.bfs(A, 0, layout="2d-block")
    snap = counters.snapshot()
    calls = snap.get("graph.dist_spmv.or-and")
    assert calls == snap.get("graph.bfs.iters") + 1
    # Rebuild the operator's DistCSR the same way bfs did to price it.
    from legate_sparse_tpu.graph.algorithms import _push_operator
    op, _n = _push_operator(A, directed=True, unweighted=True)
    dA = shard_csr(op, layout="2d-block")
    vols = semiring_spmv_comm_volumes(dA, 1, 1, "por")
    for kind, nbytes in vols.items():
        got = snap.get(f"comm.dist_spmv.{kind}_bytes", 0)
        want = calls * nbytes
        assert abs(got - want) <= 0.01 * want, (kind, got, want)


def test_graph_counters_and_knobs():
    from legate_sparse_tpu.settings import settings

    assert settings.graph_conv_iters >= 1
    Sc = _graph_csr(40, 0.08, 71)
    A = sparse.csr_array(Sc)
    obs.reset_all()
    pr5 = graph.pagerank(A, tol=0.0, max_iters=10, conv_test_iters=5)
    snap = counters.snapshot()
    # tol=0 never converges -> exactly max_iters device iterations,
    # quantized by the cadence (10 = 2 cycles of 5).
    assert snap.get("graph.pagerank.iters") == 10
    assert snap.get("graph.pagerank.runs") == 1
    pr2 = graph.pagerank(A, tol=0.0, max_iters=10, conv_test_iters=2)
    np.testing.assert_allclose(pr5, pr2, rtol=1e-12)


def test_sssp_negative_cycle_raises():
    D = np.zeros((4, 4))
    D[0, 1] = 1.0
    D[1, 2] = -2.0
    D[2, 1] = -2.0
    D[2, 3] = 1.0
    A = sparse.csr_array(sp.csr_array(D))
    with pytest.raises(Exception, match="[Nn]egative"):
        graph.sssp(A, 0)


# ------------------------------------------------ push-operator cache --
def _deltas(fn):
    """``(fn(), counter deltas)``."""
    before = counters.snapshot()
    out = fn()
    after = counters.snapshot()
    return out, {k: v - before.get(k, 0) for k, v in after.items()
                 if v != before.get(k, 0)}


def _levels_ref(Sc, s, directed=True):
    """BFS levels from scipy's unweighted shortest paths (-1 unreached)."""
    d = scsg.dijkstra(Sc, directed=directed, indices=s, unweighted=True)
    return np.where(np.isinf(d), -1, d).astype(np.int32)


def _canonical_graph(n=64, density=0.06, seed=0):
    Sc = _graph_csr(n, density, seed)
    Sc.sum_duplicates()
    Sc.sort_indices()
    return Sc


def test_second_bfs_on_one_graph_is_a_hit():
    Sc = _canonical_graph(seed=81)
    A = sparse.csr_array(Sc)
    first, miss = _deltas(lambda: graph.bfs(A, 0))
    second, hit = _deltas(lambda: graph.bfs(A, 0))
    assert miss["graph.build.misses"] == 1
    assert miss["transfer.host_sync.graph_edges"] == 3
    assert miss["transfer.host_sync.shard_csr"] == 3
    assert hit["graph.build.hits"] == 1
    assert "graph.build.misses" not in hit
    assert "transfer.host_sync.graph_edges" not in hit
    assert "transfer.host_sync.shard_csr" not in hit
    np.testing.assert_array_equal(first, _levels_ref(Sc, 0))
    np.testing.assert_array_equal(second, first)


def test_build_keys_keep_algorithms_apart():
    """Direction, weighting and zero-weighting each key their own
    operator; an unweighted SSSP has the directed BFS's exact build
    inputs and so shares its entry."""
    Sc = _canonical_graph(seed=83)
    A = sparse.csr_array(Sc)
    runs = {
        "bfs-directed": (lambda: graph.bfs(A, 3),
                         lambda: _levels_ref(Sc, 3)),
        "bfs-undirected": (lambda: graph.bfs(A, 3, directed=False),
                           lambda: _levels_ref(Sc, 3, directed=False)),
        "sssp-weighted": (lambda: graph.sssp(A, 3),
                          lambda: scsg.dijkstra(Sc, indices=3)),
        "sssp-unweighted": (lambda: graph.sssp(A, 3, unweighted=True),
                            lambda: scsg.dijkstra(Sc, indices=3,
                                                  unweighted=True)),
        "cc": (lambda: graph.connected_components(A)[1],
               lambda: scsg.connected_components(Sc, directed=False)[1]),
    }
    for rnd in ("build", "reuse"):
        for name, (run, ref) in runs.items():
            got, d = _deltas(run)
            want_hit = rnd == "reuse" or name == "sssp-unweighted"
            assert d.get("graph.build.hits", 0) == int(want_hit), (rnd, name)
            assert d.get("graph.build.misses", 0) == int(not want_hit), (
                rnd, name)
            if name == "cc":
                pairs = set(zip(got.tolist(), ref().tolist()))
                assert len(pairs) == len(set(got.tolist())) == \
                    len(set(ref().tolist()))
            else:
                np.testing.assert_allclose(got, ref(), rtol=1e-6,
                                           err_msg=f"{rnd} {name}")
    assert len(A._graph_ops) == 4


@needs_mesh
def test_layout_setting_change_rebuilds(monkeypatch):
    from legate_sparse_tpu.settings import settings

    Sc = _canonical_graph(seed=85)
    A = sparse.csr_array(Sc)
    ref = _levels_ref(Sc, 1)
    for layout, want in (("1d-row", "graph.build.misses"),
                         ("2d-block", "graph.build.misses"),
                         ("1d-row", "graph.build.hits")):
        monkeypatch.setattr(settings, "dist_layout", layout)
        lv, d = _deltas(lambda: graph.bfs(A, 1))
        assert d.get(want) == 1 and sum(
            d.get(k, 0) for k in ("graph.build.hits",
                                  "graph.build.misses")) == 1, layout
        np.testing.assert_array_equal(lv, ref)
    assert sorted(dA.layout for dA, _, _ in A._graph_ops.values()) == \
        ["1d-row", "2d-block"]


def _mut_data(A, Sc):
    w = np.random.default_rng(5).uniform(0.1, 9.0, Sc.nnz)
    A.data = w.astype(np.asarray(A.data).dtype)
    Sc.data = w
    return Sc


def _mut_indices(A, Sc):
    idx = (Sc.indices + 1) % Sc.shape[1]
    A.indices = idx
    return sp.csr_array((Sc.data, idx, Sc.indptr), shape=Sc.shape)


def _mut_setdiag(A, Sc):
    n = Sc.shape[0]
    A.setdiag(np.ones(n - 1), k=1)
    L = sp.lil_array(Sc)
    L.setdiag(np.ones(n - 1), k=1)
    return L.tocsr()


def _mut_eliminate_zeros(A, Sc):
    A.eliminate_zeros()
    Sc = Sc.copy()
    Sc.eliminate_zeros()
    return Sc


def _mut_sort_indices(A, Sc):
    A.sort_indices()
    return Sc


def _mut_sum_duplicates(A, Sc):
    A.sum_duplicates()
    return Sc


def _mut_resize(A, Sc):
    A.resize(48, 48)
    return Sc[:48, :48].tocsr()


def _half_zero_graph():
    Sc = _canonical_graph(seed=87)
    Sc.data[::2] = 0.0                   # stored zeros are edges
    return sparse.csr_array(Sc), Sc


def _reversed_rows_graph():
    """Raw (data, indices, indptr) with every row's columns reversed."""
    Sc = _canonical_graph(seed=89)
    data, idx = Sc.data.copy(), Sc.indices.copy()
    for r in range(Sc.shape[0]):
        lo, hi = Sc.indptr[r], Sc.indptr[r + 1]
        data[lo:hi], idx[lo:hi] = data[lo:hi][::-1], idx[lo:hi][::-1]
    return sparse.csr_array((data, idx, Sc.indptr), shape=Sc.shape), Sc


def _duplicated_graph():
    """COO with every edge stored twice: the push operator keeps the
    smaller copy, and ``sum_duplicates`` adds them."""
    Sc = _canonical_graph(seed=91)
    C = Sc.tocoo()
    rows = np.concatenate([C.row, C.row])
    cols = np.concatenate([C.col, C.col])
    vals = np.concatenate([C.data, 2.0 * C.data])
    A = sparse.csr_array((vals, (rows, cols)), shape=Sc.shape)
    return A, sp.csr_array((vals, (rows, cols)), shape=Sc.shape)


#: (search on a package array, its scipy twin) from vertex 0.
_SEARCHES = {
    "bfs": (lambda A: graph.bfs(A, 0), lambda S: _levels_ref(S, 0)),
    "sssp": (lambda A: graph.sssp(A, 0),
             lambda S: scsg.dijkstra(S, indices=0)),
}


def _plain_graph(seed):
    def make():
        Sc = _canonical_graph(seed=seed)
        return sparse.csr_array(Sc), Sc
    return make


@pytest.mark.parametrize("mutate,make,alg,changes", [
    (_mut_data, _plain_graph(93), "sssp", True),
    (_mut_indices, _plain_graph(95), "bfs", True),
    (_mut_setdiag, _plain_graph(97), "bfs", True),
    (_mut_eliminate_zeros, _half_zero_graph, "bfs", True),
    (_mut_sort_indices, _reversed_rows_graph, "bfs", False),
    (_mut_sum_duplicates, _duplicated_graph, "sssp", True),
    (_mut_resize, _plain_graph(99), "bfs", True),
], ids=["data", "indices", "setdiag", "eliminate_zeros", "sort_indices",
        "sum_duplicates", "resize"])
def test_mutation_drops_the_stored_operator(mutate, make, alg, changes):
    """Every in-place mutator clears ``_graph_ops``; the next search
    rebuilds and matches scipy on the mutated graph."""
    search, ref = _SEARCHES[alg]
    A, Sc = make()
    before = search(A)
    assert A._graph_ops
    Sc = mutate(A, Sc)
    assert A._graph_ops is None
    after, d = _deltas(lambda: search(A))
    assert d.get("graph.build.misses") == 1 and "graph.build.hits" not in d
    np.testing.assert_allclose(after, ref(Sc), rtol=1e-6)
    assert (before.shape != after.shape
            or not np.allclose(before, after)) == changes


def test_scipy_and_dense_inputs_never_hit():
    Sc = _canonical_graph(seed=101)
    ref = _levels_ref(Sc, 2)
    for G in (Sc, Sc.toarray()):
        for _ in range(2):
            lv, d = _deltas(lambda: graph.bfs(G, 2))
            assert d.get("graph.build.misses") == 1
            assert "graph.build.hits" not in d
            np.testing.assert_array_equal(lv, ref)
