# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Spans that follow ``jax.profiler``: with tracing off and no profiler
the span API stays a shared no-op; while a profiler trace runs, the
solver (one-chip and distributed), graph and dispatch layers record
their span trees into the obs buffer and annotate the profiler's host
plane with the same names; and the host-sync counters read the same
with tracing off and on."""

import glob
import os

import numpy as np
import pytest
import scipy.sparse as sp

import jax

import legate_sparse_tpu as sparse
from legate_sparse_tpu import graph, linalg, obs, parallel
from legate_sparse_tpu.obs import counters, trace

#: Blocking fetches of one single-root search on a graph whose push
#: operator is already built, beyond its sweeps' (one per sweep, the
#: last finding nothing new): the returned levels (1) plus the
#: terminating sweep's scalar.  ``host_syncs.bfs`` minus ``bfs_sweeps``
#: in the benchmark reads this.
BFS_SYNCS_BEYOND_SWEEPS = 2

#: What the first search of a graph adds: the build's edge fetch (3)
#: and the host cut of ``shard_csr`` (3).
BFS_BUILD_SYNCS = 6


@pytest.fixture(autouse=True)
def _obs_isolation():
    was_enabled = trace.enabled()
    obs.reset_all()
    trace.disable()
    yield
    obs.reset_all()
    if was_enabled:
        trace.enable()


def _banded(n=64):
    return sparse.diags(
        [np.full(n - 1, -1.0), np.full(n, 4.0), np.full(n - 1, -1.0)],
        [-1, 0, 1], shape=(n, n), format="csr", dtype=np.float32)


def _graph(n=64):
    S = sp.random(n, n, density=0.06, format="csr",
                  random_state=np.random.default_rng(3))
    S.data[:] = 1.0
    return sparse.csr_array(S)


def _host_syncs(deltas):
    return {k: v for k, v in deltas.items()
            if k.startswith("transfer.host_sync.")}


def _measure(fn, profile_dir=None):
    """(result, span records, counter deltas) of ``fn()``, under a
    profiler trace written to ``profile_dir`` when one is given."""
    obs.trace.reset()
    before = counters.snapshot()
    if profile_dir is not None:
        jax.profiler.start_trace(str(profile_dir))
    try:
        out = fn()
    finally:
        if profile_dir is not None:
            jax.profiler.stop_trace()
    after = counters.snapshot()
    deltas = {k: v - before.get(k, 0) for k, v in after.items()
              if v != before.get(k, 0)}
    spans = [r for r in obs.records() if r["type"] == "span"]
    return out, spans, deltas


def _children(spans, parent):
    lo, hi = parent["ts_ns"], parent["ts_ns"] + parent["dur_ns"]
    return [r for r in spans if r["depth"] == parent["depth"] + 1
            and r["tid"] == parent["tid"]
            and lo <= r["ts_ns"] and r["ts_ns"] + r["dur_ns"] <= hi]


def _named(spans, name):
    return [r for r in spans if r["name"] == name]


def _host_event_names(profile_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(profile_dir), "**",
                                     "*.xplane.pb"), recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    return names


# ------------------------------------------------------------ off path --
def test_off_path_is_the_shared_null_span(monkeypatch):
    """Tracing off, no profiler: one profiler-flag read per span site,
    the shared null span (no allocation), no clock read, no record."""
    reads = []

    def flag():
        reads.append(1)
        return False

    class NoClock:
        @staticmethod
        def perf_counter_ns():
            raise AssertionError("the off path read the clock")

    monkeypatch.setattr(trace, "_profiling", flag)
    monkeypatch.setattr(trace, "time", NoClock)
    with obs.span("spmv", nnz=3) as sp_:
        assert sp_ is None
    assert trace.span("cg") is trace.span("graph.bfs") is trace._NULL_SPAN
    obs.event("never")
    trace.complete_span("never", 0, 1)
    assert len(reads) == 5
    assert obs.records() == []


def test_disable_does_not_stop_a_running_profiler(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        trace.disable()
        with obs.span("outer") as sp_:
            assert sp_ is not None
            obs.event("inside")
    finally:
        jax.profiler.stop_trace()
    with obs.span("after") as sp_:
        assert sp_ is None
    assert [r["name"] for r in obs.records()] == ["inside", "outer"]
    assert {"outer", "inside"} <= _host_event_names(tmp_path)


# ---------------------------------------------------------- span trees --
def test_cg_span_tree_and_norm_sync(tmp_path):
    A = _banded()
    b = np.ones(64, np.float32)
    solve = lambda: linalg.cg(A, b, rtol=1e-6, maxiter=200)  # noqa: E731
    solve()                                  # compile outside
    _, off_spans, off = _measure(solve)
    (x, iters), spans, on = _measure(solve, tmp_path)
    assert off_spans == []
    (cg,) = _named(spans, "cg")
    assert cg["depth"] == 0 and cg["attrs"]["iters"] == int(iters) > 0
    (launch,) = [r for r in _children(spans, cg) if r["name"] == "cg.launch"]
    assert launch["ts_ns"] >= cg["ts_ns"]
    assert [r["name"] for r in _children(spans, launch)
            if r["name"] == "cg.norm"] == ["cg.norm"]
    assert _host_syncs(off) == _host_syncs(on) == {
        "transfer.host_sync.cg_norm": 1}
    assert {"cg", "cg.launch", "cg.norm"} <= _host_event_names(tmp_path)


def _dist_banded(n=64):
    return parallel.dist_diags(
        [4.0, -1.0, -1.0], [0, 1, -1], shape=(n, n),
        mesh=parallel.make_row_mesh(4), dtype=np.float32)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs four devices")
def test_dist_cg_span_tree_and_norm_sync(tmp_path):
    """``dist_cg`` records ``linalg.cg``'s tree: ``dist_cg`` ⊃
    ``dist_cg.launch`` ⊃ ``dist_cg.norm``, and one counted fetch a
    solve, traced or not."""
    dA = _dist_banded()
    b = np.ones(64, np.float32)
    solve = lambda: [parallel.dist_cg(dA, b, rtol=1e-6)  # noqa: E731
                     for _ in range(2)]
    solve()                                  # compile outside
    _, off_spans, off = _measure(solve)
    out, spans, on = _measure(solve, tmp_path)
    assert off_spans == []
    tops = _named(spans, "dist_cg")
    assert [t["attrs"]["iters"] for t in tops] == [int(it) for _, it in out]
    for top in tops:
        assert top["depth"] == 0 and top["attrs"]["maxiter"] == 640
        (launch,) = [r for r in _children(spans, top)
                     if r["name"] == "dist_cg.launch"]
        assert [r["name"] for r in _children(spans, launch)
                if r["name"] == "dist_cg.norm"] == ["dist_cg.norm"]
    assert _host_syncs(off) == _host_syncs(on) == {
        "transfer.host_sync.dist_cg_norm": 2}
    assert {"dist_cg", "dist_cg.launch", "dist_cg.norm"} <= \
        _host_event_names(tmp_path)


def test_bfs_span_tree_and_sync_counts(tmp_path):
    G = _graph()
    search = lambda: graph.bfs(G, 0, directed=False)  # noqa: E731
    search()
    _, _, off = _measure(search)
    _, spans, on = _measure(search, tmp_path)
    iters = on["graph.bfs.iters"]
    assert iters == off["graph.bfs.iters"] > 1
    (top,) = _named(spans, "graph.bfs")
    kids = [r["name"] for r in _children(spans, top)]
    assert kids.count("graph.build") == 1
    (build,) = _named(spans, "graph.build")
    assert build["attrs"]["cached"] is True
    assert kids.count("graph.sweep") == iters + 1
    assert len(_named(spans, "graph.sweep")) == iters + 1
    assert _host_syncs(off) == _host_syncs(on) == {
        "transfer.host_sync.bfs_sweep": iters + 1,
        "transfer.host_sync.bfs_levels": 1,
    }
    assert off["graph.build.hits"] == on["graph.build.hits"] == 1
    assert "graph.build.misses" not in off and "graph.build.misses" not in on
    assert (sum(_host_syncs(on).values()) - iters
            == BFS_SYNCS_BEYOND_SWEEPS)
    assert {"graph.bfs", "graph.build", "graph.sweep"} <= \
        _host_event_names(tmp_path)


def test_bfs_first_search_builds_and_counts_its_fetches(tmp_path):
    """A fresh graph's first search is a miss: its build fetches the
    edges (3 arrays) and cuts the shards on the host (3 more)."""
    graph.bfs(_graph(), 0, directed=False)   # compile outside
    _, _, off = _measure(lambda: graph.bfs(_graph(), 0, directed=False))
    _, spans, on = _measure(lambda: graph.bfs(_graph(), 0, directed=False),
                            tmp_path)
    iters = on["graph.bfs.iters"]
    assert iters == off["graph.bfs.iters"] > 1
    (build,) = _named(spans, "graph.build")
    assert build["attrs"]["cached"] is False
    assert _host_syncs(off) == _host_syncs(on) == {
        "transfer.host_sync.bfs_sweep": iters + 1,
        "transfer.host_sync.bfs_levels": 1,
        "transfer.host_sync.graph_edges": 3,
        "transfer.host_sync.shard_csr": 3,
    }
    assert off["graph.build.misses"] == on["graph.build.misses"] == 1
    assert "graph.build.hits" not in off and "graph.build.hits" not in on
    assert (sum(_host_syncs(on).values()) - iters
            == BFS_SYNCS_BEYOND_SWEEPS + BFS_BUILD_SYNCS)


def test_graph_build_sits_under_each_algorithm(tmp_path):
    G = _graph()
    runs = {"graph.sssp": lambda: graph.sssp(G, 0),
            "graph.cc": lambda: graph.connected_components(G),
            "graph.pagerank": lambda: graph.pagerank(G, max_iters=3)}
    for name, fn in runs.items():
        fn()
        _, spans, _ = _measure(fn, tmp_path / name)
        (top,) = _named(spans, name)
        assert [r["name"] for r in _children(spans, top)
                if r["name"] == "graph.build"] == ["graph.build"], name


def test_spmv_span_under_profiler(tmp_path):
    A = _banded()
    x = np.ones(64, np.float32)
    A @ x
    y, spans, deltas = _measure(lambda: A @ x, tmp_path)
    (s,) = _named(spans, "spmv")
    assert s["depth"] == 0 and s["attrs"]["nnz"] == A.nnz
    assert deltas["op.spmv"] == 1
    assert "spmv" in _host_event_names(tmp_path)
    np.testing.assert_allclose(np.asarray(y), A.todense() @ x, rtol=1e-5)
