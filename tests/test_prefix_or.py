# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""The padded-CSR semiring kernels' prefix-count OR.

Where the add is ``max`` over a bool product (``or-and``), the kernels
reduce each row by an int32 prefix count read at the row bounds in
place of the ``segment_max`` scatter.  These tests hold the two forms
equal bit for bit, on hand-made blocks (empty rows, an all-empty
block, a row holding the whole block, padding slots whose gathered bit
is True) and through the distributed builds and dispatches that carry
``DistCSR.row_ptr``.
"""

import dataclasses

import numpy as np
import pytest
import scipy.sparse as sp
import scipy.sparse.csgraph as scsg

import jax
import jax.numpy as jnp

import legate_sparse_tpu as sparse
from legate_sparse_tpu import graph, obs
from legate_sparse_tpu.obs import counters, trace
from legate_sparse_tpu.ops import spmv as spv
from legate_sparse_tpu.parallel import shard_csr
from legate_sparse_tpu.parallel.dist_csr import (
    dist_spmm, dist_spmv, shard_dense, shard_vector,
)
from legate_sparse_tpu.parallel.mesh import make_row_mesh

needs_4 = pytest.mark.skipif(len(jax.devices()) < 4,
                             reason="needs four devices")


@pytest.fixture(autouse=True)
def _obs_isolation():
    was = trace.enabled()
    obs.reset_all()
    trace.disable()
    yield
    obs.reset_all()
    if was:
        trace.enable()


# Per-row stored counts of one block (rows = len), and the padding
# slots after the valid ones.
BLOCKS = {
    "empty-rows": ([0, 3, 0, 0, 2, 5, 0, 1, 0, 4], 3),
    "all-empty": ([0, 0, 0, 0, 0, 0], 2),
    "whole-block": ([0, 0, 17, 0], 0),
    "padding-true": ([2, 1, 0, 3, 1], 6),
}


def _block(name, cols=24, seed=0):
    """(row_ids, indices, valid, row_ptr, rows, nnz_max) of a padded
    block laid out as ``shard_csr`` lays it: padding slots take the
    last row id and, here, a column whose frontier bit is True."""
    counts, pad = BLOCKS[name]
    rows = len(counts)
    valid = int(sum(counts))
    rng = np.random.default_rng(seed)
    rid = np.repeat(np.arange(rows, dtype=np.int32), counts)
    idx = rng.integers(0, cols, valid).astype(np.int32)
    rid = np.concatenate([rid, np.full(pad, rows - 1, np.int32)])
    idx = np.concatenate([idx, np.zeros(pad, np.int32)])
    if valid + pad == 0:          # a block holds at least one slot
        rid, idx, pad = np.array([rows - 1], np.int32), \
            np.zeros(1, np.int32), 1
    row_ptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    return rid, idx, valid, row_ptr, rows, valid + pad


def _frontier(cols, k, seed):
    f = np.random.default_rng(seed).random((cols,) if k is None
                                           else (cols, k)) < 0.3
    f[0] = True                   # the padding slots' column
    return f


@pytest.mark.parametrize("k", [None, 1, 3, 64])
@pytest.mark.parametrize("name", sorted(BLOCKS))
def test_prefix_or_equals_segment_max(name, k):
    rid, idx, valid, row_ptr, rows, nnz = _block(name)
    f = _frontier(24, k, 5)
    vals = jnp.ones((nnz,), jnp.float32)
    args = (vals, jnp.asarray(idx), jnp.asarray(rid), jnp.int32(valid),
            jnp.asarray(f), rows, "max", "and")
    kern = (spv.csr_semiring_spmv_rowids_masked if k is None
            else spv.csr_semiring_spmm_rowids_masked)
    seg = np.asarray(kern(*args))
    pre = np.asarray(kern(*args, row_ptr=jnp.asarray(row_ptr)))
    assert pre.dtype == seg.dtype == np.bool_
    np.testing.assert_array_equal(pre, seg)
    # ... and both equal the rows' OR over their valid slots alone.
    g = f[idx[:valid]]
    want = np.stack([g[row_ptr[r]:row_ptr[r + 1]].any(axis=0)
                     for r in range(rows)])
    np.testing.assert_array_equal(pre, want)


def test_prefix_form_follows_add_and_dtype():
    is_or = spv.is_boolean_or
    assert is_or("max", "and", np.float32, np.bool_)
    assert is_or("max", "times", np.bool_, np.bool_)
    assert not is_or("max", "times", np.float32, np.float32)
    assert not is_or("min", "plus", np.float32, np.float32)
    assert not is_or("sum", "times", np.bool_, np.bool_)


def _graph(n=64, seed=3):
    """A graph with empty rows, a heavy row and an empty row block
    (rows 16..31: a whole shard of four on 64 rows)."""
    rng = np.random.default_rng(seed)
    S = sp.random(n, n, density=0.08, random_state=rng, format="lil")
    S[16:32, :] = 0
    S[5, :] = 1.0                 # one row holding a full row of edges
    S = S.tocsr()
    S.eliminate_zeros()
    return S


def _dist(S, shards, layout):
    """Padded-CSR blocks over ``shards`` devices; layout "precise" is
    1d-row with the precise-image x realization."""
    precise = layout == "precise"
    return shard_csr(sparse.csr_array(S), mesh=make_row_mesh(shards),
                     layout="1d-row" if precise else layout,
                     ell_max_expand=0, precise=precise or None)


CASES = [(1, "1d-row"), pytest.param(4, "1d-row", marks=needs_4),
         pytest.param(4, "precise", marks=needs_4),
         pytest.param(4, "2d-block", marks=needs_4)]


@pytest.mark.parametrize("shards,layout", CASES)
def test_row_ptr_agrees_with_row_ids_and_counts(shards, layout):
    dA = _dist(_graph(), shards, layout)
    assert not dA.ell and dA.row_ptr is not None
    rps = dA.rows_per_shard
    rp = np.asarray(dA.row_ptr).reshape(-1, rps + 1)
    rid = np.asarray(dA.row_ids).reshape(rp.shape[0], -1)
    cnt = np.asarray(dA.counts).reshape(-1)
    assert rp.dtype == np.int32
    for s in range(rp.shape[0]):
        c = int(cnt[s])
        assert rp[s, 0] == 0 and rp[s, -1] == c
        np.testing.assert_array_equal(
            np.diff(rp[s]), np.bincount(rid[s, :c], minlength=rps))


@pytest.mark.parametrize("shards,layout", CASES)
def test_dist_or_and_prefix_equals_segment(shards, layout):
    S = _graph()
    dA = _dist(S, shards, layout)
    dA_seg = dataclasses.replace(dA, row_ptr=None)
    mask = S.toarray() != 0
    f = _frontier(64, None, 9)
    df = shard_vector(jnp.asarray(f), dA.mesh, dA.rows_padded,
                      layout=dA.layout)
    pre = np.asarray(dist_spmv(dA, df, semiring="or-and"))
    seg = np.asarray(dist_spmv(dA_seg, df, semiring="or-and"))
    np.testing.assert_array_equal(pre, seg)
    np.testing.assert_array_equal(pre[:64], (mask & f[None, :]).any(1))
    if layout != "2d-block":
        F = _frontier(64, 3, 11)
        dF = shard_dense(jnp.asarray(F), dA.mesh, dA.rows_padded)
        pre = np.asarray(dist_spmm(dA, dF, semiring="or-and"))
        seg = np.asarray(dist_spmm(dA_seg, dF, semiring="or-and"))
        np.testing.assert_array_equal(pre, seg)
        np.testing.assert_array_equal(pre[:64], np.stack(
            [(mask & F[None, :, j]).any(1) for j in range(3)], axis=1))


@pytest.mark.parametrize("semiring,prefix", [("or-and", True),
                                             ("min-plus", False)])
def test_prefix_or_counter_and_span(semiring, prefix):
    S = _graph()
    dA = _dist(S, 1, "1d-row")
    x = (_frontier(64, None, 2) if semiring == "or-and"
         else np.linspace(0, 1, 64).astype(np.asarray(dA.data).dtype))
    dx = shard_vector(jnp.asarray(x), dA.mesh, dA.rows_padded)
    trace.enable()
    obs.reset_all()
    for _ in range(3):
        dist_spmv(dA, dx, semiring=semiring)
    dist_spmm(dA, shard_dense(jnp.asarray(np.stack([x, x], 1)),
                              dA.mesh, dA.rows_padded), semiring=semiring)
    assert counters.snapshot().get("graph.dist_spmv.prefix_or", 0) == \
        (4 if prefix else 0)
    got = [r["attrs"]["reduce"] for r in obs.records()
           if r["name"] == "dist_spmv" and r["type"] == "span"]
    assert got == ["prefix" if prefix else "segment"] * 3


def test_bfs_prefix_count_per_sweep(monkeypatch):
    """A padded-CSR BFS takes the prefix form in every sweep, the last
    one (which finds no vertex) included, and its levels are scipy's;
    a batch of roots takes it through ``dist_spmm``."""
    from legate_sparse_tpu.settings import settings

    monkeypatch.setattr(settings, "ell_max_expand", 0.0)
    S = _graph(seed=21)
    A = sparse.csr_array(S)
    obs.reset_all()
    lv = graph.bfs(A, 0, layout="1d-row")
    snap = counters.snapshot()
    assert snap.get("graph.dist_spmv.prefix_or") == \
        snap.get("graph.bfs.iters") + 1
    ref = scsg.shortest_path(S, unweighted=True, indices=0)
    np.testing.assert_array_equal(
        lv, np.where(np.isinf(ref), -1, ref).astype(np.int32))
    obs.reset_all()
    lvb = graph.bfs(A, [0, 7, 40], layout="1d-row")
    assert counters.snapshot().get("graph.dist_spmv.prefix_or") == \
        counters.snapshot().get("graph.bfs.iters") + 1
    np.testing.assert_array_equal(lvb[0], lv)


def test_matvec_semiring_csr_or_and_reads_indptr():
    S = _graph(seed=8)
    A = sparse.csr_array(S)
    f = _frontier(64, None, 4)
    got = graph.matvec(A, jnp.asarray(f), semiring="or-and")
    ref = spv.csr_semiring_spmv_rowids_masked(
        A.data, A.indices, A._get_row_ids(),
        jnp.int32(A.data.shape[0]), jnp.asarray(f), 64, "max", "and")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    np.testing.assert_array_equal(
        np.asarray(got), ((S.toarray() != 0) & f[None, :]).any(1))
