# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Comm ledger (obs/comm.py): the distributed layer's collective byte
accounting must MATCH the static shard-shape prediction — asserted
here by recomputing the model from first principles (mesh size, halo
width, block sizes) and comparing against the recorded counters and
span attrs.  Also covers the sparsity-aware window-decline key
(ADVICE r5 low, finished this round)."""

import importlib

import numpy as np
import pytest
import scipy.sparse as sp

import jax

import legate_sparse_tpu as sparse
from legate_sparse_tpu import obs
from legate_sparse_tpu.obs import comm, counters, trace
from legate_sparse_tpu.parallel import (
    DistGMG, dist_cg, dist_spgemm, make_row_mesh, shard_csr,
)
from legate_sparse_tpu.parallel.dist_csr import (
    cg_comm_volumes, dist_spmv, shard_vector, spmv_comm_volumes,
)

_spg = importlib.import_module("legate_sparse_tpu.parallel.dist_spgemm")

R = len(jax.devices())
needs_mesh = pytest.mark.skipif(R < 2, reason="needs a multi-device mesh")
needs_window = pytest.mark.skipif(R < 4,
                                  reason="window + density buckets "
                                         "need R >= 4")


@pytest.fixture(autouse=True)
def _obs_isolation():
    was = trace.enabled()
    obs.reset_all()
    trace.disable()
    yield
    obs.reset_all()
    if was:
        trace.enable()
    else:
        trace.disable()


def _banded(n, dtype=np.float32):
    return sparse.diags(
        [np.ones(n - 1), np.full(n, 4.0), np.ones(n - 1)], [-1, 0, 1],
        shape=(n, n), format="csr", dtype=dtype,
    )


# ----------------------------------------------------------- the model --
def test_model_single_shard_moves_nothing():
    for fn in (comm.all_gather_bytes, comm.psum_bytes,
               comm.all_to_all_bytes):
        assert fn(100, 4, 1) == 0
    assert comm.halo_exchange_bytes(5, 4, 1) == 0
    assert comm.ppermute_bytes(10, 4, 1, rounds=3) == 0


def test_model_formulas():
    assert comm.all_gather_bytes(10, 4, 8) == 8 * 7 * 10 * 4
    assert comm.halo_exchange_bytes(5, 4, 8) == 2 * 8 * 5 * 4
    assert comm.halo_exchange_bytes(0, 4, 8) == 0
    assert comm.psum_bytes(1, 4, 8) == 2 * 7 * 4
    assert comm.all_to_all_bytes(3, 4, 8) == 8 * 7 * 3 * 4
    assert comm.ppermute_bytes(10, 4, 8, rounds=3) == 3 * 8 * 10 * 4


def test_merge_scale_total():
    a = {"psum": 10, "ppermute": 5}
    b = {"psum": 1}
    assert comm.merge(a, b) == {"psum": 11, "ppermute": 5}
    assert comm.scale(a, 3) == {"psum": 30, "ppermute": 15}
    assert comm.total(a) == 15


def test_record_drops_zero_entries_and_accumulates():
    counters.reset("comm.")
    got = comm.record("unit_op", {"psum": 0, "all_gather": 128},
                      calls={"all_gather": 4})
    assert got == 128
    assert counters.get("comm.unit_op.all_gather") == 4
    assert counters.get("comm.unit_op.all_gather_bytes") == 128
    assert counters.get("comm.unit_op.psum") == 0
    assert counters.get("comm.total_bytes") == 128
    assert counters.get("comm.total_calls") == 4


# ----------------------------------------- counters match shard shapes --
@needs_mesh
def test_halo_spmv_counters_match_static_prediction():
    mesh = make_row_mesh()
    n = 32 * R
    dA = shard_csr(_banded(n), mesh=mesh)
    assert dA.halo == 1       # tridiagonal band
    x = shard_vector(np.ones(n, np.float32), mesh, dA.rows_padded)
    counters.reset("comm.")
    _ = dist_spmv(dA, x)
    _ = dist_spmv(dA, x)
    per_call = 2 * R * dA.halo * 4      # two-sided exchange, f32
    assert counters.get("comm.dist_spmv.ppermute") == 2
    assert counters.get("comm.dist_spmv.ppermute_bytes") == 2 * per_call
    assert counters.get("comm.total_bytes") == 2 * per_call


@needs_mesh
def test_all_gather_spmv_counters_match_static_prediction():
    mesh = make_row_mesh()
    n = 32 * R
    dA = shard_csr(_banded(n), mesh=mesh, force_all_gather=True)
    assert dA.halo == -1 and dA.gather_idx is None
    x = shard_vector(np.ones(n, np.float32), mesh, dA.rows_padded)
    counters.reset("comm.")
    _ = dist_spmv(dA, x)
    per_call = R * (R - 1) * (dA.rows_padded // R) * 4
    assert counters.get("comm.dist_spmv.all_gather") == 1
    assert counters.get("comm.dist_spmv.all_gather_bytes") == per_call


@needs_mesh
def test_precise_spmv_counters_match_static_prediction():
    mesh = make_row_mesh()
    n = 32 * R
    dA = shard_csr(_banded(n), mesh=mesh, precise=True)
    assert dA.gather_idx is not None
    C = int(dA.gather_idx.shape[-1])
    x = shard_vector(np.ones(n, np.float32), mesh, dA.rows_padded)
    counters.reset("comm.")
    _ = dist_spmv(dA, x)
    per_call = R * (R - 1) * C * 4
    assert counters.get("comm.dist_spmv.all_to_all") == 1
    assert counters.get("comm.dist_spmv.all_to_all_bytes") == per_call


@needs_mesh
def test_spmv_span_carries_comm_attrs():
    trace.enable()
    mesh = make_row_mesh()
    n = 32 * R
    dA = shard_csr(_banded(n), mesh=mesh)
    x = shard_vector(np.ones(n, np.float32), mesh, dA.rows_padded)
    _ = dist_spmv(dA, x)
    (span,) = [r for r in obs.records() if r["name"] == "dist_spmv"]
    assert span["attrs"]["comm_bytes"] == 2 * R * dA.halo * 4
    assert span["attrs"]["comm_calls"] == 1


@needs_mesh
def test_dist_cg_comm_matches_iteration_model():
    trace.enable()
    mesh = make_row_mesh()
    n = 32 * R
    dA = shard_csr(_banded(n), mesh=mesh)
    counters.reset("comm.")
    maxiter = 7
    _, iters = dist_cg(dA, np.ones(n, np.float32), rtol=0.0,
                       maxiter=maxiter, conv_test_iters=5)
    it = int(iters)
    assert it == maxiter        # rtol=0/atol=0 never converges early
    vols, _calls = cg_comm_volumes(dA, 4, it)
    (span,) = [r for r in obs.records() if r["name"] == "dist_cg"]
    assert span["attrs"]["comm_bytes"] == sum(vols.values())
    # Independent recomputation against the fused _cg_loop program:
    # iters+1 halo exchanges (initial residual + one per iteration)
    # and 3 scalar psums per iteration (rho, pq, and the
    # unconditional rnorm2 vdot).
    expect_pp = (it + 1) * 2 * R * dA.halo * 4
    expect_ps = 3 * it * 2 * (R - 1) * 4
    assert counters.get("comm.dist_cg.ppermute_bytes") == expect_pp
    assert counters.get("comm.dist_cg.psum_bytes") == expect_ps


@needs_mesh
def test_dist_cg_callback_path_does_not_double_count_spmv():
    """A callback solve runs the loop path's iteration, so it leaves the
    loop path's ledger: the same comm.dist_spmv.* (one SpMV dispatch
    for the initial residual, one traced into the loop) and the same
    comm.dist_cg.*, priced from ``cg_comm_volumes`` alone."""
    trace.enable()
    mesh = make_row_mesh()
    n = 32 * R
    dA = shard_csr(_banded(n), mesh=mesh)
    b = np.ones(n, np.float32)

    def ledger(**kw):
        counters.reset("comm.")
        _, iters = dist_cg(dA, b, rtol=0.0, maxiter=3, **kw)
        return int(iters), {k: v for k, v in counters.snapshot().items()
                            if k.startswith(("comm.dist_cg.",
                                             "comm.dist_spmv."))}

    seen = []
    it_loop, loop = ledger()
    it_cb, observed = ledger(callback=seen.append)
    assert it_cb == it_loop == len(seen) == 3
    assert observed == loop
    vols, _calls = cg_comm_volumes(dA, 4, it_cb)
    assert sum(v for k, v in observed.items()
               if k.startswith("comm.dist_cg.") and k.endswith("_bytes")) \
        == sum(vols.values())


@needs_mesh
def test_dist_spgemm_realization_event_carries_predictions():
    trace.enable()
    mesh = make_row_mesh()
    n = 16 * R
    rng = np.random.RandomState(0)
    A_sp = sp.random(n, n, density=0.4, random_state=rng,
                     format="csr", dtype=np.float64)
    A_sp.sum_duplicates()
    dA = shard_csr(sparse.csr_array(A_sp), mesh=mesh,
                   force_all_gather=True)
    counters.reset("comm.")
    _ = dist_spgemm(dA, dA)
    evs = [r for r in obs.records()
           if r["name"] == "dist_spgemm.realization"]
    assert len(evs) == 1
    at = evs[0]["attrs"]
    assert at["choice"] == "all_gather"
    assert at["predicted_bytes"] == at["predicted_all_gather_bytes"] > 0
    # The chosen realization is what entered the ledger.
    assert (counters.get("comm.dist_spgemm.all_gather_bytes")
            == at["predicted_bytes"])
    (span,) = [r for r in obs.records() if r["name"] == "dist_spgemm"]
    assert span["attrs"]["comm_bytes"] == at["predicted_bytes"]


@needs_window
def test_windowed_realization_predicts_fewer_bytes_than_all_gather():
    """The window-vs-all_gather choice is now evidence-backed: for a
    narrow-window band on the general ESC path the recorded window
    prediction must undercut the all_gather counterfactual."""
    trace.enable()
    mesh = make_row_mesh()
    n = 16 * R
    d0 = np.where(np.arange(n) % 3 == 0, 0.0, 2.0)
    A = sparse.diags([d0, np.ones(n - 1)], [0, 1], shape=(n, n),
                     format="csr")
    dA = shard_csr(A, mesh=mesh)
    assert dA.dia_mask is not None     # holey band -> general ESC
    _spg.reset_window_declines()
    counters.reset("comm.")
    _ = dist_spgemm(dA, dA)
    assert _spg.LAST_B_REALIZATION == "window"
    evs = [r for r in obs.records()
           if r["name"] == "dist_spgemm.realization"]
    at = evs[-1]["attrs"]
    assert at["choice"] == "window"
    assert 0 < at["predicted_window_bytes"] == at["predicted_bytes"]
    assert at["predicted_window_bytes"] < at["predicted_all_gather_bytes"]
    assert (counters.get("comm.dist_spgemm.ppermute_bytes")
            == at["predicted_bytes"])
    # The probe's own two scalar all_gathers are ledgered too.
    assert counters.get(
        "comm.dist_spgemm.window_probe.all_gather") == 2


@pytest.mark.slow
@pytest.mark.skipif(R < 8, reason="needs the 8-device mesh")
def test_gmg_hierarchy_prices_its_cycle():
    # Same operator/mesh construction as test_grid_mesh's
    # test_full_dist_stack_on_grid_mesh, so the expensive
    # hierarchy-build compiles are shared once per suite run.
    from legate_sparse_tpu.parallel import make_grid_mesh

    trace.enable()
    mesh = make_grid_mesh(jax.devices()[:8])
    n = 256
    A = sparse.diags([-1.0, 4.0, -1.0], [-16, 0, 16], shape=(n, n),
                     format="csr", dtype=np.float64)
    gmg = DistGMG(shard_csr(A, mesh=mesh), levels=2)
    assert gmg.cycle_comm_bytes == sum(gmg.cycle_comm_volumes.values())
    assert gmg.cycle_comm_bytes > 0
    evs = [r for r in obs.records()
           if r["name"] == "dist_gmg.hierarchy"]
    assert evs and evs[0]["attrs"]["cycle_comm_bytes"] == \
        gmg.cycle_comm_bytes


@needs_mesh
def test_model_matches_lowered_collectives():
    """Anti-circularity check: the ledger's collective KINDS,
    multiplicities AND bytes must match the program XLA actually
    lowers, not just the model that produced the counters.  Goes
    through planverify's schedule checker (tools/verify) — the same
    parser and byte convention the contract gate enforces — instead of
    ad-hoc substring counting."""
    from tools.verify.catalog import Built
    from tools.verify.rules import lowered_volumes, schedule_of

    mesh = make_row_mesh()
    n = 32 * R

    def built_of(dA):
        x = shard_vector(np.ones(n, np.float32), mesh, dA.rows_padded)
        hlo = jax.jit(lambda v: dist_spmv(dA, v)).lower(x).as_text()
        return Built(hlo=hlo, jaxpr=None, predicted=None)

    def model_of(dA):
        vols = spmv_comm_volumes(dA, dA.rows_padded // dA.num_shards, 4)
        return {k: v for k, v in vols.items() if v > 0}

    dA = shard_csr(_banded(n), mesh=mesh)
    built = built_of(dA)
    # Two-sided halo exchange: exactly the two ppermutes the model
    # prices as one exchange of 2*R*halo*itemsize bytes; no gather.
    assert [e["kind"] for e in schedule_of(built)] == \
        ["collective_permute", "collective_permute"]
    assert lowered_volumes(built) == model_of(dA)

    dA = shard_csr(_banded(n), mesh=mesh, force_all_gather=True)
    built = built_of(dA)
    kinds = [e["kind"] for e in schedule_of(built)]
    assert kinds and set(kinds) == {"all_gather"}
    assert lowered_volumes(built) == model_of(dA)


# ------------------------------------- sparsity-aware window declines --
@needs_window
def test_window_decline_keyed_on_density_bucket():
    """ADVICE r5 low, finished: one wide-window matrix must not pin a
    later SAME-LAYOUT but much sparser matrix to all_gather.  Two
    matrices engineered to share an identical ``_Layout`` (same ELL
    width, shards, shape, halo) but sit in different nnz-density
    buckets: the dense one declines; the sparse one still probes and
    wins the window."""
    mesh = make_row_mesh()
    n = 8 * R
    rps = 8

    # Wide: every row has R entries striped across every shard.
    rows1, cols1 = [], []
    for i in range(n):
        for k in range(R):
            rows1.append(i)
            cols1.append((i + k * rps) % n)
    A1 = sp.csr_matrix(
        (np.ones(len(rows1)), (rows1, cols1)), shape=(n, n))

    # Narrow: near-diagonal pairs, one row widened to R entries inside
    # its own shard so the ELL width (and so the layout) matches A1.
    rows2, cols2 = [0] * R, list(range(R))
    for i in range(1, n - 1):
        rows2 += [i, i]
        cols2 += [i, i + 1]
    rows2.append(n - 1)
    cols2.append(n - 1)
    A2 = sp.csr_matrix(
        (np.ones(len(rows2)), (rows2, cols2)), shape=(n, n))

    dA1 = shard_csr(sparse.csr_array(A1), mesh=mesh,
                    force_all_gather=True)
    dA2 = shard_csr(sparse.csr_array(A2), mesh=mesh,
                    force_all_gather=True)
    la1 = _spg._layout_of(dA1)
    la2 = _spg._layout_of(dA2)
    assert la1 == la2, "test precondition: identical layouts"
    b1 = _spg._density_bucket(dA1.nnz_hint, n)
    b2 = _spg._density_bucket(dA2.nnz_hint, n)
    assert b1 != b2, "test precondition: distinct density buckets"

    _spg.reset_window_declines()
    _ = dist_spgemm(dA1, dA1)
    assert _spg.LAST_B_REALIZATION == "all_gather"
    assert len(_spg._WINDOW_DECLINED) > 0

    # Same layout, sparser bucket: the probe must run (and accept).
    probes0 = counters.get("transfer.host_sync.spgemm_window_probe")
    _ = dist_spgemm(dA2, dA2)
    assert (counters.get("transfer.host_sync.spgemm_window_probe")
            == probes0 + 1)
    assert _spg.LAST_B_REALIZATION == "window"

    # Identical density still short-circuits on the cached decline.
    cached0 = counters.get("dist_spgemm.window_decline_cached")
    _ = dist_spgemm(dA1, dA1)
    assert (counters.get("dist_spgemm.window_decline_cached")
            == cached0 + 1)
    _spg.reset_window_declines()


def test_density_bucket_edges():
    assert _spg._density_bucket(0, 100) == -1
    assert _spg._density_bucket(50, 100) == -1       # < 1 per row
    assert _spg._density_bucket(100, 100) == 0
    assert _spg._density_bucket(800, 100) == 3
    assert _spg._density_bucket(100, 0) == -1


# --------------------------------------------- 2-d-block exactness --
needs_grid = pytest.mark.skipif(R < 8, reason="needs the 8-device mesh")


def _random_sym(n, density=0.08, dtype=np.float64, seed=3):
    rng = np.random.default_rng(seed)
    A_sp = sp.random(n, n, density=density, random_state=rng,
                     format="csr", dtype=np.float64)
    A_sp = (A_sp + A_sp.T + 10.0 * sp.eye(n)).tocsr().astype(dtype)
    return sparse.csr_array(A_sp)


@needs_grid
def test_2d_spmv_counters_match_static_prediction():
    from legate_sparse_tpu.parallel import make_grid_mesh

    mesh = make_grid_mesh(2, 4)
    n = 96
    A = _random_sym(n)
    dA = shard_csr(A, mesh=mesh, layout="2d-block")
    assert dA.grid == (2, 4) and dA.layout == "2d-block"
    x = shard_vector(np.ones(n, np.float64), mesh, dA.rows_padded,
                     layout=dA.layout)
    vols = spmv_comm_volumes(dA, dA.rows_padded // dA.num_shards, 8)
    assert set(vols) == {"ppermute", "all_gather", "psum"}
    counters.reset("comm.")
    _ = dist_spmv(dA, x)
    for kind, nbytes in vols.items():
        assert counters.get(f"comm.dist_spmv.{kind}") == 1, kind
        assert counters.get(
            f"comm.dist_spmv.{kind}_bytes") == nbytes, kind
    assert counters.get(
        "comm.layout.2d-block.dist_spmv_bytes") == sum(vols.values())
    # And the 2-D program moves fewer predicted bytes than the 1-D
    # all_gather the same matrix forces at equal device count.
    dA1 = shard_csr(A, mesh=make_row_mesh(), force_all_gather=True)
    vols1 = spmv_comm_volumes(dA1, dA1.rows_padded // 8, 8)
    assert sum(vols.values()) < sum(vols1.values())


@needs_grid
def test_2d_model_matches_lowered_collectives():
    """Anti-circularity for the 2-d-block program, through
    planverify's schedule checker: the lowered HLO carries exactly the
    collectives the ledger prices — one input fixup permute, one
    x-panel all-gather, one reduce-scatter — and their byte volumes
    (ledger convention) match the static model exactly, here at f64."""
    from legate_sparse_tpu.parallel import make_grid_mesh
    from tools.verify.catalog import Built
    from tools.verify.rules import lowered_volumes, schedule_of

    mesh = make_grid_mesh(2, 4)
    n = 96
    dA = shard_csr(_random_sym(n), mesh=mesh, layout="2d-block")
    x = shard_vector(np.ones(n, np.float64), mesh, dA.rows_padded,
                     layout=dA.layout)
    hlo = jax.jit(lambda v: dist_spmv(dA, v)).lower(x).as_text()
    built = Built(hlo=hlo, jaxpr=None, predicted=None)
    assert [e["kind"] for e in schedule_of(built)] == [
        "collective_permute", "all_gather", "reduce_scatter"]
    vols = spmv_comm_volumes(dA, dA.rows_padded // 8, 8)
    assert lowered_volumes(built) == {
        k: v for k, v in vols.items() if v > 0}


@needs_grid
def test_2d_cg_comm_matches_iteration_model():
    from legate_sparse_tpu.parallel import make_grid_mesh

    trace.enable()
    mesh = make_grid_mesh(2, 4)
    n = 96
    dA = shard_csr(_random_sym(n), mesh=mesh, layout="2d-block")
    counters.reset("comm.")
    maxiter = 7
    _, iters = dist_cg(dA, np.ones(n, np.float64), rtol=0.0,
                       maxiter=maxiter, conv_test_iters=5)
    it = int(iters)
    assert it == maxiter
    vols, calls = cg_comm_volumes(dA, 8, it)
    # The SpMV's own psum_scatter merges ADDITIVELY with the solver's
    # 3 scalar psums per iteration — the 2-D regression this guards:
    # an overwrite would drop one or the other from the ledger.
    assert calls["psum"] == (it + 1) + 3 * it
    (span,) = [r for r in obs.records() if r["name"] == "dist_cg"]
    assert span["attrs"]["comm_bytes"] == sum(vols.values())
    spmv_vols = spmv_comm_volumes(dA, dA.rows_padded // 8, 8)
    expect_psum = ((it + 1) * spmv_vols["psum"]
                   + 3 * it * 2 * (8 - 1) * 8)
    assert counters.get("comm.dist_cg.psum_bytes") == expect_psum
    assert counters.get("comm.dist_cg.ppermute_bytes") == (
        (it + 1) * spmv_vols["ppermute"])


@needs_grid
def test_2d_spgemm_counters_match_summa_prediction():
    from legate_sparse_tpu.parallel import make_grid_mesh

    trace.enable()
    mesh = make_grid_mesh(2, 4)
    n = 96
    A = _random_sym(n)
    dA = shard_csr(A, mesh=mesh, layout="2d-block")
    vols, calls = _spg._summa_volumes_2d(dA, dA, dA.grid)
    counters.reset("comm.")
    C = dist_spgemm(dA, dA)
    assert C.grid == (2, 4) and C.layout == "2d-block"
    for kind, nbytes in vols.items():
        assert counters.get(
            f"comm.dist_spgemm.{kind}_bytes") == nbytes, kind
        assert counters.get(
            f"comm.dist_spgemm.{kind}") == calls[kind], kind
    assert counters.get(
        "comm.layout.2d-block.dist_spgemm_bytes") == sum(vols.values())
    evs = [r for r in obs.records()
           if r["name"] == "dist_spgemm.realization"]
    at = evs[-1]["attrs"]
    assert at["choice"] == "2d-panel"
    assert at["predicted_bytes"] == sum(vols.values())
    # Evidence of the win: the SUMMA panels undercut the recorded 1-D
    # all_gather realization of the same product.
    counters.reset("comm.")
    dA1 = shard_csr(A, mesh=make_row_mesh(), force_all_gather=True)
    _ = dist_spgemm(dA1, dA1)
    bytes_1d = sum(
        v for k, v in counters.snapshot().items()
        if k.startswith("comm.dist_spgemm.") and k.endswith("_bytes"))
    assert at["predicted_all_gather_bytes"] > 0
    assert sum(vols.values()) < bytes_1d


@pytest.mark.slow
@needs_mesh
def test_builders_set_nnz_hint():
    from legate_sparse_tpu.parallel import dist_diags

    mesh = make_row_mesh()
    n = 16 * R
    A = _banded(n)
    dA = shard_csr(A, mesh=mesh)
    assert dA.nnz_hint == A.nnz
    dD = dist_diags([4.0, -1.0, -1.0], [0, 1, -1], shape=(n, n),
                    mesh=mesh, dtype=np.float32)
    assert dD.nnz_hint == 3 * n - 2
    C = dist_spgemm(dA, dA)
    assert C.nnz_hint == C.global_nnz > 0
