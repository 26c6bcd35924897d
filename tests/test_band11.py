"""The reference SpMV microbenchmark's 11-diagonal band (rows x 11,
``examples/common.py`` ``banded_matrix``), built directly from
``(data, indices, indptr)`` with seeded values, through ``A @ x``:
against a float64 product built by ``scipy.sparse.diags`` from the
same per-diagonal values, on the XLA route and on the Pallas route in
interpret mode, with the band detected as exact and built once."""

import numpy as np
import pytest
import scipy.sparse as scsp

import jax.numpy as jnp

import legate_sparse_tpu as sparse
from legate_sparse_tpu import obs
from legate_sparse_tpu.obs import counters

N = 1 << 13
OFFSETS = tuple(range(-5, 6))


def _values(seed: int) -> np.ndarray:
    """(11, N): ``vals[d, i] = A[i, i + OFFSETS[d]]``, uniform on
    [0.5, 1.5), +0.0 out of range."""
    vals = np.random.default_rng(seed).random(
        (len(OFFSETS), N), dtype=np.float32) + np.float32(0.5)
    for d, off in enumerate(OFFSETS):
        if off > 0:
            vals[d, N - off:] = 0.0
        elif off < 0:
            vals[d, :-off] = 0.0
    return vals


def _direct_csr(vals):
    """The CSR as ``banded_matrix`` builds it: column arithmetic, the
    out-of-range slots dropped, int32 indices, int64 indptr."""
    nd = len(OFFSETS)
    cols = (np.tile(np.asarray(OFFSETS, np.int32), N)
            + np.repeat(np.arange(N, dtype=np.int32), nd))
    keep = (cols >= 0) & (cols < N)
    indptr = np.zeros(N + 1, dtype=np.int64)
    np.cumsum(keep.reshape(N, nd).sum(axis=1), out=indptr[1:])
    return sparse.csr_array((vals.T.ravel()[keep], cols[keep], indptr),
                            shape=(N, N))


def _reference(vals):
    return scsp.diags(
        [vals[d, max(0, -k): N - max(0, k)].astype(np.float64)
         for d, k in enumerate(OFFSETS)], OFFSETS, shape=(N, N))


@pytest.mark.parametrize("route", ["xla", "pallas"])
def test_band11_matches_reference(route, monkeypatch):
    if route == "pallas":
        monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIA", "interpret")
        # 8 grid steps at this size, so the neighbour tiles are read.
        monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_TILE", "1024")
    else:
        monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIA", "0")
    vals = _values(29)
    A = _direct_csr(vals)
    assert A.nnz == len(OFFSETS) * N - 30
    x = np.random.default_rng(30).random(N, dtype=np.float32)
    obs.trace.reset()
    before = counters.get("dia.build.count")
    obs.enable()
    try:
        y = np.asarray(A @ jnp.asarray(x))
        y2 = np.asarray(A @ jnp.asarray(x))
    finally:
        obs.disable()
    ref = _reference(vals) @ x.astype(np.float64)
    np.testing.assert_allclose(y, ref, rtol=2e-6, atol=0)
    np.testing.assert_array_equal(y2, y)

    dia_data, offsets, mask = A._get_dia()
    assert offsets == OFFSETS and mask is None
    spans = [r for r in obs.records() if r["name"].startswith("dia.")]
    (build,) = [r for r in spans if r["name"] == "dia.build"]
    assert counters.get("dia.build.count") == before + 1
    assert build["attrs"]["exact"] is True
    assert build["attrs"]["diags"] == len(OFFSETS)
    assert build["attrs"]["nnz"] == A.nnz
    kids = [r["name"] for r in spans if r["depth"] == build["depth"] + 1]
    if route == "pallas":
        assert kids == ["dia.detect", "dia.scatter", "dia.pack"]
        assert build["attrs"]["pack_bytes"] == len(OFFSETS) * N * 4
        (path,) = {r["attrs"]["path"] for r in obs.records()
                   if r["name"] == "spmv"}
        assert path == "dia-pallas"
    else:
        assert kids == ["dia.detect", "dia.scatter"]
        assert build["attrs"]["pack_bytes"] == 0


def test_band11_pack_built_later_records_its_own_build(monkeypatch):
    """A band built for the XLA route and packed later (the Pallas
    route turned on afterwards) records a second build, the pack's."""
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIA", "0")
    A = _direct_csr(_values(31))
    x = jnp.ones(N, jnp.float32)
    A @ x
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIA", "interpret")
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_TILE", "1024")
    obs.trace.reset()
    obs.enable()
    try:
        y = np.asarray(A @ x)
    finally:
        obs.disable()
    spans = [r for r in obs.records() if r["name"].startswith("dia.")]
    assert [r["name"] for r in spans] == ["dia.pack", "dia.build"]
    assert spans[1]["attrs"]["pack_bytes"] == len(OFFSETS) * N * 4
    np.testing.assert_allclose(
        y, _reference(_values(31)) @ np.ones(N), rtol=2e-6)
