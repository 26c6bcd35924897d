# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Distributed banded SpMV through the per-shard Mosaic kernel
(LEGATE_SPARSE_TPU_PALLAS_DIST=interpret on the CPU mesh)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import legate_sparse_tpu as sparse
from legate_sparse_tpu.parallel import shard_csr, dist_spmv
from legate_sparse_tpu.parallel.dist_csr import shard_vector
from legate_sparse_tpu.parallel.mesh import make_row_mesh


@pytest.fixture
def mesh():
    devs = jax.devices("cpu")
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return make_row_mesh(devs[:8])


def _poisson(n_grid, dtype=np.float32):
    n = n_grid * n_grid
    return sparse.diags(
        [-1.0, -1.0, 4.0, -1.0, -1.0],
        [-n_grid, -1, 0, 1, n_grid],
        shape=(n, n), format="csr", dtype=dtype,
    )


@pytest.mark.slow
def test_dist_dia_spmv_pallas_matches(mesh, monkeypatch):
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIST", "interpret")
    A = _poisson(16)
    n = A.shape[0]
    dA = shard_csr(A, mesh=mesh)
    assert dA.dia_data is not None and dA.halo >= 0, "need banded halo mode"
    x = np.linspace(-1.0, 1.0, n).astype(np.float32)
    xs = shard_vector(x, mesh, dA.rows_padded)
    y = np.asarray(dist_spmv(dA, xs))[:n]
    y_ref = A.toscipy() @ x
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)


def test_dist_prepack_built_and_routes_match(mesh, monkeypatch):
    """shard_csr pre-blocks the Mosaic layout once (pdia_*); the Pallas
    route over it matches the XLA shifted-add branch exactly."""
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIST", "interpret")
    A = _poisson(16)
    n = A.shape[0]
    dA = shard_csr(A, mesh=mesh)
    assert dA.pdia_tile > 0 and dA.pdia_data is not None
    assert not hasattr(dA, "pdia_mask")
    assert dA.pdia_data.dtype == jnp.float32
    assert dA.pdia_data.shape[1] == len(dA.dia_offsets)
    x = np.linspace(-2.0, 2.0, n).astype(np.float32)
    xs = shard_vector(x, mesh, dA.rows_padded)
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIST", "0")
    y_xla = np.asarray(dist_spmv(dA, xs))[:n]
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIST", "interpret")
    y_pl = np.asarray(dist_spmv(dA, xs))[:n]
    np.testing.assert_allclose(y_pl, y_xla, rtol=1e-6, atol=1e-6)


def test_dist_prepack_on_builders(mesh, monkeypatch):
    """dist_diags (the memory-lean path) and the banded dist_spgemm
    product also carry the prepack — not just shard_csr."""
    from legate_sparse_tpu.parallel import dist_poisson2d
    from legate_sparse_tpu.parallel.dist_spgemm import dist_spgemm

    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIST", "interpret")
    dA = dist_poisson2d(16, mesh=mesh, dtype=np.float32,
                        materialize_ell=False)
    assert dA.pdia_tile > 0, "dist_diags lost the prepack"
    n = dA.shape[0]
    x = np.linspace(-1.0, 1.0, n).astype(np.float32)
    xs = shard_vector(x, mesh, dA.rows_padded)
    y = np.asarray(dist_spmv(dA, xs))[:n]
    import scipy.sparse as sp

    # True 2-D Poisson: (i, i+1) coupling is zero across grid-row
    # boundaries (unlike the plain 5-diagonal band in _poisson).
    N = 16
    main = np.full(n, 4.0)
    off1 = np.full(n - 1, -1.0)
    off1[np.arange(1, N) * N - 1] = 0.0
    offn = np.full(n - N, -1.0)
    Aref = sp.diags([main, off1, off1, offn, offn],
                    [0, 1, -1, N, -N]).tocsr()
    np.testing.assert_allclose(y, Aref @ x, rtol=1e-5, atol=1e-5)

    dB = shard_csr(_poisson(16), mesh=mesh)
    C = dist_spgemm(dB, dB)
    if C.dia_data is not None:
        assert C.pdia_tile > 0, "banded dist_spgemm product lost prepack"


@pytest.mark.tpu
def test_dist_prepack_kernel_on_chip(monkeypatch):
    """The pre-blocked Mosaic dist kernel lowers and runs on a real
    chip inside shard_map (1-device mesh; ring halo wraps to self and
    must stay masked)."""
    if jax.devices()[0].platform != "tpu":
        pytest.skip("no TPU")
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIST", "1")
    A = _poisson(32)
    n = A.shape[0]
    dA = shard_csr(A, mesh=make_row_mesh(jax.devices()[:1]))
    assert dA.pdia_tile > 0
    x = np.linspace(-1.0, 1.0, n).astype(np.float32)
    xs = shard_vector(x, dA.mesh, dA.rows_padded)
    y = np.asarray(dist_spmv(dA, xs))[:n]
    np.testing.assert_allclose(y, A.toscipy() @ x, rtol=1e-4, atol=1e-4)


def _holey_poisson(n_grid, rng):
    """The 5-point band with 20% of its slots dropped (holes) and
    explicit +0.0, -0.0 and NaN entries among the rest; halves only, so
    every product and sum is exact.  Returns (A, entries)."""
    import scipy.sparse as sp

    n = n_grid * n_grid
    entries = {}
    for off in (-n_grid, -1, 0, 1, n_grid):
        for i in range(max(0, -off), min(n, n - off)):
            if rng.random() > 0.2:
                entries[(i, i + off)] = float(rng.integers(-4, 5) * 0.5)
    entries[(40, 40)] = 0.0
    entries[(41, 41)] = -0.0
    entries[(42, 42)] = np.nan
    keys = sorted(entries)
    A_sp = sp.csr_matrix(
        (np.array([entries[k] for k in keys], np.float32),
         ([k[0] for k in keys], [k[1] for k in keys])), shape=(n, n))
    return sparse.csr_array(A_sp), entries


@pytest.mark.parametrize("band", ["exact", "holey"])
def test_dist_dia_spmv_pallas_ieee_nonfinite(band, mesh, monkeypatch, rng):
    # inf in a halo region another shard's rows never reference must
    # not leak NaN through the ring-wrapped exchange.
    from legate_sparse_tpu import obs
    from legate_sparse_tpu.ops import spmv as spmv_ops

    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIST", "interpret")
    if band == "exact":
        A = _poisson(16)
    else:
        A, entries = _holey_poisson(16, rng)
    n = A.shape[0]
    holes_before = obs.counters.get("pallas.pack.hole_slots")
    dA = shard_csr(A, mesh=mesh)
    assert dA.dia_data is not None and dA.halo >= 0
    x = np.ones(n, np.float32)
    x[0] = np.inf  # wraps to the LAST shard's halo via the ring
    if band == "exact":
        xs = shard_vector(x, mesh, dA.rows_padded)
        y = np.asarray(dist_spmv(dA, xs))[:n]
        y_ref = A.toscipy() @ x
        # Rows referencing column 0 see inf; the last rows (whose ring
        # halo holds the wrapped inf) must NOT.
        np.testing.assert_array_equal(np.isinf(y), np.isinf(y_ref))
        assert np.isfinite(y[-1])
        return
    assert dA.dia_mask is not None and dA.pdia_tile > 0
    in_band = sum(n - abs(o) for o in (-16, -1, 0, 1, 16))
    assert (obs.counters.get("pallas.pack.hole_slots") - holes_before
            == in_band - len(entries))
    x[[40, 41]] = np.inf                      # explicit +0.0 / -0.0 rows
    hole_cols = sorted(c for (r, c) in
                       ((r, r + o) for r in range(60, 200, 7)
                        for o in (-1, 1)) if (r, c) not in entries)
    x[hole_cols[:6]] = np.nan
    x[-1] = np.nan   # wraps to the FIRST shard's halo via the ring
    xs = shard_vector(x, mesh, dA.rows_padded)
    y = np.asarray(dist_spmv(dA, xs))[:n]
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIST", "0")
    y_xla = np.asarray(dist_spmv(dA, xs))[:n]
    y_csr = np.asarray(spmv_ops.csr_spmv_rowids(
        A._data, A._indices, A._get_row_ids(), jnp.asarray(x), n))
    bits = lambda a: np.asarray(a, np.float32).view(np.uint32)
    np.testing.assert_array_equal(bits(y), bits(y_xla))
    np.testing.assert_array_equal(bits(y), bits(y_csr))
    # 0 * inf at the explicit +0.0 and -0.0 entries, as in CSR.
    assert np.isnan(y[40]) and np.isnan(y[41]) and np.isnan(y[42])
    assert np.isfinite(y[n // 2])


# ------------------------------------------------- the in-place halo route
# A forced 1024-row tile puts whole tiles in a CPU-sized shard: 8 shards
# of 1024 rows run one grid step each, of 2048 rows two; 1536 rows is
# not a whole number of tiles and keeps the copied window.
_TILE = 1024
_REACH = 64


def _band(rps, rng, holey=False):
    """An 8-shard band of reach ``_REACH`` with small half-integer
    values, so every product and sum is exact; holey: 20% of its slots
    missing, and an explicit +0.0 and -0.0 on the diagonal of the rows
    ``_zero_rows``."""
    import scipy.sparse as sp

    n = 8 * rps
    rows, cols, vals = [], [], []
    for off in (-_REACH, -1, 0, 1, _REACH):
        r = np.arange(max(0, -off), min(n, n - off))
        if holey:
            r = r[rng.random(r.size) > 0.2]
        rows.append(r)
        cols.append(r + off)
        vals.append(rng.integers(-4, 5, r.size) * 0.5)
    rows, cols, vals = map(np.concatenate, (rows, cols, vals))
    if holey:
        zr = _zero_rows(n)
        keep = ~((rows == cols) & np.isin(rows, zr))
        rows = np.concatenate([rows[keep], zr])
        cols = np.concatenate([cols[keep], zr])
        vals = np.concatenate([vals[keep], [0.0, -0.0]])
    A_sp = sp.csr_matrix((vals.astype(np.float32), (rows, cols)),
                         shape=(n, n))
    return sparse.csr_array(A_sp)


def _zero_rows(n):
    return np.array([n // 2 + 3, n // 2 + 5])


@pytest.fixture
def tiled(monkeypatch):
    """The Pallas dist route in interpret mode at the forced tile."""
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIST", "interpret")
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_TILE", str(_TILE))


def _routes(dA, xs, monkeypatch):
    """y through the Pallas route, then through the XLA branch."""
    y_pl = np.asarray(dist_spmv(dA, xs))
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIST", "0")
    y_xla = np.asarray(dist_spmv(dA, xs))
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIST", "interpret")
    return y_pl, y_xla


def _bits(a):
    """The float32 bits of ``a``, with -0.0 read as +0.0.  A row whose
    every live product is -0.0 (an explicit zero, stored as -0.0 in the
    pack, times a positive x) reads -0.0 from the band kernel on either
    route, because the compiled kernel folds its +0.0 accumulator start
    (XLA in interpret mode, Mosaic on the chip), and +0.0 from the XLA
    branch.  Every other bit, NaN's included, is compared."""
    a = np.asarray(a, np.float32)
    return np.where(a == 0, np.float32(0), a).view(np.uint32)


@pytest.mark.parametrize("band", ["exact", "holey"])
@pytest.mark.parametrize("rps", [1024, 2048, 1536],
                         ids=["one-step", "two-steps", "not-whole-tiles"])
def test_dist_dia_window_routes_match_xla(rps, band, mesh, tiled,
                                          monkeypatch, rng):
    """Both routes of the Pallas shard body equal the XLA shifted-add
    branch bit for bit: in place with one grid step a shard (both edge
    tiles in one step) and with two; copied where the shard is not a
    whole number of tiles."""
    from legate_sparse_tpu.parallel.dist_csr import _dia_window

    A = _band(rps, rng, holey=band == "holey")
    n = A.shape[0]
    dA = shard_csr(A, mesh=mesh)
    assert dA.rows_per_shard == rps and dA.halo == _REACH
    assert dA.pdia_tile == _TILE
    assert (dA.dia_mask is not None) == (band == "holey")
    want = "copied" if rps % _TILE else "in-place"
    assert _dia_window(rps, dA.halo, dA.pdia_tile) == want
    x = (rng.integers(-8, 9, n) * 0.25).astype(np.float32)
    xs = shard_vector(x, mesh, dA.rows_padded)
    y_pl, y_xla = _routes(dA, xs, monkeypatch)
    np.testing.assert_array_equal(_bits(y_pl), _bits(y_xla))
    np.testing.assert_array_equal(y_pl[:n], A.toscipy() @ x)


@pytest.mark.parametrize("band", ["exact", "holey"])
def test_dist_dia_in_place_ieee_nonfinite(band, mesh, tiled, monkeypatch,
                                          rng):
    """A non-finite x in a ring-wrapped halo, which reaches the first
    and last shards' edge tiles, does not reach y on the in-place
    route; a non-finite x an explicit zero multiplies does."""
    A = _band(2048, rng, holey=band == "holey")
    n = A.shape[0]
    dA = shard_csr(A, mesh=mesh)
    x = np.ones(n, np.float32)
    x[0] = np.inf     # the last shard's high edge tile, via the ring
    x[-1] = np.nan    # the first shard's low edge tile, via the ring
    xs = shard_vector(x, mesh, dA.rows_padded)
    y_pl, y_xla = _routes(dA, xs, monkeypatch)
    np.testing.assert_array_equal(_bits(y_pl), _bits(y_xla))
    y_ref = A.toscipy() @ x
    np.testing.assert_array_equal(np.isnan(y_pl[:n]), np.isnan(y_ref))
    np.testing.assert_array_equal(np.isinf(y_pl[:n]), np.isinf(y_ref))
    # Rows whose band never reaches column 0 or n-1 stay finite.
    assert np.isfinite(y_pl[_REACH + 1: n - _REACH - 1]).all()
    if band == "holey":
        # The explicit +0.0 / -0.0 on the diagonal times inf is NaN.
        zr = _zero_rows(n)
        x[zr] = np.inf
        xs = shard_vector(x, mesh, dA.rows_padded)
        y_pl, y_xla = _routes(dA, xs, monkeypatch)
        np.testing.assert_array_equal(_bits(y_pl), _bits(y_xla))
        assert np.isnan(y_pl[zr]).all()


@pytest.mark.parametrize("rps,route", [(2048, "in_place"),
                                        (1536, "copied")])
def test_dist_dia_window_counters(rps, route, mesh, tiled, rng):
    """Building the shard body counts its route once a program; the
    ``dist_spmv`` span names it beside ``path="dia-pallas"``."""
    from legate_sparse_tpu import obs
    from legate_sparse_tpu.parallel.dist_csr import _dia_spmv_pallas_fn

    A = _band(rps, rng)
    dA = shard_csr(A, mesh=mesh)
    xs = shard_vector(np.ones(A.shape[0], np.float32), mesh,
                      dA.rows_padded)
    _dia_spmv_pallas_fn.cache_clear()
    keys = ("dist_csr.dia_window.in_place", "dist_csr.dia_window.copied")
    before = {k: obs.counters.get(k) for k in keys}
    obs.enable()
    try:
        obs.reset()
        for _ in range(2):
            dist_spmv(dA, xs).block_until_ready()
        spans = [r for r in obs.records()
                 if r.get("type") == "span" and r["name"] == "dist_spmv"]
    finally:
        obs.disable()
    grew = {k: obs.counters.get(k) - before[k] for k in keys}
    assert grew == {f"dist_csr.dia_window.{r}": int(r == route)
                    for r in ("in_place", "copied")}
    assert len(spans) == 2
    for sp in spans:
        assert sp["attrs"]["path"] == "dia-pallas"
        assert sp["attrs"]["window"] == route.replace("_", "-")


def _long_copies(jaxpr, length):
    """The ``concatenate`` and ``pad`` equations of ``jaxpr`` and its
    sub-jaxprs, outside any Pallas kernel, whose output holds at least
    ``length`` elements."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("concatenate", "pad"):
            if any(np.prod(v.aval.shape) >= length for v in eqn.outvars):
                found.append(eqn)
        if name == "pallas_call":
            continue
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _long_copies(sub, length)
    return found


@pytest.mark.parametrize("rps,route", [(2048, "in-place"),
                                        (1536, "copied")])
def test_dist_dia_in_place_builds_no_window(rps, route, mesh, tiled, rng):
    """On the in-place route the jitted shard body holds no
    concatenate or pad as long as the shard's rows; the copied route,
    the control, holds the window's."""
    from legate_sparse_tpu.parallel.dist_csr import _dia_spmv_pallas_fn

    A = _band(rps, rng)
    dA = shard_csr(A, mesh=mesh)
    xs = shard_vector(np.ones(A.shape[0], np.float32), mesh,
                      dA.rows_padded)
    fn = _dia_spmv_pallas_fn(dA.mesh, dA.dia_offsets, dA.halo, rps,
                             dA.pdia_tile, True)
    copies = _long_copies(jax.make_jaxpr(fn)(dA.pdia_data, xs).jaxpr, rps)
    if route == "in-place":
        assert copies == []
    else:
        assert {e.primitive.name for e in copies} >= {"concatenate"}


def test_dist_dia_in_place_diagonal(mesh, tiled, monkeypatch, rng):
    """A diagonal matrix has no halo: the in-place route exchanges
    nothing, its edge tiles are all zero, and it still matches."""
    import scipy.sparse as sp

    from legate_sparse_tpu.parallel.dist_csr import _dia_window

    n = 8 * 2048
    d = (rng.integers(-4, 5, n) * 0.5).astype(np.float32)
    A = sparse.csr_array(sp.diags([d], [0]).tocsr())
    dA = shard_csr(A, mesh=mesh)
    assert dA.halo == 0 and dA.dia_offsets == (0,)
    assert _dia_window(dA.rows_per_shard, dA.halo, dA.pdia_tile) == (
        "in-place")
    x = (rng.integers(-8, 9, n) * 0.25).astype(np.float32)
    xs = shard_vector(x, mesh, dA.rows_padded)
    y_pl, y_xla = _routes(dA, xs, monkeypatch)
    np.testing.assert_array_equal(_bits(y_pl), _bits(y_xla))
    np.testing.assert_array_equal(y_pl[:n], d * x)
