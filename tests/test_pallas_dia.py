# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Differential tests for the Pallas DIA SpMV kernel (interpret mode).

The exact kernel logic (roll-based shifts, row-aligned layout, boundary
validity, hole masks) runs on CPU via ``interpret=True`` — the same
discipline as the reference testing its CUDA leaf tasks through the
integration suite, but at kernel granularity.
"""

import numpy as np
import pytest
import scipy.sparse as scsp

import jax.numpy as jnp

import legate_sparse_tpu as sparse
from legate_sparse_tpu.ops import pallas_dia


def _spmv_via_pallas(A, x):
    """Run A @ x through the pallas kernel in interpret mode."""
    dia = A._get_dia()
    assert dia is not None, "matrix must be band-detected"
    dia_data, offsets, mask = dia
    packed = pallas_dia.pack_band(dia_data, offsets, A.shape, mask=mask)
    assert packed is not None, "kernel must support this band"
    return np.asarray(
        pallas_dia.pallas_dia_spmv(
            packed.rdata, jnp.asarray(x), packed.offsets,
            packed.shape, packed.tile, interpret=True,
        )
    )


def _banded(n, offsets, rng, dtype=np.float32, m=None):
    m = n if m is None else m
    diags = [rng.standard_normal(max(n, m)).astype(dtype) for _ in offsets]
    A_sp = scsp.diags(diags, offsets, shape=(n, m), format="csr",
                      dtype=dtype)
    return sparse.csr_array(A_sp), A_sp


@pytest.mark.parametrize("n", [64, 1000, 5000])
@pytest.mark.parametrize("offsets", [(-1, 0, 1), (-5, -1, 0, 1, 5),
                                     (0,), (-37, 2)])
def test_exact_band_matches_scipy(n, offsets, rng):
    A, A_sp = _banded(n, list(offsets), rng)
    x = rng.standard_normal(n).astype(np.float32)
    y = _spmv_via_pallas(A, x)
    np.testing.assert_allclose(y, A_sp @ x, rtol=2e-5, atol=2e-5)


def test_large_offsets_multirow_shift(rng):
    # Offsets beyond one lane row (|off| > 128) exercise the sublane
    # (q) component of the shift decomposition.
    n = 4096
    offsets = [-1030, -129, -128, -127, 0, 127, 128, 129, 1030]
    A, A_sp = _banded(n, offsets, rng)
    x = rng.standard_normal(n).astype(np.float32)
    y = _spmv_via_pallas(A, x)
    np.testing.assert_allclose(y, A_sp @ x, rtol=2e-5, atol=2e-5)


def test_holey_band_mask(rng):
    # diags().tocsr() drops interior zeros -> holes, +0.0 in the pack.
    n = 600
    main = rng.standard_normal(n).astype(np.float32)
    off1 = rng.standard_normal(n - 1).astype(np.float32)
    off1[::7] = 0.0
    A_sp = scsp.diags([main, off1, off1], [0, 1, -1], format="csr")
    A_sp.eliminate_zeros()
    A = sparse.csr_array(A_sp)
    dia = A._get_dia()
    assert dia is not None and dia[2] is not None, "expect holey band"
    x = rng.standard_normal(n).astype(np.float32)
    y = _spmv_via_pallas(A, x)
    np.testing.assert_allclose(y, A_sp @ x, rtol=2e-5, atol=2e-5)


def test_holey_band_ieee_nonfinite_x(rng):
    # A hole must never multiply x: an inf parked on a hole column in a
    # row that has no entry there must not propagate NaN into that row.
    n = 256
    main = np.ones(n, np.float32)
    off1 = np.ones(n - 1, np.float32)
    off1[10] = 0.0  # hole at (10, 11)
    A_sp = scsp.diags([main, off1], [0, 1], format="csr")
    A_sp.eliminate_zeros()
    A = sparse.csr_array(A_sp)
    x = np.ones(n, np.float32)
    x[11] = np.inf
    y = _spmv_via_pallas(A, x)
    y_ref = A_sp @ x
    # Row 10 references only column 10 -> finite.
    assert np.isfinite(y[10]), y[10]
    assert y[10] == y_ref[10]
    # Rows 11 (diag) and 10's neighbors referencing column 11 see inf.
    assert np.isinf(y[11])


def test_boundary_edges_zeroed(rng):
    # First/last rows: shifts reach outside [0, n) and must contribute
    # exactly zero even though the clamped neighbor tiles hold real
    # (finite) x values.
    n = 300
    A, A_sp = _banded(n, [-2, 0, 3], rng)
    x = rng.standard_normal(n).astype(np.float32)
    y = _spmv_via_pallas(A, x)
    np.testing.assert_allclose(y, A_sp @ x, rtol=2e-5, atol=2e-5)


def test_rectangular_shapes(rng):
    for (n, m) in [(200, 300), (300, 200)]:
        A, A_sp = _banded(n, [-1, 0, 1], rng, m=m)
        x = rng.standard_normal(m).astype(np.float32)
        y = _spmv_via_pallas(A, x)
        np.testing.assert_allclose(y, A_sp @ x, rtol=2e-5, atol=2e-5)


def test_bfloat16_supported(rng):
    n = 512
    diags = [np.ones(n, np.float32), np.full(n, 0.5, np.float32)]
    A_sp = scsp.diags(diags, [0, 1], shape=(n, n), format="csr")
    A = sparse.csr_array(A_sp).astype(jnp.bfloat16)
    x = jnp.ones((n,), jnp.bfloat16)
    dia = A._get_dia()
    dia_data, offsets, mask = dia
    packed = pallas_dia.pack_band(dia_data, offsets, A.shape, mask=mask)
    assert packed is not None
    y = np.asarray(
        pallas_dia.pallas_dia_spmv(
            packed.rdata, x, packed.offsets, packed.shape,
            packed.tile, interpret=True,
        ).astype(jnp.float32)
    )
    y_ref = np.asarray(A_sp @ np.ones(n, np.float32))
    np.testing.assert_allclose(y, y_ref, rtol=2e-2)


def test_f64_unsupported():
    assert pallas_dia.supported((0, 1), np.float64) is None


def test_band_reach_cap():
    assert pallas_dia.supported((-(1 << 20), 0), np.float32) is None
    assert pallas_dia.choose_tile(1 << 16) == 1 << 16


def test_dispatch_interpret_mode(rng, monkeypatch):
    # csr dot routes through the pallas kernel when forced to interpret
    # mode, and matches the XLA path bit-for-bit on the same input.
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIA", "interpret")
    n = 1024
    A, A_sp = _banded(n, [-1, 0, 1], rng)
    x = rng.standard_normal(n).astype(np.float32)
    y = np.asarray(A @ jnp.asarray(x))
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIA", "0")
    A2 = sparse.csr_array(A_sp)
    y_xla = np.asarray(A2 @ jnp.asarray(x))
    np.testing.assert_allclose(y, y_xla, rtol=1e-6, atol=1e-6)


# ---------------- SpMM (dense multi-RHS) variant ----------------

def _spmm_via_pallas(A, X):
    dia = A._get_dia()
    assert dia is not None
    dia_data, offsets, mask = dia
    packed = pallas_dia.pack_band(dia_data, offsets, A.shape, mask=mask)
    assert packed is not None
    tile = pallas_dia._spmm_tile(packed, X.shape[1])
    assert tile is not None
    return np.asarray(
        pallas_dia.pallas_dia_spmm(
            packed.rdata, jnp.asarray(X), packed.offsets,
            packed.shape, tile, interpret=True,
        )
    )


@pytest.mark.parametrize("k", [1, 3, 32])
def test_spmm_exact_band(k, rng):
    n = 700
    A, A_sp = _banded(n, [-2, 0, 1], rng)
    X = rng.standard_normal((n, k)).astype(np.float32)
    Y = _spmm_via_pallas(A, X)
    np.testing.assert_allclose(Y, A_sp @ X, rtol=2e-5, atol=2e-5)


def test_spmm_holey_band_mask(rng):
    n = 400
    main = rng.standard_normal(n).astype(np.float32)
    off1 = rng.standard_normal(n - 1).astype(np.float32)
    off1[::5] = 0.0
    A_sp = scsp.diags([main, off1], [0, 1], format="csr")
    A_sp.eliminate_zeros()
    A = sparse.csr_array(A_sp)
    assert A._get_dia()[2] is not None
    X = rng.standard_normal((n, 4)).astype(np.float32)
    Y = _spmm_via_pallas(A, X)
    np.testing.assert_allclose(Y, A_sp @ X, rtol=2e-5, atol=2e-5)


def test_spmm_large_offsets(rng):
    n = 4096
    A, A_sp = _banded(n, [-1100, 0, 1100], rng)
    X = rng.standard_normal((n, 8)).astype(np.float32)
    Y = _spmm_via_pallas(A, X)
    np.testing.assert_allclose(Y, A_sp @ X, rtol=2e-5, atol=2e-5)


def test_spmm_dispatch_interpret(rng, monkeypatch):
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIA", "interpret")
    n = 512
    A, A_sp = _banded(n, [-1, 0, 1], rng)
    X = rng.standard_normal((n, 5)).astype(np.float32)
    Y = np.asarray(A @ jnp.asarray(X))
    np.testing.assert_allclose(Y, A_sp @ X, rtol=2e-5, atol=2e-5)


# ---------------- banded SpGEMM variant ----------------

def _exact_band(n, offsets, rng, m=None):
    """Band with every in-bounds slot explicit (no holes)."""
    m = n if m is None else m
    diags = []
    for o in offsets:
        vals = rng.standard_normal(max(n, m)).astype(np.float32)
        vals[vals == 0] = 1.0
        diags.append(vals)
    A_sp = scsp.diags(diags, offsets, shape=(n, m), format="csr",
                      dtype=np.float32)
    return sparse.csr_array(A_sp), A_sp


def _spgemm_via_pallas(A, B):
    from legate_sparse_tpu.ops.dia_ops import band_product_offsets

    da, db = A._get_dia(), B._get_dia()
    assert da is not None and da[2] is None
    assert db is not None and db[2] is None
    offs_c = band_product_offsets(da[1], db[1])
    tile = pallas_dia._spgemm_tile(db[1], len(da[1]), len(db[1]),
                                   len(offs_c), da[0].dtype)
    assert tile is not None
    return np.asarray(
        pallas_dia.pallas_dia_spgemm(
            da[0], db[0], da[1], db[1], offs_c, A.shape, B.shape,
            tile, interpret=True,
        )
    ), offs_c


def _dense_from_band(Cd, offs_c, shape):
    out = np.zeros(shape)
    m, n = shape
    for d, o in enumerate(offs_c):
        for j in range(max(0, o), min(n, m + o)):
            out[j - o, j] = Cd[d, j]
    return out


@pytest.mark.parametrize("offsets", [(-1, 0, 1), (-3, 0, 2), (0,)])
def test_spgemm_band_matches_scipy(offsets, rng):
    n = 500
    A, A_sp = _exact_band(n, list(offsets), rng)
    B, B_sp = _exact_band(n, [-2, 0, 1], rng)
    Cd, offs_c = _spgemm_via_pallas(A, B)
    C_ref = (A_sp @ B_sp).toarray()
    np.testing.assert_allclose(_dense_from_band(Cd, offs_c, (n, n)),
                               C_ref, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_spgemm_band_large_offsets(rng):
    n = 4096
    A, A_sp = _exact_band(n, [-640, 0, 640], rng)
    B, B_sp = _exact_band(n, [-640, 0, 640], rng)
    Cd, offs_c = _spgemm_via_pallas(A, B)
    C_ref = (A_sp @ B_sp).toarray()
    np.testing.assert_allclose(_dense_from_band(Cd, offs_c, (n, n)),
                               C_ref, rtol=2e-4, atol=2e-4)


def test_spgemm_band_rectangular(rng):
    A, A_sp = _exact_band(300, [-1, 0], rng, m=400)
    B, B_sp = _exact_band(400, [0, 2], rng, m=350)
    Cd, offs_c = _spgemm_via_pallas(A, B)
    C_ref = (A_sp @ B_sp).toarray()
    np.testing.assert_allclose(
        _dense_from_band(Cd, offs_c, (300, 350)), C_ref,
        rtol=2e-4, atol=2e-4)


def test_spgemm_dispatch_interpret(rng, monkeypatch):
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIA", "interpret")
    n = 600
    A, A_sp = _exact_band(n, [-1, 0, 1], rng)
    C = A @ A
    C_ref = (A_sp @ A_sp).tocsr()
    np.testing.assert_allclose(C.toscipy().toarray(), C_ref.toarray(),
                               rtol=2e-4, atol=2e-4)


def test_dia_array_dispatch_interpret(rng, monkeypatch):
    # dia_array.dot routes through the pallas kernel too (same
    # dispatch as csr's banded path).
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIA", "interpret")
    n = 800
    data = rng.standard_normal((3, n)).astype(np.float32)
    A = sparse.dia_array((jnp.asarray(data), jnp.asarray([-1, 0, 2])),
                         shape=(n, n))
    A_sp = scsp.dia_array((data, [-1, 0, 2]), shape=(n, n))
    x = rng.standard_normal(n).astype(np.float32)
    y = np.asarray(A @ jnp.asarray(x))
    np.testing.assert_allclose(y, A_sp @ x, rtol=2e-5, atol=2e-5)
    assert A._pack not in (None, False)
    X = rng.standard_normal((n, 5)).astype(np.float32)
    Y = np.asarray(A @ jnp.asarray(X))
    np.testing.assert_allclose(Y, A_sp @ X, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("tile", [2048, 8192])
def test_tile_override_matches_default(tile, rng, monkeypatch):
    # LEGATE_SPARSE_TPU_PALLAS_TILE changes the grid length (fault
    # isolation) and VMEM working set (tuning); results must be
    # identical to the default tile.
    n = 1 << 13
    offsets = (-5, -1, 0, 1, 5)
    A, A_sp = _banded(n, offsets, rng)
    x = rng.standard_normal(n).astype(np.float32)
    ref = _spmv_via_pallas(A, x)
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_TILE", str(tile))
    dia_data, offs, mask = A._get_dia()
    packed = pallas_dia.pack_band(dia_data, offs, A.shape, mask=mask)
    assert packed is not None and packed.tile == tile
    got = np.asarray(pallas_dia.pallas_dia_spmv(
        packed.rdata, jnp.asarray(x), packed.offsets,
        packed.shape, packed.tile, interpret=True))
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)


def test_tile_override_ignored_when_too_small(rng, monkeypatch):
    # An override below the band reach must not break the kernel: the
    # auto-grown tile wins.
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_TILE", "1024")
    assert pallas_dia.choose_tile(5000) == pallas_dia.TILE_MIN


def test_tile_override_over_vmem_budget_degrades_to_auto(monkeypatch):
    # A forced tile that blows the VMEM budget must degrade to the
    # auto tile with a warning, not silently disable the kernel.
    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_TILE",
                       str(pallas_dia.TILE_MAX))
    offsets = tuple(range(-100, 101))       # 201 diagonals
    tile = pallas_dia.supported(offsets, np.float32)
    assert tile == pallas_dia.TILE_MIN


# ---------------- holes carried in the sign of zero ----------------

IEEE_N = 2600                   # three 1024-row tiles, the last partial
IEEE_OFFSETS = (-3, -1, 0, 1, 4)


def _ieee_band(rng):
    """A holey band whose explicit entries include +0.0, -0.0 and NaN,
    and an x with inf/NaN at hole columns and at the columns an
    out-of-range reach reads through the clamped neighbour tiles (run
    at a forced tile of 1024).  Returns (A, A_sp, x, slots): ``slots``
    names the rows the checks below read."""
    n = IEEE_N
    rows, cols, vals = [], [], []
    for off in IEEE_OFFSETS:
        i = np.arange(max(0, -off), min(n, n - off))
        keep = rng.random(i.size) > 0.2
        rows.append(i[keep])
        cols.append(i[keep] + off)
        # Halves and quarters: every product and sum is exact, so bits
        # compare the semantics, not one order of rounding.
        vals.append((rng.integers(-4, 5, int(keep.sum())) * 0.5
                     ).astype(np.float32))
    r, c, v = (np.concatenate(a) for a in (rows, cols, vals))
    entries = dict(zip(zip(r.tolist(), c.tolist()), v.tolist()))
    # Row 100: an explicit +0.0 on the diagonal; row 200: an explicit
    # -0.0; row 400: an explicit NaN; row 300: a hole at (300, 301),
    # its other slots finite entries.
    entries[(100, 100)] = 0.0
    entries[(200, 200)] = -0.0
    entries[(400, 400)] = np.nan
    for off in IEEE_OFFSETS:
        entries[(300, 300 + off)] = 0.5
    del entries[(300, 301)]
    keys = sorted(entries)
    r = np.array([k[0] for k in keys])
    c = np.array([k[1] for k in keys])
    v = np.array([entries[k] for k in keys], np.float32)
    A_sp = scsp.csr_matrix((v, (r, c)), shape=(n, n))
    A = sparse.csr_array(A_sp)
    x = (rng.integers(-4, 5, n) * 0.25).astype(np.float32)
    x[[297, 299, 300, 304]] = 1.0
    x[[100, 200, 301]] = np.inf           # explicit +-0.0 rows, a hole
    holes = [(i, i + o) for o in IEEE_OFFSETS for i in range(0, n, 37)
             if 0 <= i + o < n and (i, i + o) not in entries]
    for i, (_, col) in enumerate(holes[:12]):
        x[col] = np.inf if i % 2 else np.nan
    # What rows 0-2 (negative reach) and the partial tile's last rows
    # (positive reach) would read through the clamped neighbours.
    x[1021:1024] = np.inf
    x[2048:2051] = np.nan
    return A, A_sp, x


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


def _csr_reference(A, x):
    from legate_sparse_tpu.ops import spmv as spmv_ops

    return np.asarray(spmv_ops.csr_spmv_rowids(
        A._data, A._indices, A._get_row_ids(), jnp.asarray(x),
        A.shape[0]))


def test_holey_band_ieee_bit_for_bit(rng, monkeypatch):
    from legate_sparse_tpu.ops import dia_ops

    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_TILE", "1024")
    A, A_sp, x = _ieee_band(rng)
    dia_data, offsets, mask = A._get_dia()
    assert mask is not None, "expect a holey band"
    packed = pallas_dia.pack_band(dia_data, offsets, A.shape, mask=mask)
    assert packed.tile == 1024
    y = np.asarray(pallas_dia.pallas_dia_spmv(
        packed.rdata, jnp.asarray(x), packed.offsets, packed.shape,
        packed.tile, interpret=True))
    ref = np.asarray(dia_ops.dia_spmv_masked(
        dia_data, mask, jnp.asarray(x), offsets, A.shape))
    np.testing.assert_array_equal(_bits(y), _bits(ref))
    np.testing.assert_array_equal(_bits(y), _bits(_csr_reference(A, x)))
    # An explicit +0.0 or -0.0 against inf is NaN, as in CSR; so is an
    # explicit NaN; a hole against inf contributes exactly 0.
    assert np.isnan(y[100]) and np.isnan(y[200]) and np.isnan(y[400])
    assert np.isfinite(y[300]) and y[300] == np.float32(0.5 * 4)
    assert np.isfinite(y[:3]).any()


@pytest.mark.parametrize("k", [1, 3])
def test_holey_band_ieee_spmm_bit_for_bit(k, rng, monkeypatch):
    from legate_sparse_tpu.ops import dia_ops

    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_TILE", "1024")
    A, A_sp, x = _ieee_band(rng)
    X = np.stack([np.roll(x, 5 * j) for j in range(k)], axis=1)
    dia_data, offsets, mask = A._get_dia()
    packed = pallas_dia.pack_band(dia_data, offsets, A.shape, mask=mask)
    tile = pallas_dia._spmm_tile(packed, k)
    Y = np.asarray(pallas_dia.pallas_dia_spmm(
        packed.rdata, jnp.asarray(X), packed.offsets, packed.shape, tile,
        interpret=True))
    ref = np.asarray(dia_ops.dia_spmm_masked(
        dia_data, mask, jnp.asarray(X), offsets, A.shape))
    np.testing.assert_array_equal(_bits(Y), _bits(ref))
    for j in range(k):
        np.testing.assert_array_equal(
            _bits(Y[:, j]), _bits(_csr_reference(A, X[:, j])))
    assert np.isnan(Y[100, 0]) and np.isfinite(Y[300, 0])


def test_pack_encodes_entries_in_sign_of_zero(rng):
    A, A_sp, _ = _ieee_band(rng)
    dia_data, offsets, mask = A._get_dia()
    packed = pallas_dia.pack_band(dia_data, offsets, A.shape, mask=mask)
    arrays = [getattr(packed, s) for s in packed.__slots__
              if hasattr(getattr(packed, s), "dtype")]
    assert [a.dtype for a in arrays] == [np.dtype(np.float32)]
    bits = _bits(packed.rdata).reshape(len(offsets), -1)
    coo = A_sp.tocoo()
    live = np.zeros_like(bits, dtype=bool)
    d_of = {o: d for d, o in enumerate(offsets)}
    live[[d_of[o] for o in (coo.col - coo.row)], coo.row] = True
    np.testing.assert_array_equal(bits != 0, live)
    # Values survive, with +0.0 stored as -0.0.
    d0 = d_of[0]
    assert bits[d0, 100] == np.float32(-0.0).view(np.uint32)
    assert bits[d0, 200] == np.float32(-0.0).view(np.uint32)
    assert np.isnan(packed.rdata.reshape(len(offsets), -1)[d0, 400])


def test_pack_counts_hole_slots():
    from legate_sparse_tpu import obs

    N = 16
    A = sparse.diags([-1.0, -1.0, 4.0, -1.0, -1.0], [-N, -1, 0, 1, N],
                     shape=(N * N, N * N), format="csr", dtype=np.float32)
    main = np.full(N * N, 4.0, np.float32)
    off1 = np.full(N * N - 1, -1.0, np.float32)
    off1[np.arange(1, N) * N - 1] = 0.0      # grid-row boundaries
    offn = np.full(N * N - N, -1.0, np.float32)
    P = sparse.csr_array(scsp.diags(
        [offn, off1, main, off1, offn], [-N, -1, 0, 1, N],
        format="csr", dtype=np.float32))     # drops the boundary zeros
    for M, holes in ((A, 0), (P, 2 * (N - 1))):
        dia_data, offsets, mask = M._get_dia()
        before = obs.counters.get("pallas.pack.hole_slots")
        assert pallas_dia.pack_band(dia_data, offsets, M.shape,
                                    mask=mask) is not None
        assert obs.counters.get("pallas.pack.hole_slots") == before + holes


def test_pallas_spans_carry_stream_bytes(rng, monkeypatch):
    from legate_sparse_tpu import obs

    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIA", "interpret")
    n = 1500
    A, A_sp = _banded(n, [-2, 0, 1], rng)
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    X = jnp.asarray(rng.standard_normal((n, 3)).astype(np.float32))
    was = obs.enabled()
    obs.enable()
    try:
        obs.reset()
        A @ x
        A @ X
        recs = {r["name"]: r for r in obs.records()
                if r["name"] in ("pallas.spmv", "pallas.spmm")}
    finally:
        if not was:
            obs.disable()
    rows_pad = A._get_dia_pack().rdata.shape[1] * pallas_dia.L
    assert recs["pallas.spmv"]["attrs"]["bytes"] == rows_pad * 4 * (3 + 4)
    assert recs["pallas.spmm"]["attrs"]["bytes"] == rows_pad * 4 * (3 + 12)


def test_cell_bands_keep_their_tile():
    # The 5-point bands of the 2048^2 grid (one chip) and of the 4096^2
    # grid's per-shard window (offsets + halo on four chips) keep the
    # 2^14-row tile now that the band streams no mask.
    assert pallas_dia.supported((-2048, -1, 0, 1, 2048),
                                np.float32) == 1 << 14
    halo = 4096
    assert pallas_dia.supported(
        tuple(o + halo for o in (-4096, -1, 0, 1, 4096)),
        np.float32) == 1 << 14
