# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""diags / dia_array tests (mirrors reference ``test_diags.py`` and the
dia_array transpose/tocsr paths)."""

import jax
import numpy as np
import pytest
import scipy.sparse as scsp

import legate_sparse_tpu as sparse


@pytest.mark.parametrize(
    "diagonals,offsets,shape",
    [
        ([[1, 2, 3, 4]], [0], None),
        ([[1, 2, 3], [4, 5, 6, 7], [8, 9, 10]], [-1, 0, 1], None),
        ([[1, 2, 3, 4, 5]], [2], (7, 7)),
        ([[1] * 6, [2] * 6], [0, -1], (7, 6)),
    ],
)
def test_diags_matches_scipy(diagonals, offsets, shape):
    ours = sparse.diags(diagonals, offsets, shape=shape, format="csr",
                        dtype=np.float64)
    theirs = scsp.diags(diagonals, offsets, shape=shape, format="csr",
                        dtype=np.float64)
    np.testing.assert_allclose(np.asarray(ours.todense()), theirs.todense())


def test_diags_scalar_broadcast():
    ours = sparse.diags([1, -2, 1], [-1, 0, 1], shape=(8, 8), format="csr")
    theirs = scsp.diags([1, -2, 1], [-1, 0, 1], shape=(8, 8), format="csr")
    np.testing.assert_allclose(np.asarray(ours.todense()), theirs.todense())


def test_diags_dia_format():
    ours = sparse.diags([[1, 2, 3, 4], [4, 5, 6]], [0, 1], shape=(4, 4))
    assert ours.format == "dia"
    theirs = scsp.diags([[1, 2, 3, 4], [4, 5, 6]], [0, 1], shape=(4, 4))
    np.testing.assert_allclose(np.asarray(ours.todense()), theirs.todense())


def test_dia_transpose():
    d = sparse.diags([[1, 2, 3, 4], [5, 6, 7, 8]], [0, -1], shape=(5, 4))
    s = scsp.diags([[1, 2, 3, 4], [5, 6, 7, 8]], [0, -1], shape=(5, 4))
    np.testing.assert_allclose(
        np.asarray(d.T.todense()), s.T.todense()
    )


def test_dia_nnz():
    d = sparse.diags([1, 1], [0, 2], shape=(6, 6))
    s = scsp.diags([np.ones(6), np.ones(4)], [0, 2], shape=(6, 6))
    assert d.nnz == s.nnz


def test_dia_tocsr_explicit_zero_drop():
    # tocsr drops explicit zeros from the stored diagonals (in-band zeros).
    d = sparse.dia_array(
        (np.array([[1.0, 0.0, 3.0]]), np.array([0])), shape=(3, 3)
    )
    c = d.tocsr()
    assert c.nnz == 2


def test_eye_identity():
    np.testing.assert_allclose(
        np.asarray(sparse.identity(5, format="csr").todense()), np.eye(5)
    )
    np.testing.assert_allclose(
        np.asarray(sparse.eye(4, 6, k=1, format="csr").todense()),
        np.eye(4, 6, k=1),
    )


def test_diags_integer_input_casts_to_float():
    # scipy.sparse.diags casts integer diagonals to float64; keeping
    # int64 made A @ x raise (integer dtypes are gated out of the
    # kernels, same as the reference).  Platform float policy applies.
    ours = sparse.diags([1, -2, 1], [-1, 0, 1], shape=(5, 5), format="csr")
    assert np.issubdtype(ours.dtype, np.floating)
    y = np.asarray(ours @ np.ones(5, dtype=ours.dtype))
    np.testing.assert_allclose(y, [-1.0, 0.0, 0.0, 0.0, -1.0])


_COMPILES = []


def _count_compile(event, secs, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES.append(secs)


jax.monitoring.register_event_duration_secs_listener(_count_compile)


@pytest.mark.parametrize(
    "offsets,shape,hole",
    [
        (tuple(range(-5, 6)), (4099, 4099), False),   # the bench band
        ((-3, 0, 2), (1031, 1031), True),             # explicit zeros
        ((-2, 0, 7), (1033, 1500), False),            # wide
        ((-7, 0, 2), (1500, 1037), False),            # tall
    ],
)
def test_diags_to_csr_is_two_programs(offsets, shape, hole):
    # The user's band build (bench.py _banded_config): a handful of
    # compiled programs, not one per eager op — on the chip each
    # compile costs ~2 s and the eager DIA->CSR path compiled ~60.
    rows, cols = shape
    rng = np.random.default_rng(len(offsets) + rows)
    diagonals = []
    for o in offsets:
        n = min(rows + min(o, 0), cols - max(o, 0))
        d = rng.random(n).astype(np.float32) + np.float32(0.5)
        if hole:
            d[::5] = 0.0
        diagonals.append(d)
    before = len(_COMPILES)
    ours = sparse.diags(diagonals, list(offsets), shape=shape,
                        format="csr", dtype=np.float32)
    assert len(_COMPILES) - before <= 2
    theirs = scsp.diags(diagonals, list(offsets), shape=shape,
                        format="csr", dtype=np.float32)
    theirs.eliminate_zeros()
    np.testing.assert_array_equal(np.asarray(ours.indptr), theirs.indptr)
    np.testing.assert_array_equal(np.asarray(ours.indices), theirs.indices)
    np.testing.assert_array_equal(np.asarray(ours.data), theirs.data)
