"""The 11-diagonal band cell at a tiny size: its plain reference is the
dense product, a sound run passes the comparison, and the control and
the faults a band product can have (a diagonal read one column off, a
diagonal left out) fail it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import legate_sparse_tpu
from benchmark.ops import spmv_band
from benchmark.reference import banded
from benchmark.tests.conftest import run_tiny, tiny_cell

ROWS = 1 << 13
SEED = 2 ** 33 + 29


def _cell():
    cell = tiny_cell("spmv-band11")
    cell.config.update(rows=ROWS)
    cell.config["nnz"] = banded.nnz(cell.config)
    return cell


def _dense(cfg, vals):
    n = int(cfg["rows"])
    A = np.zeros((n, n))
    for d, off in enumerate(cfg["offsets"]):
        for i in range(max(0, -off), min(n, n - off)):
            A[i, i + off] = vals[d, i]
    return A


def test_reference_is_the_dense_product():
    cfg = dict(_cell().config, rows=64)
    vals = banded.values(cfg, SEED)
    A = _dense(cfg, vals)
    x = np.random.default_rng(1).random(64)
    assert np.count_nonzero(A) == banded.nnz(cfg) == 64 * 11 - 30
    np.testing.assert_allclose(banded.scipy_operator(cfg, vals) @ x,
                               A @ x, rtol=1e-13)
    np.testing.assert_allclose(
        np.asarray(banded.matvec(jnp.asarray(x, jnp.float32),
                                 jnp.asarray(vals), tuple(cfg["offsets"]),
                                 jnp.float32)), A @ x, rtol=1e-5)
    # The program's own build of the same operator.
    np.testing.assert_array_equal(
        np.asarray(spmv_band.banded_csr(cfg, vals).todense()),
        A.astype(np.float32))


def test_sound_run_is_correct():
    cell = _cell()
    res = run_tiny(cell, seed=SEED)
    assert res["correct"], res
    assert res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "spmv_ms"}
    assert res["checks"]["max_rel_err"]["value"] < 1e-6


def test_control_is_not_correct():
    res = run_tiny(_cell(), seed=SEED, control=True)
    assert "error" not in res
    assert not res["correct"], res["checks"]


def _faulty(cfg, kind):
    """A float32 band product with one diagonal read one column off,
    or left out."""
    offsets = list(cfg["offsets"])
    vals = banded.values(cfg, SEED)
    d = offsets.index(2)
    if kind == "shifted":
        offsets[d] += 1
    else:
        vals[d] = 0.0
    vals = jax.device_put(vals)
    return lambda x: banded.matvec(x, vals, tuple(offsets), jnp.float32)


@pytest.mark.parametrize("kind", ["shifted", "dropped"])
def test_a_wrong_band_is_not_correct(kind, monkeypatch):
    cell = _cell()
    f = _faulty(cell.config, kind)
    monkeypatch.setattr(legate_sparse_tpu.csr_array, "__matmul__",
                        lambda self, x: f(x))
    res = run_tiny(cell, seed=SEED)
    assert "error" not in res
    assert not res["correct"], res["checks"]
    assert res["checks"]["max_rel_err"]["value"] > 1e-2
