"""The comparisons that decide ``correct`` fail what they must: the
control (the plain reference one precision below, in the program's
place) and the faults a cell can have, planted under the timed path.
The harness's look for a chip is skipped; the rest of a run is not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.tests.conftest import run_tiny, tiny_cell

CELLS = ["pde2d-cg", "pde2d-spmv", "bfs-graph500", "pde2d-cg-4chip"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    res = run_tiny(tiny_cell(name), control=True)
    assert "error" not in res
    assert not res["correct"], res["checks"]


def _unchanged(kind):
    # The state the operation starts from, returned as its answer.
    return {"vector": lambda out, inp: jnp.zeros_like(out),
            "levels": lambda out, inp: np.where(
                np.arange(out.size) == inp, 0, -1).astype(out.dtype),
            "product": lambda out, inp: inp}[kind]


def _half(kind):
    # Half of the answer left out.
    def f(out, inp):
        h = out.shape[0] // 2
        if kind == "levels":
            out = np.array(out)
            out[h:] = -1
            return out
        return jnp.concatenate([out[:h], jnp.zeros_like(out[h:])])
    return f


def _altered(kind):
    # One value of the answer (the largest) doubled where it is produced.
    def f(out, inp):
        if kind == "levels":
            out = np.array(out)
            k = int(np.flatnonzero(out > 0)[0])
            out[k] += 1
            return out
        k = int(jnp.argmax(jnp.abs(out)))
        return out.at[k].add(jnp.abs(out[k]))
    return f


FAULTS = {"unchanged": _unchanged, "half": _half, "altered": _altered}


def _plant(monkeypatch, name, fault):
    import legate_sparse_tpu
    from legate_sparse_tpu import graph, linalg, parallel

    if name == "pde2d-cg":
        real, f = linalg.cg, FAULTS[fault]("vector")
        monkeypatch.setattr(linalg, "cg", lambda A, b, **kw: (
            lambda x, it: (f(x, b), it))(*real(A, b, **kw)))
    elif name == "pde2d-cg-4chip":
        real, f = parallel.dist_cg, FAULTS[fault]("vector")
        monkeypatch.setattr(parallel, "dist_cg", lambda A, b, **kw: (
            lambda x, it: (f(x, b), it))(*real(A, b, **kw)))
    elif name == "pde2d-spmv":
        cls = legate_sparse_tpu.csr_array
        real, f = cls.__matmul__, FAULTS[fault]("product")
        monkeypatch.setattr(cls, "__matmul__",
                            lambda self, x: f(real(self, x), x))
    else:
        real, f = graph.bfs, FAULTS[fault]("levels")
        monkeypatch.setattr(graph, "bfs", lambda G, root, **kw: f(
            real(G, root, **kw), root))


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_planted_fault_is_not_correct(monkeypatch, name, fault):
    cell = tiny_cell(name)
    _plant(monkeypatch, name, fault)
    res = run_tiny(cell)
    assert "error" not in res
    assert not res["correct"], (fault, res["checks"])


@pytest.mark.xfail(strict=True, reason=(
    "the 2-norm residual misses a dropped halo (0.149 against the limit "
    "0.2 here, less at larger grids): the four-chip cell stays out of "
    "BENCHMARK.json until its check compares the max-norm residual"))
def test_exchange_left_out_is_not_correct(monkeypatch):
    """The four-chip cell with every halo ``ppermute`` answering zeros:
    each chip's rows then miss their neighbours' coupling."""
    cell = tiny_cell("pde2d-cg-4chip")
    cell.config.update({"nx": 134, "ny": 134})  # shapes no other test traced
    jax.clear_caches()
    monkeypatch.setattr(jax.lax, "ppermute",
                        lambda x, axis_name, perm: jnp.zeros_like(x))
    res = run_tiny(cell)
    jax.clear_caches()
    assert "error" not in res
    assert not res["correct"], res["checks"]
