"""The four-chip cell ``pde2d-cg-4chip`` as ``BENCHMARK.json`` has it,
at a tiny grid on four virtual devices: a run is correct, and its
comparisons fail the control, the planted faults and a dropped halo.
Its eight per-layer readers are fed an excerpt of a v5e 2x2 trace."""

import json
import math
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from benchmark import harness, trace
from benchmark.tests.conftest import ROOT, run_tiny
from benchmark.tests.test_controls import FAULTS, _plant

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "pde2d-cg-4chip"

#: 128^2 unknowns, as the parked rehearsal of the same cell takes.
TINY = {"nx": 130, "ny": 130}

READERS = ["cg_iters.dist", "launch_ms.dist", "host_syncs.dist",
           "band_roofline.dist", "halo_us.dist", "allreduce_us.dist",
           "collective_exposed.cg", "idle_share.dist"]


def _cell(**sizes):
    cell = harness.load_cell(NAME)
    cell.config.update(TINY, **sizes)
    return cell


def test_the_cell_is_the_new_definition():
    cell = _cell()
    assert cell.chips == 4 and cell.op == "dist_cg_x4"
    assert set(cell.config["limits"]) == {"true_residual_max",
                                          "residual_inf_max"}
    assert [m["name"] for m in cell.per_layer] == READERS
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "solve_s"]


def test_a_run_is_correct():
    res = run_tiny(_cell())
    assert res["correct"], res
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["checks"]) == {"true_residual_max", "residual_inf_max"}
    assert set(res["metrics"]) == {"setup_s", "solve_s"}


def test_control_is_not_correct():
    res = run_tiny(_cell(), control=True)
    assert "error" not in res
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(monkeypatch, fault):
    cell = _cell()
    _plant(monkeypatch, NAME, fault)
    res = run_tiny(cell)
    assert "error" not in res
    assert not res["correct"], (fault, res["checks"])


def test_dropped_halo_is_not_correct(monkeypatch):
    """Every halo ``ppermute`` answering zeros: each chip's edge rows
    miss their neighbours' coupling.  The max-norm residual fails it.
    The grid is one no other test traces, and the caches are cleared
    around the patch, so no compiled program keeps the real exchange or
    takes the dropped one."""
    cell = _cell(nx=138, ny=138)
    jax.clear_caches()
    monkeypatch.setattr(jax.lax, "ppermute",
                        lambda x, axis_name, perm: jnp.zeros_like(x))
    try:
        res = run_tiny(cell)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert "error" not in res
    assert not res["correct"], res["checks"]
    inf = res["checks"]["residual_inf_max"]
    assert inf["value"] > inf["limit"]


# ------------------------------------------------ the recorded excerpt --
#: Host clock, in seconds, at the excerpt's ``solve`` annotation.
T0 = 1000.0


def _excerpt():
    with open(os.path.join(HERE, "data",
                           "trace_pde2d_cg_4chip_v5e.json")) as f:
        fx = json.load(f)
    return fx, trace.Reduced.from_planes(fx["planes"])


def _kernel_starts(red, dev):
    return sorted(s for n, s, d, k in red.ops[dev] if k == "kernel")


@pytest.fixture
def obs():
    from legate_sparse_tpu import obs

    was = obs.enabled()
    obs.reset()
    obs.enable()
    yield obs
    obs.reset()
    if not was:
        obs.disable()


def _excerpt_run(obs):
    """A run of the excerpt at the cell's full size: its one solve's
    record starts at host time ``T0`` where the trace's ``solve``
    annotation starts and counts the loop iterations the excerpt holds
    (its band-kernel calls less the initial residual's); the solve's
    ``dist_cg`` spans and its one counted fetch are recorded through
    the library's obs API."""
    fx, red = _excerpt()
    cell = harness.load_cell(NAME)
    assert {k: cell.config[k] for k in fx["config"]} == fx["config"]
    (s0, _), = [(s, e) for n, s, e in red.host if n == "solve"]
    off = s0 - T0 * 1e9
    for name, start, end in fx["spans"]:
        obs.trace.complete_span(name, round(start - off), round(end - start))
    iters = len(_kernel_starts(red, 0)) - 1
    from benchmark.reference import poisson

    run = SimpleNamespace(
        cell=cell, reduced=red, notes={},
        devices=[SimpleNamespace(device_kind="TPU v5 lite")] * cell.chips,
        records=[{"t0": T0, "t1": (red.window[1] - off) * 1e-9, "i": 0,
                  "iters": iters}],
        counters={"transfer.host_sync.dist_cg_norm": 1},
        window=((red.window[0] - off) * 1e-9, (red.window[1] - off) * 1e-9),
        state=SimpleNamespace(nnz=poisson.nnz(cell.config), itemsize=4))
    return run, iters, fx


def test_readers_on_the_recorded_excerpt(obs):
    run, iters, fx = _excerpt_run(obs)
    got = {n: harness._reader(n, ROOT).read(run) for n in READERS}
    assert all(v is not None and math.isfinite(v)
               for v in got.values()), got
    assert got["cg_iters.dist"] == iters > 0
    assert got["host_syncs.dist"] == 1.0
    (launch,) = [e - s for n, s, e in fx["spans"] if n == "dist_cg.launch"]
    assert got["launch_ms.dist"] == pytest.approx(launch * 1e-6)
    assert got["halo_us.dist"] > 0 and got["allreduce_us.dist"] > 0
    assert 0 < got["band_roofline.dist"] < 100
    assert 0 < got["collective_exposed.cg"] < 100 - got["idle_share.dist"]
    assert 0 < got["idle_share.dist"] < 100


def test_readers_none_without_trace(obs):
    run, _, _ = _excerpt_run(obs)
    run.reduced = None
    got = {n: harness._reader(n, ROOT).read(run) for n in READERS}
    assert [n for n, v in got.items() if v is not None] == ["cg_iters.dist"]


def test_excerpt_collectives_match_the_iteration_model():
    """Between two band-kernel calls of the loop, every device runs
    what ``cg_comm_volumes`` counts for an iteration: one two-sided
    halo phase (two ``ppermute``s, each a ``collective-permute`` start
    and done) and three scalar ``psum``s (``all-reduce``)."""
    from legate_sparse_tpu import parallel

    dA = parallel.dist_diags([4.0, -1.0, -1.0], [0, 1, -1], shape=(64, 64),
                             mesh=parallel.make_row_mesh(4),
                             dtype="float32", materialize_ell=False)
    (_, one), (_, two) = (parallel.dist_csr.cg_comm_volumes(dA, 4, it)
                          for it in (1, 2))
    phases, psums = two["ppermute"] - one["ppermute"], two["psum"] - one["psum"]
    assert (phases, psums) == (1, 3)
    _, red = _excerpt()
    assert len(red.ops) == 4
    for dev, evs in red.ops.items():
        kern = _kernel_starts(red, dev)
        assert len(kern) >= 4
        # Each product's halo phase, the initial residual's included.
        assert sum(n.startswith("collective-permute") for n, *_ in evs) \
            == 2 * 2 * phases * len(kern)
        for a, b in zip(kern[1:], kern[2:]):
            inside = [n for n, s, d, k in evs
                      if a < s < b and k == "collective"]
            assert sum(n.startswith("collective-permute")
                       for n in inside) == 2 * 2 * phases, (dev, inside)
            assert sum(n.startswith("all-reduce")
                       for n in inside) == psums, (dev, inside)
