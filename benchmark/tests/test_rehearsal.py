"""Each cell end to end on the CPU at a tiny size: set-up, a short
window and the reference check (the four-chip cell on four virtual
devices)."""

import json
import math
import os
import shutil

import jax
import pytest

from benchmark import harness, trace
from benchmark.tests.conftest import ROOT, run_tiny, tiny_cell

CELLS = ["pde2d-cg", "pde2d-spmv", "bfs-graph500", "pde2d-cg-4chip"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(name):
    cell = tiny_cell(name)
    res = run_tiny(cell)
    assert res["correct"], res
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["count"] == len(jax.devices())
    # The compared numbers come last, each beside its limit.
    assert list(res)[-1] == "checks"
    assert all(set(c) == {"value", "limit"}
               for c in res["checks"].values())


def test_per_layer_readers_on_a_recorded_trace():
    """The solver cell's per-layer readers, fed the recorded v5e excerpt
    in place of a trace (a CPU run has no device plane)."""
    cell = tiny_cell("pde2d-cg")
    run = harness.Run(cell=cell, seed=7, seconds=0.3, trace=False,
                      devices=jax.devices()[:1])
    drv = harness.op_module(cell.op)
    drv.setup(run)
    harness.window(run, drv)
    drv.finish(run)
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "trace_pde2d_cg_v5e.json")) as f:
        run.reduced = trace.Reduced.from_planes(json.load(f)["planes"])

    class V5e:
        device_kind = "TPU v5 lite"

    run.devices = [V5e()]
    got = harness.read_metrics(run, cell.per_layer)
    assert set(got) == {"cg_iters", "cg_roofline", "band_roofline.cg",
                        "idle_share.cg"}
    iters = [r["iters"] for r in run.records]
    assert got["cg_iters"]["value"] == pytest.approx(sum(iters) / len(iters))
    assert 0 < got["idle_share.cg"]["value"] < 100
    assert all(math.isfinite(v["value"]) for v in got.values())


def test_a_new_cell_is_new_files_only(tmp_path):
    """A cell, configuration, traffic mix and metric added as files and
    entries alone run through the unchanged harness."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (root / "benchmark" / "configs" / "poisson2d-dummy.json").write_text(
        json.dumps({**json.loads((root / "benchmark" / "configs" /
                                  "poisson2d-2048.json").read_text()),
                    "nx": 18, "ny": 18, "rows": 256, "nnz": 1216}))
    (root / "benchmark" / "traffic" / "cg-dummy.json").write_text(
        json.dumps({"op": "cg", "rhs": "uniform", "maxiter": 500,
                    "checked": 3}))
    (root / "benchmark" / "metrics" / "dummy_solves.py").write_text(
        "def read(run):\n    return len(run.records)\n")
    bench["configs"].append({
        "name": "poisson2d-dummy", "source": "a test",
        "file": "benchmark/configs/poisson2d-dummy.json", "reduced": [],
        "why": "a test"})
    bench["workloads"].append({
        "name": "dummy-cg", "config": "poisson2d-dummy",
        "traffic": "cg-dummy", "chips": 1, "why": "a test"})
    bench["end_to_end"].append({
        "name": "dummy_solves", "unit": "solves", "better": "higher",
        "bound": 0.25, "source": "host_clock", "workloads": ["dummy-cg"]})
    bench["end_to_end"][1]["workloads"].append("dummy-cg")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("dummy-cg", root=str(root))
    assert cell.config["nx"] == 18 and cell.traffic["rhs"] == "uniform"
    res = run_tiny(cell)
    assert res["correct"], res
    assert set(res["metrics"]) == {"setup_s", "solve_s", "dummy_solves"}
    assert res["metrics"]["dummy_solves"]["value"] == res["attempted"]
