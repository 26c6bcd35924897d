"""The benchmark's own tests run on the CPU at tiny sizes, four virtual
devices standing in for the four-chip mesh.  They check the harness,
the yardstick and the comparisons; no number they print is a device
number."""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import pytest  # noqa: E402

import legate_sparse_tpu  # noqa: E402,F401

# The library turns 64-bit mode on for the CPU; the chip runs without.
jax.config.update("jax_enable_x64", False)

#: Cell sizes a test run holds.  The solver cells take 128^2 unknowns:
#: at 32^2 the bfloat16 control's residual (0.157) stays under the
#: configured limit, which its 7.8 at the cell's size does not.
TINY = {"pde2d-cg": {"nx": 130, "ny": 130},
        "pde2d-spmv": {"nx": 34, "ny": 34},
        "bfs-graph500": {"scale": 8},
        "pde2d-cg-4chip": {"nx": 130, "ny": 130}}


#: The four-chip cell, kept out of BENCHMARK.json until its check can
#: see a dropped halo (PERF.md, Open questions); its files are
#: rehearsed here on four virtual devices.
PARKED = {"name": "pde2d-cg-4chip", "config": "poisson2d-4096",
          "traffic": "dist-cg-solves", "chips": 4, "why": "parked"}


def _with_parked(bench: dict) -> dict:
    bench = json.loads(json.dumps(bench))
    bench["workloads"].append(PARKED)
    bench["configs"].append({
        "name": "poisson2d-4096", "source": "parked", "reduced": [],
        "file": "benchmark/configs/poisson2d-4096.json", "why": "parked"})
    for m in bench["end_to_end"]:
        if m["name"] == "solve_s":
            m["workloads"].append(PARKED["name"])
    return bench


def tiny_cell(name: str, root: str = ROOT, bench=None):
    from benchmark import harness

    if bench is None:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
    if name == PARKED["name"]:
        bench = _with_parked(bench)
    cell = harness.load_cell(name, bench=bench, root=root)
    cell.config.update(TINY.get(name, {}))
    return cell


def run_tiny(cell, seed: int = 2 ** 33 + 5, seconds: float = 0.5,
             control: bool = False) -> dict:
    from benchmark import harness

    return harness.run_cell(cell, seed, seconds, False,
                            jax.devices()[:cell.chips], control=control)


@pytest.fixture
def tiny():
    return tiny_cell
