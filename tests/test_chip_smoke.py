# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""``chip_smoke.py`` off the chip: it refuses to report without a TPU,
and its phases — run here at tiny sizes with the Pallas kernels in
interpret mode — match scipy and take the paths the chip run expects."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = {
    "band_rows": 1 << 12,
    "loop_iters": 3,
    "cg_grid": 16,
    "cg_maxiter": 2000,
    "spgemm_band_rows": 1 << 11,
    "spgemm_pl_rows": 1 << 9,
    "spgemm_pl_nnz": 2,
    "irr_rows": 1 << 10,
    "irr_nnz": 4,
    "bsr_block_rows": 4,
    "bsr_blocks_per_row": 2,
    "rmat_scale": 8,
    "dist_band_rows": 1 << 12,
    "dist_cg_grid": 32,
}
assert set(TINY) == set(chip_smoke.FULL)


def _run_script(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_refuses_without_tpu(where, tmp_path):
    if where == "alone":
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    else:
        cwd = REPO
    r = _run_script(cwd)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


@pytest.fixture
def interpret_lane(monkeypatch):
    """The Pallas kernels in interpret mode, BSR built off-TPU, spans
    on (the phases read the dispatch path from them)."""
    from legate_sparse_tpu import obs
    from legate_sparse_tpu.settings import settings

    monkeypatch.setenv("LEGATE_SPARSE_TPU_PALLAS_DIA", "interpret")
    monkeypatch.setattr(settings, "bsr_force", True)
    was = obs.enabled()
    obs.enable()
    yield
    obs.reset()
    if not was:
        obs.disable()


def _mesh4():
    from legate_sparse_tpu.parallel.mesh import make_row_mesh

    return make_row_mesh(4)


PHASES = {fn.__name__: (fn, False) for fn in chip_smoke.PHASES}
PHASES.update({
    "phase_dist_band": (chip_smoke.phase_dist_band, True),
    "phase_dist_cg": (chip_smoke.phase_dist_cg, True),
})


@pytest.mark.parametrize("name", list(PHASES))
def test_phase_matches_scipy(name, interpret_lane):
    fn, four = PHASES[name]
    rng = np.random.default_rng(chip_smoke.SEED)
    args = (TINY, rng) + ((_mesh4(),) if four else ())
    lines = list(fn(*args))      # raises PhaseError when off reference
    assert lines
    for line in lines:
        json.dumps(line)         # each is one printable JSON line
        assert line.get("err", 0.0) <= line.get("tol", 0.0)
    if name in ("phase_band", "phase_cg"):
        assert all(ln["path"] == "dia-pallas" for ln in lines)
    if four:
        assert lines[-1]["shard_devices"] == 4
