# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""int32-local / int64-global index split (VERDICT r4 #4, SURVEY §7
hard part 5).

The reference runs ``coord_ty = int64`` everywhere
(``legate_sparse/types.py:20-25``); the TPU policy is the split: device
structures are shard-LOCAL int32, global bookkeeping (row offsets,
total nnz) is host-side int64/Python ints.  The capability these tests
pin: a NO-x64 process builds and SpMVs a distributed matrix whose
GLOBAL nnz exceeds 2^31 while every shard stays within int32 —
``coord_dtype_for``'s OverflowError is the single-device boundary only.

The >2^31 run is slow-lane (a ~10 GB DIA-only build on this box); the
default lane proves the same pathway end-to-end at small n.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Template: runs in a SUBPROCESS with x64 hard-disabled (the TPU
# process policy), builds a banded DistCSR shard-locally (no host CSR
# ever exists), SpMVs, and verifies sampled rows exactly against
# host-side references computed with Python ints.
_SNIPPET = r"""
import sys
import numpy as np
from legate_sparse_tpu._platform import pin_cpu
pin_cpu(8)
import jax
jax.config.update("jax_enable_x64", False)   # the TPU-process policy
import jax.numpy as jnp
import legate_sparse_tpu as sparse
from legate_sparse_tpu.parallel import make_row_mesh
from legate_sparse_tpu.parallel.dist_build import dist_diags
from legate_sparse_tpu.parallel.dist_csr import dist_spmv, shard_vector
from legate_sparse_tpu import types

log2n = int(sys.argv[1])
n = 1 << log2n
offsets = [0, 1, -1, 2, -2, 3, -3, 4, -4]


def val(k):
    if k == 0:
        return 2.0                       # scalar diagonal
    # Callable diagonal: traced on device per shard; the SAME formula
    # re-evaluated on host (numpy int64) for the expected values.
    return lambda i: ((i % 97).astype(jnp.float32) * 0.01 + 0.5 + k * 0.05)


def val_host(k, i):
    if k == 0:
        return np.float32(2.0)
    return np.float32((i % 97) * 0.01 + 0.5 + k * 0.05)


mesh = make_row_mesh(jax.devices())
A = dist_diags([val(k) for k in offsets], offsets, shape=(n, n),
               mesh=mesh, dtype=np.float32, materialize_ell=False)

# --- the int64-global bookkeeping -----------------------------------
gn = A.global_nnz
expected_nnz = sum(n - abs(k) for k in offsets)
assert gn == expected_nnz, (gn, expected_nnz)
starts = A.shard_row_starts
assert starts.dtype == np.int64
assert int(starts[-1]) == (A.num_shards - 1) * A.rows_per_shard

# --- every DEVICE array must be int32-or-narrower / float -----------
for name in ("data", "cols", "counts", "row_ids", "dia_data",
             "dia_mask", "pdia_data"):
    arr = getattr(A, name)
    if arr is None:
        continue
    assert np.dtype(arr.dtype).itemsize <= 4, (name, arr.dtype)

# --- SpMV with exact sampled verification ---------------------------
rng = np.random.default_rng(12)
x = ((np.arange(n, dtype=np.int64) * 2654435761) % (1 << 20)
     ).astype(np.float32) / np.float32(1 << 20)
xs = shard_vector(x, mesh, A.rows_padded)
y = np.asarray(dist_spmv(A, xs))[:n]

rps = A.rows_per_shard
samples = sorted(set(
    [0, 1, 4, n // 2, n - 1, n - 5, rps - 1, rps, rps + 1,
     3 * rps - 1, 3 * rps]
    + [int(v) for v in rng.integers(0, n, size=8)]))
for g in samples:
    exp = np.float32(0.0)
    for k in offsets:
        c = g + k
        if 0 <= c < n:
            exp += val_host(k, np.int64(g + min(k, 0))) * x[c]
    got = y[g]
    assert abs(float(got) - float(exp)) <= 1e-4 * max(1.0, abs(float(exp))), (
        g, float(got), float(exp))

assert np.dtype(types.index_dtype()) == np.dtype(np.int32)
print(f"INT64-GLOBAL-OK nnz={gn}")
"""


def _run(log2n: int, timeout_s: int) -> str:
    env = dict(os.environ)
    env.pop("LEGATE_SPARSE_TPU_X64", None)
    env.pop("JAX_ENABLE_X64", None)
    r = subprocess.run([sys.executable, "-c", _SNIPPET, str(log2n)],
                       capture_output=True, text=True, timeout=timeout_s,
                       env=env)
    assert r.returncode == 0, (
        f"rc={r.returncode}\nstdout: {r.stdout[-800:]}\n"
        f"stderr: {r.stderr[-2500:]}"
    )
    assert "INT64-GLOBAL-OK" in r.stdout
    return r.stdout


def test_no_x64_dist_pathway_small():
    out = _run(12, timeout_s=420)          # n=4096: fast sanity
    assert "nnz=" in out


@pytest.mark.slow
def test_no_x64_global_nnz_past_2_31():
    """The VERDICT done-criterion: global nnz > 2^31 in a no-x64
    process, int32 everywhere on device, exact sampled results."""
    out = _run(28, timeout_s=1500)         # n=2^28, 9 diagonals
    nnz = int(out.split("nnz=")[1].split()[0])
    assert nnz > (1 << 31), nnz
