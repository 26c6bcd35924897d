# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Off-chip de-risking of the r3 on-chip Pallas worker fault.

Three permanent gates, one subprocess each:

1. **TPU lowering**: the band kernels (SpMV over exact and holey
   bands, SpMM,
   banded SpGEMM) and the exact looped composition that crashed r3
   (kernel chained in a jitted ``fori_loop`` at the bench trip counts
   2/6/24, production tile 2^14, 2^24 rows) must lower + serialize for
   the TPU platform via ``jax.export`` — no chip needed.  This catches
   Mosaic verification errors (it already caught the i64 roll-shift
   bind).  ``tests/test_chip_compile.py`` goes one step further and
   compiles the kernels for a described v5e.

2. **Interpret-mode execution** of the same chained composition (same
   trip counts; tile forced to 1024 so the grid is still multi-step at
   a CPU-feasible 2^14 rows) with numeric checks against scipy.

3. **Distributed TPU lowering**: the full distributed composition —
   shard_map + ppermute halo + the per-shard Mosaic band kernel over
   the prepacked layout, the solver-shaped fori_loop nesting, and
   dist SpMM — must likewise export for the TPU platform (the dist
   lanes otherwise only ever run interpret mode).

The r3 fault signature: eager full-size launches PASS; the jitted
fori_loop composition crashed the worker (records at commit f6e5f18).
``chip_smoke.py`` runs that composition on the chip, where it now
passes (PR 21).
"""

import os
import subprocess
import sys


sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

def _run(code: str, env_extra: dict, timeout_s: int = 420) -> None:
    env = dict(os.environ)
    env.update(env_extra)
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, timeout=timeout_s,
                       env=env)
    assert r.returncode == 0 and "all-ok" in r.stdout, (
        f"rc={r.returncode}\nstdout: {r.stdout[-1500:]}\n"
        f"stderr: {r.stderr[-3000:]}"
    )


# TPU-platform serialization of every kernel + the crash-shaped looped
# composition at the PRODUCTION shapes (2^24 rows, tile 2^14: abstract
# avals only — nothing is materialized).
_EXPORT_CODE = r"""
from legate_sparse_tpu._platform import pin_cpu
pin_cpu(1)
from functools import partial
import numpy as np
import jax, jax.numpy as jnp
import jax.export as jex
from legate_sparse_tpu.ops import pallas_dia

W = 11
offsets = tuple(range(-(W // 2), W // 2 + 1))
tile = pallas_dia.supported(offsets, np.float32)
assert tile == 1 << 14, tile          # the production bench tile
n = 1 << 24                           # the production bench rows
rows_pad = -(-n // tile) * tile
rdata = jax.ShapeDtypeStruct((W, rows_pad // 128, 128), jnp.float32)
# A holey band: scipy-layout values and their explicit-entry mask.
hdata = jax.ShapeDtypeStruct((W, n), jnp.float32)
hmask = jax.ShapeDtypeStruct((W, n), jnp.bool_)
x = jax.ShapeDtypeStruct((n,), jnp.float32)


def spmv(rd, v):
    return pallas_dia.pallas_dia_spmv(rd, v, offsets, (n, n), tile)


def spmv_holey(d, m, v):
    rd = pallas_dia.row_align(d, offsets, (n, n), tile, mask=m)
    return pallas_dia.pallas_dia_spmv(rd, v, offsets, (n, n), tile)


assert jex.export(jax.jit(spmv), platforms=["tpu"])(rdata, x).serialize()
assert jex.export(jax.jit(spmv_holey), platforms=["tpu"])(
    hdata, hmask, x).serialize()

# The r3 crash composition: kernel chained inside one jitted fori_loop,
# at the bench/canary trip counts (k_lo=2, k_hi=6, k_cap=24).
def loop(rd, v, k):
    out = jax.lax.fori_loop(0, k, lambda i, u: spmv(rd, u), v)
    return jnp.ravel(out)[0]

for k in (2, 6, 24):
    assert jex.export(jax.jit(partial(loop, k=k)),
                      platforms=["tpu"])(rdata, x).serialize()

# SpMM kernel (k=4 RHS — the canary's width) + its short loop.
kk = 4
mm_tile = 1024
X = jax.ShapeDtypeStruct((n, kk), jnp.float32)


def spmm(rd, V):
    return pallas_dia.pallas_dia_spmm(rd, V, offsets, (n, n), mm_tile)


def mm_loop(rd, V):
    return jax.lax.fori_loop(0, 8, lambda i, U: spmm(rd, U), V)

assert jex.export(jax.jit(spmm), platforms=["tpu"])(rdata, X).serialize()
assert jex.export(jax.jit(mm_loop), platforms=["tpu"])(rdata, X).serialize()

# Banded SpGEMM at the canary's reduced size.
ng = 1 << 22
offs_c = tuple(sorted({a + b for a in offsets for b in offsets}))
gg_tile = pallas_dia._spgemm_tile(offsets, W, W, len(offs_c),
                                  np.dtype(np.float32))
assert gg_tile is not None
band = jax.ShapeDtypeStruct((W, ng), jnp.float32)


def spgemm(b):
    return pallas_dia.pallas_dia_spgemm(b, b, offsets, offsets, offs_c,
                                        (ng, ng), (ng, ng), gg_tile)

assert jex.export(jax.jit(spgemm), platforms=["tpu"])(band).serialize()
print("all-ok")
"""


# Interpret-mode execution of the crash-shaped composition with numeric
# verification.  Tile forced to 1024 keeps the grid multi-step (16
# steps at 2^14 rows) at CPU-interpretable cost; trip counts are the
# production 2/6/24.
_INTERP_CODE = r"""
import os
os.environ["LEGATE_SPARSE_TPU_PALLAS_TILE"] = "1024"
from legate_sparse_tpu._platform import pin_cpu
pin_cpu(1)
import numpy as np
import scipy.sparse as sp
import jax, jax.numpy as jnp
from legate_sparse_tpu.ops import pallas_dia

W = 11
half = W // 2
offsets = tuple(range(-half, half + 1))
tile = pallas_dia.supported(offsets, np.float32)
assert tile == 1024, tile
n = 1 << 14
assert n // tile == 16                # multi-step grid, like production

rng = np.random.default_rng(7)
# Scipy column-aligned DIA layout, magnitude-stable rows.
dia_data = (rng.uniform(0.5, 1.0, (W, n)) / W).astype(np.float32)
A = sp.dia_array((dia_data, offsets), shape=(n, n)).tocsr()
rdata = pallas_dia.row_align(jnp.asarray(dia_data), offsets, (n, n), tile)
x_np = rng.uniform(-1.0, 1.0, n).astype(np.float32)
x = jnp.asarray(x_np)


def step(v):
    return pallas_dia.pallas_dia_spmv(rdata, v, offsets, (n, n), tile,
                                      interpret=True)

# Eager launch (passed on-chip in r3) ...
y = np.asarray(step(x))
np.testing.assert_allclose(y, A @ x_np, rtol=2e-4, atol=1e-5)

# ... then the chained fori_loop composition (crashed on-chip in r3),
# at the bench/canary trip counts.
for k in (2, 6, 24):
    yk = np.asarray(jax.jit(
        lambda v: jax.lax.fori_loop(0, k, lambda i, u: step(u), v)
    )(x))
    ref = x_np.copy()
    for _ in range(k):
        ref = A @ ref
    np.testing.assert_allclose(yk, ref, rtol=5e-3, atol=1e-5)

# Holey band (holes +0.0 in the pack) through the same composition.
mask = (rng.uniform(size=(W, n)) > 0.2)
dia_masked = np.where(mask, dia_data, 0.0).astype(np.float32)
Am = sp.dia_array((dia_masked, offsets), shape=(n, n)).tocsr()
rd_m = pallas_dia.row_align(jnp.asarray(dia_masked), offsets, (n, n),
                            tile, mask=jnp.asarray(mask))


def mstep(v):
    return pallas_dia.pallas_dia_spmv(rd_m, v, offsets, (n, n), tile,
                                      interpret=True)

ym = np.asarray(jax.jit(
    lambda v: jax.lax.fori_loop(0, 6, lambda i, u: mstep(u), v))(x))
refm = x_np.copy()
for _ in range(6):
    refm = Am @ refm
np.testing.assert_allclose(ym, refm, rtol=5e-3, atol=1e-5)

# SpMM kernel in its loop (canary trip count 8).
kk = 4
X0 = rng.uniform(-1.0, 1.0, (n, kk)).astype(np.float32)


def mm_step(V):
    return pallas_dia.pallas_dia_spmm(rdata, V, offsets, (n, n), tile,
                                      interpret=True)

Ym = np.asarray(jax.jit(
    lambda V: jax.lax.fori_loop(0, 8, lambda i, U: mm_step(U), V)
)(jnp.asarray(X0)))
refM = X0.copy()
for _ in range(8):
    refM = A @ refM
np.testing.assert_allclose(Ym, refM, rtol=5e-3, atol=1e-5)

# Banded SpGEMM, carry-dependent loop (canary trip count 4; the
# operand depends on the carry so the kernel stays inside the loop).
offs_c = tuple(sorted({a + b for a in offsets for b in offsets}))
gg_tile = pallas_dia._spgemm_tile(offsets, W, W, len(offs_c),
                                  np.dtype(np.float32))
assert gg_tile is not None
band = jnp.asarray(dia_data)


def gg(b):
    return pallas_dia.pallas_dia_spgemm(
        b, band, offsets, offsets, offs_c, (n, n), (n, n), gg_tile,
        interpret=True)

C_dia = np.asarray(gg(band))
C_ref = (sp.dia_array((dia_data, offsets), shape=(n, n)) @
         sp.dia_array((dia_data, offsets), shape=(n, n))).todia()
# Align reference rows to offs_c ordering.
ref_rows = {int(o): C_ref.data[i] for i, o in enumerate(C_ref.offsets)}
for i, o in enumerate(offs_c):
    got = C_dia[i]
    want = ref_rows.get(int(o), np.zeros(n, np.float32))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-5)

final = jax.jit(lambda c: jnp.sum(jax.lax.fori_loop(
    0, 4,
    lambda i, c: c * 0.5 + gg(
        band.at[0, 0].add((c[0, 0] * 1e-30).astype(band.dtype)))[0][:1],
    c)))(jnp.zeros((1, n), dtype=jnp.float32))
assert bool(jnp.isfinite(final))
print("all-ok")
"""


def test_tpu_export():
    """The band kernels + the r3 crash composition must lower and
    serialize for the TPU platform from this CPU host."""
    _run(_EXPORT_CODE, {})


def test_interpret_crash_composition():
    """The exact chained-fori_loop composition that crashed the r3
    worker, executed (interpret mode) with numeric checks."""
    _run(_INTERP_CODE, {"LEGATE_SPARSE_TPU_PALLAS_DIA": "interpret"})


# The DISTRIBUTED Mosaic route (shard_map + ppermute halo + the
# per-shard Pallas band kernel over the prepacked layout): this gate
# proves the full composition LOWERS + SERIALIZES for the TPU platform
# from the CPU host — so chip time is spent measuring, not discovering
# Mosaic lowering bugs in the dist path.
_DIST_EXPORT_CODE = r"""
import os
os.environ["LEGATE_SPARSE_TPU_PALLAS_DIST"] = "1"
from legate_sparse_tpu._platform import pin_cpu
pin_cpu(8)
import numpy as np
import jax, jax.numpy as jnp
import jax.export as jex
import legate_sparse_tpu as sparse
from legate_sparse_tpu.parallel import make_row_mesh, shard_csr
from legate_sparse_tpu.parallel.dist_csr import dist_spmm, dist_spmv
from jax.sharding import NamedSharding, PartitionSpec as P

n = 1 << 16
W = 11
half = W // 2
offs = list(range(-half, half + 1))
diags = [np.full(n - abs(o), 1.0 / W, np.float32) for o in offs]
A = sparse.diags(diags, offs, shape=(n, n), format="csr",
                 dtype=np.float32)
mesh = make_row_mesh(jax.devices()[:8])
dA = shard_csr(A, mesh=mesh)
assert dA.pdia_tile, "Mosaic dist prepack must engage for this band"
sh = NamedSharding(mesh, P("rows"))

xa = jax.ShapeDtypeStruct((dA.rows_padded,), jnp.float32, sharding=sh)
exp = jex.export(jax.jit(lambda x: dist_spmv(dA, x)),
                 platforms=["tpu"])(xa)
assert exp.serialize()

# The looped composition (solver-shaped: the kernel inside fori_loop
# inside shard_map-consuming jit) — the r3 fault shape, distributed.
def loop(x):
    out = jax.lax.fori_loop(0, 6, lambda i, v: dist_spmv(dA, v), x)
    return jnp.ravel(out)[0]

assert jex.export(jax.jit(loop), platforms=["tpu"])(xa).serialize()

# Dist SpMM over the same prepack.
Xa = jax.ShapeDtypeStruct((dA.rows_padded, 4), jnp.float32,
                          sharding=NamedSharding(mesh, P("rows", None)))
assert jex.export(jax.jit(lambda X: dist_spmm(dA, X)),
                  platforms=["tpu"])(Xa).serialize()
print("all-ok")
"""


def test_dist_mosaic_tpu_export():
    """Distributed shard_map + Pallas band SpMV/SpMM (and the looped
    solver composition) must lower and serialize for the TPU platform
    from this CPU host."""
    _run(_DIST_EXPORT_CODE, {})
