#!/usr/bin/env python3
# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Chip smoke: drive the library's main path once on a TPU, at the
widths its users run, in one process, and check every result against a
plain scipy/numpy reference computed on the host from the same seeded
data.

    python chip_smoke.py               # one chip: phases a-e
    python chip_smoke.py --four-chips  # four chips: dist SpMV and CG only

Each phase prints one JSON line: its name, sizes, dtype, the dispatch
``path`` its op's span recorded, the error against the reference with
the tolerance it is held to, and the wall seconds including compile.
The last line is ``{"ok": true, "device": {...}}``.  The script exits
non-zero without that line when JAX finds no TPU, when any phase is off
its reference, or when a phase that should run a Pallas kernel ran
another path.

The phase functions take their sizes as an argument, so
``tests/test_chip_smoke.py`` runs them at tiny sizes on the CPU with the
Pallas kernels in interpret mode.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

# The widths users and the reference run (sources in PERF.md, "Cells"):
# the reference's SpMV microbenchmark band (2^24 rows x 11 diagonals,
# bench.py's headline), the bench's 1M-row CG grid, the bench's 2^18-row
# power-law autotune matrix, Graph500's scale-20 Kronecker graph.
FULL = {
    "band_rows": 1 << 24,
    "loop_iters": 8,
    "cg_grid": 1024,
    "cg_maxiter": 20000,
    "spgemm_band_rows": 1 << 22,
    "spgemm_pl_rows": 1 << 16,
    "spgemm_pl_nnz": 1,
    "irr_rows": 1 << 18,
    "irr_nnz": 8,
    "bsr_block_rows": 512,
    "bsr_blocks_per_row": 8,
    "rmat_scale": 20,
    "dist_band_rows": 1 << 24,
    "dist_cg_grid": 2048,
}

SEED = 20261015
OFFSETS = tuple(range(-5, 6))        # 11 diagonals, bench _banded_config
CG_RTOL = 1e-5
# f32 CG stops on its recursive residual; the true one may sit ~100x
# above it (.claude/skills/verify/SKILL.md, "Gotchas").
CG_TRUE_TOL = 100 * CG_RTOL


class PhaseError(AssertionError):
    """A phase's result is off its reference, or ran the wrong path."""


# ---------------------------------------------------------------------------
# Seeded data and host references
# ---------------------------------------------------------------------------
def band_diagonals(n: int, rng) -> list:
    """11 f32 diagonals with values in [0.5/11, 1.5/11): row sums near
    1, so a chained x <- A x stays bounded (bench.py ``_banded_config``
    uses 1/11 everywhere, which a transposed or mis-shifted band would
    pass).  No value is zero: CSR would drop it and leave a hole, and a
    holey band takes the general SpGEMM path."""
    return [(rng.random(n - abs(o), dtype=np.float32) + np.float32(0.5))
            * np.float32(1 / 11) for o in OFFSETS]


def scipy_band(diagonals, n: int, dtype=np.float64):
    import scipy.sparse as sp

    return sp.diags([d.astype(dtype) for d in diagonals], list(OFFSETS),
                    shape=(n, n), format="csr")


def scipy_poisson(g: int):
    """5-point Poisson operator on a g x g grid, from scipy alone: kron
    of 1-D Laplacians.  Its boundary couplings are absent, so the band
    has holes and takes the masked kernel."""
    import scipy.sparse as sp

    T = sp.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(g, g))
    eye = sp.identity(g)
    return (sp.kron(eye, T) + sp.kron(T, eye)).tocsr()


def block_matrix(nbr: int, per_row: int, rng, density=0.125):
    """scipy f32 matrix of dense-ish 128x128 blocks at random block
    columns: no band, within the BSR densification budget — the BSR
    route."""
    import scipy.sparse as sp

    B = 128
    bcols = np.stack([np.sort(rng.choice(nbr, per_row, replace=False))
                      for _ in range(nbr)]).ravel()
    brows = np.repeat(np.arange(nbr), per_row)
    keep = rng.random((brows.size, B, B), dtype=np.float32) < density
    b, r, c = np.nonzero(keep)
    rows = brows[b] * B + r
    cols = bcols[b] * B + c
    vals = rng.random(rows.size, dtype=np.float32)
    n = nbr * B
    return sp.csr_matrix((vals, (rows, cols)), shape=(n, n))


def rel_err(y, ref) -> float:
    y = np.asarray(y, dtype=np.float64)
    ref = np.asarray(ref, dtype=np.float64)
    if y.shape != ref.shape:
        raise PhaseError(f"shape {y.shape} != reference {ref.shape}")
    if not np.isfinite(y).all():
        raise PhaseError("non-finite values in the result")
    scale = max(float(np.abs(ref).max()), 1e-30)
    return float(np.abs(y - ref).max()) / scale


def csr_diff(C, ref) -> tuple:
    """(pattern mismatches, max relative value error) of two scipy CSR
    matrices, explicit zeros dropped from both."""
    C = C.tocsr().astype(np.float64)
    ref = ref.tocsr().astype(np.float64)
    for M in (C, ref):
        M.sum_duplicates()
        M.eliminate_zeros()
    pattern = int(abs((C != 0).astype(np.int8)
                      - (ref != 0).astype(np.int8)).sum())
    scale = max(float(abs(ref).max()), 1e-30)
    return pattern, float(abs(C - ref).max()) / scale


# ---------------------------------------------------------------------------
# Measurement plumbing
# ---------------------------------------------------------------------------
# XLA compiles (count, seconds) so far, fed by a jax.monitoring listener
# that main() registers: on the chip, compiling the eager ops of the
# matrix builds is much of each phase's wall time.
_COMPILES = [0, 0.0]


def _on_duration(event: str, secs: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES[0] += 1
        _COMPILES[1] += secs


def start() -> tuple:
    return time.perf_counter(), _COMPILES[0], _COMPILES[1]


def stamp(line: dict, t0: tuple) -> None:
    """Wall seconds since ``t0`` (compile included) and the compiles
    in them."""
    line["seconds"] = time.perf_counter() - t0[0]
    line["compiles"] = _COMPILES[0] - t0[1]
    line["compile_s"] = _COMPILES[1] - t0[2]


def last_path(span_name: str):
    """The ``path`` attribute of the newest ``span_name`` span."""
    from legate_sparse_tpu import obs

    for rec in reversed(obs.records()):
        if rec.get("type") == "span" and rec.get("name") == span_name:
            path = rec.get("attrs", {}).get("path")
            if path is not None:
                return path
    return None


def check(line: dict, err: float, tol: float, path=None, want=None):
    line["err"] = err
    line["tol"] = tol
    if path is not None or want is not None:
        line["path"] = path
    if want is not None and path != want:
        raise PhaseError(f"path {path!r}, expected {want!r}: {line}")
    if not err <= tol:
        raise PhaseError(f"error above tolerance: {line}")


# ---------------------------------------------------------------------------
# Phases (one chip).  Each yields its lines as they complete.
# ---------------------------------------------------------------------------
def timed(fn, *args):
    """(fn(*args), wall seconds)."""
    t = time.perf_counter()
    out = fn(*args)
    return out, time.perf_counter() - t


def band(sparse, diagonals, n: int, dtype):
    """The band built as bench.py ``_banded_config`` builds it."""
    return sparse.diags(diagonals, list(OFFSETS), shape=(n, n),
                        format="csr", dtype=dtype)


def from_scipy(sparse, ref, dtype):
    """A scipy matrix's CSR arrays handed to ``sparse.csr_array``."""
    return sparse.csr_array((ref.data.astype(dtype), ref.indices,
                             ref.indptr), shape=ref.shape)


def phase_band(sz, rng):
    """a. The headline banded SpMV: eager, chained K times in one
    compiled fori_loop, and in bf16."""
    import jax
    import jax.numpy as jnp

    import legate_sparse_tpu as sparse

    n = sz["band_rows"]
    K = sz["loop_iters"]
    diagonals = band_diagonals(n, rng)
    x = rng.random(n, dtype=np.float32)
    ref_A, ref_s = timed(scipy_band, diagonals, n)

    t0 = start()
    A, build_s = timed(band, sparse, diagonals, n, np.float32)
    y = np.asarray(A @ jnp.asarray(x))
    ref, r_s = timed(ref_A.dot, x.astype(np.float64))
    line = {"phase": "a.band_spmv", "rows": n, "diags": len(OFFSETS),
            "nnz": int(A.nnz), "dtype": "float32", "build_s": build_s,
            "ref_s": ref_s + r_s}
    check(line, rel_err(y, ref), 1e-5, last_path("spmv"), "dia-pallas")
    stamp(line, t0)
    yield line

    # The composition that crashed the worker in the earlier on-chip
    # bench runs: the kernel chained inside one compiled fori_loop.
    # Called eagerly, the loop takes A's arrays as arguments; under an
    # outer jax.jit they would be baked into the executable (2 GB here).
    t0 = start()
    yk = np.asarray(jax.lax.fori_loop(0, K, lambda i, u: A @ u,
                                      jnp.asarray(x)))
    t_ref = time.perf_counter()
    ref = x.astype(np.float64)
    for _ in range(K):
        ref = ref_A @ ref
    line = {"phase": "a.band_spmv_fori_loop", "rows": n,
            "diags": len(OFFSETS), "iters": K, "dtype": "float32",
            "ref_s": time.perf_counter() - t_ref}
    check(line, rel_err(yk, ref), 1e-4, last_path("spmv"), "dia-pallas")
    stamp(line, t0)
    yield line
    del A

    t0 = start()
    A16, build_s = timed(band, sparse, diagonals, n, jnp.bfloat16)
    x16 = jnp.asarray(x, dtype=jnp.bfloat16)
    y16 = np.asarray((A16 @ x16).astype(jnp.float32))
    t_ref = time.perf_counter()
    ref_A.data = ref_A.data.astype(jnp.bfloat16).astype(np.float64)
    ref = ref_A @ np.asarray(x16, dtype=np.float64)
    line = {"phase": "a.band_spmv_bf16", "rows": n, "diags": len(OFFSETS),
            "dtype": "bfloat16", "build_s": build_s,
            "ref_s": time.perf_counter() - t_ref}
    check(line, rel_err(y16, ref), 1e-2, last_path("spmv"), "dia-pallas")
    stamp(line, t0)
    yield line


def phase_cg(sz, rng):
    """b. CG to tolerance on the 5-point Poisson grid."""
    import legate_sparse_tpu as sparse
    from legate_sparse_tpu import linalg

    g = sz["cg_grid"]
    b = rng.standard_normal(g * g).astype(np.float32)
    A_sp = scipy_poisson(g)
    t0 = start()
    A, build_s = timed(from_scipy, sparse, A_sp, np.float32)
    (x, iters), solve_s = timed(
        lambda: linalg.cg(A, b, rtol=CG_RTOL, maxiter=sz["cg_maxiter"]))
    x = np.asarray(x, dtype=np.float64)
    b64 = b.astype(np.float64)
    res = float(np.linalg.norm(b64 - A_sp @ x) / np.linalg.norm(b64))
    line = {"phase": "b.cg_poisson", "rows": g * g, "grid": g,
            "dtype": "float32", "rtol": CG_RTOL, "iters": int(iters),
            "build_s": build_s, "solve_s": solve_s}
    check(line, res, CG_TRUE_TOL, last_path("spmv"), "dia-pallas")
    stamp(line, t0)
    yield line


def phase_spgemm(sz, rng):
    """c. SpGEMM: the banded product (DIA path) and a power-law product
    (the general ESC path of ``ops/spgemm.py``)."""
    import legate_sparse_tpu as sparse
    from legate_sparse_tpu import gallery

    n = sz["spgemm_band_rows"]
    diagonals = band_diagonals(n, rng)
    ref_A = scipy_band(diagonals, n)
    t0 = start()
    A, build_s = timed(band, sparse, diagonals, n, np.float32)
    C = A @ A
    path = last_path("spgemm")
    t_ref = time.perf_counter()
    pattern, err = csr_diff(C.toscipy(), ref_A @ ref_A)
    line = {"phase": "c.spgemm_band", "rows": n, "diags": len(OFFSETS),
            "nnz_out": int(C.nnz), "dtype": "float32",
            "pattern_mismatches": pattern, "build_s": build_s,
            "ref_s": time.perf_counter() - t_ref}
    if pattern:
        raise PhaseError(f"{pattern} pattern mismatches: {line}")
    check(line, err, 1e-5, path, "dia-pallas")
    stamp(line, t0)
    yield line
    del A, C

    m = sz["spgemm_pl_rows"]
    t0 = start()
    P = gallery.powerlaw(m, nnz_per_row=sz["spgemm_pl_nnz"], rng=SEED,
                         dtype=np.float32)
    P.sum_duplicates()
    C = P @ P
    path = last_path("spgemm")
    t_ref = time.perf_counter()
    P_sp = P.toscipy()
    pattern, err = csr_diff(C.toscipy(), P_sp @ P_sp)
    line = {"phase": "c.spgemm_powerlaw", "rows": m, "nnz": int(P.nnz),
            "nnz_out": int(C.nnz), "dtype": "float32",
            "pattern_mismatches": pattern,
            "ref_s": time.perf_counter() - t_ref}
    if pattern:
        raise PhaseError(f"{pattern} pattern mismatches: {line}")
    check(line, err, 1e-5, path, "esc")
    stamp(line, t0)
    yield line


def phase_irregular(sz, rng):
    """d. Irregular SpMV: a power-law matrix (gather path) and a
    block-structured matrix (BSR kernel)."""
    import jax.numpy as jnp

    import legate_sparse_tpu as sparse
    from legate_sparse_tpu import gallery

    m = sz["irr_rows"]
    t0 = start()
    # Duplicates stay (COO semantics, summed by the kernel and by
    # scipy alike): canonicalizing 5e7 entries costs more than the phase.
    P, build_s = timed(lambda: gallery.powerlaw(
        m, nnz_per_row=sz["irr_nnz"], rng=SEED + 1, dtype=np.float32))
    x = rng.random(m, dtype=np.float32)
    y = np.asarray(P @ jnp.asarray(x))
    ref, ref_s = timed(lambda: P.toscipy().astype(np.float64) @ x)
    line = {"phase": "d.spmv_powerlaw", "rows": m, "nnz": int(P.nnz),
            "dtype": "float32", "build_s": build_s, "ref_s": ref_s}
    # f32 sums over rows of up to m entries: 1e-4, not 1e-5.
    check(line, rel_err(y, ref), 1e-4, last_path("spmv"))
    stamp(line, t0)
    yield line
    del P

    ref_B = block_matrix(sz["bsr_block_rows"], sz["bsr_blocks_per_row"],
                         rng)
    n = ref_B.shape[0]
    x = rng.random(n, dtype=np.float32)
    t0 = start()
    Bm, build_s = timed(from_scipy, sparse, ref_B, np.float32)
    y = np.asarray(Bm @ jnp.asarray(x))
    ref, ref_s = timed(lambda: ref_B.astype(np.float64) @ x)
    line = {"phase": "d.spmv_bsr", "rows": n, "nnz": int(Bm.nnz),
            "blocks": sz["bsr_block_rows"] * sz["bsr_blocks_per_row"],
            "dtype": "float32", "build_s": build_s, "ref_s": ref_s}
    check(line, rel_err(y, ref), 1e-5, last_path("spmv"), "bsr")
    stamp(line, t0)
    yield line


def phase_graph(sz, rng):
    """e. BFS levels on a Graph500-style R-MAT graph."""
    from scipy.sparse import csgraph

    from legate_sparse_tpu import gallery, graph

    scale = sz["rmat_scale"]
    t0 = start()
    G, build_s = timed(lambda: gallery.rmat(scale, rng=SEED + 2,
                                            dtype=np.float32))
    levels = np.asarray(graph.bfs(G, source=0))
    dist, ref_s = timed(lambda: csgraph.shortest_path(
        G.toscipy(), unweighted=True, indices=0, directed=True))
    ref = np.where(np.isinf(dist), -1, dist).astype(np.int64)
    bad = int((levels.astype(np.int64) != ref).sum())
    line = {"phase": "e.bfs_rmat", "scale": scale, "vertices": 1 << scale,
            "edges": int(G.nnz), "reached": int((ref >= 0).sum()),
            "depth": int(ref.max()), "mismatches": bad,
            "build_s": build_s, "ref_s": ref_s}
    check(line, float(bad), 0.0, last_path("dist_spmv"))
    stamp(line, t0)
    yield line


PHASES = (phase_band, phase_cg, phase_spgemm, phase_irregular, phase_graph)


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------
def _shard_devices(*arrays) -> int:
    devs = set()
    for a in arrays:
        devs |= {s.device for s in a.addressable_shards}
    return len(devs)


def phase_dist_band(sz, rng, mesh):
    """The phase-a band through ``shard_csr`` + ``dist_spmv``, against
    the one-chip ``A @ x`` of the same run and scipy."""
    import jax.numpy as jnp

    import legate_sparse_tpu as sparse
    from legate_sparse_tpu.parallel import dist_spmv, shard_csr
    from legate_sparse_tpu.parallel.dist_csr import shard_vector

    n = sz["dist_band_rows"]
    diagonals = band_diagonals(n, rng)
    ref_A = scipy_band(diagonals, n)
    x = rng.random(n, dtype=np.float32)
    t0 = start()
    A, build_s = timed(band, sparse, diagonals, n, np.float32)
    y1 = np.asarray(A @ jnp.asarray(x))
    line = {"phase": "4.one_chip_band", "rows": n, "diags": len(OFFSETS),
            "dtype": "float32", "path": last_path("spmv"),
            "build_s": build_s}
    stamp(line, t0)
    yield line

    t0 = start()
    dA, shard_s = timed(lambda: shard_csr(A, mesh=mesh))
    ys = dist_spmv(dA, shard_vector(x, mesh, dA.rows_padded))
    y4 = np.asarray(ys)[:n]
    ndev = _shard_devices(ys, *(a for a in (dA.pdia_data, dA.dia_data)
                                if a is not None))
    line = {"phase": "4.dist_spmv_band", "rows": n, "diags": len(OFFSETS),
            "layout": "1d-row", "dtype": "float32", "shard_devices": ndev,
            "err_vs_one_chip": rel_err(y4, y1), "shard_s": shard_s}
    if ndev != mesh.size:
        raise PhaseError(f"shards on {ndev} devices, mesh has {mesh.size}")
    if not line["err_vs_one_chip"] <= 1e-6:
        raise PhaseError(f"dist result off the one-chip result: {line}")
    check(line, rel_err(y4, ref_A @ x.astype(np.float64)), 1e-5,
          last_path("dist_spmv"))
    stamp(line, t0)
    yield line


def phase_dist_cg(sz, rng, mesh):
    """``dist_cg`` on the 2048^2 Poisson grid, 1d-row and 2d-block,
    against the one-chip ``linalg.cg`` of the same run and scipy."""
    import legate_sparse_tpu as sparse
    from legate_sparse_tpu import linalg
    from legate_sparse_tpu.parallel import dist_cg, shard_csr

    g = sz["dist_cg_grid"]
    b = rng.standard_normal(g * g).astype(np.float32)
    b64 = b.astype(np.float64)
    A_sp = scipy_poisson(g)
    t0 = start()
    A = from_scipy(sparse, A_sp, np.float32)
    x1, it1 = linalg.cg(A, b, rtol=CG_RTOL, maxiter=sz["cg_maxiter"])
    x1 = np.asarray(x1, dtype=np.float64)
    res1 = float(np.linalg.norm(b64 - A_sp @ x1) / np.linalg.norm(b64))
    line = {"phase": "4.cg_one_chip", "rows": g * g, "grid": g,
            "dtype": "float32", "iters": int(it1)}
    check(line, res1, CG_TRUE_TOL, last_path("spmv"))
    stamp(line, t0)
    yield line
    for layout in ("1d-row", "2d-block"):
        t0 = start()
        dA, shard_s = timed(lambda: shard_csr(A, mesh=mesh, layout=layout))
        x4, it4 = dist_cg(dA, b, rtol=CG_RTOL, maxiter=sz["cg_maxiter"])
        ndev = _shard_devices(x4, *(a for a in (dA.data, dA.dia_data)
                                    if a is not None))
        x4 = np.asarray(x4, dtype=np.float64)
        res4 = float(np.linalg.norm(b64 - A_sp @ x4) / np.linalg.norm(b64))
        # Both solves stop within CG_TRUE_TOL, so A (x4 - x1) is bounded
        # by twice that: the two solutions agree as far as CG can say.
        agree = float(np.linalg.norm(A_sp @ (x4 - x1))
                      / np.linalg.norm(b64))
        line = {"phase": f"4.dist_cg_{layout}", "rows": g * g, "grid": g,
                "layout": layout, "dtype": "float32", "iters": int(it4),
                "shard_devices": ndev, "agree_vs_one_chip": agree,
                "shard_s": shard_s}
        if ndev != mesh.size:
            raise PhaseError(f"shards on {ndev} devices, mesh has "
                             f"{mesh.size}")
        if not agree <= 2 * CG_TRUE_TOL:
            raise PhaseError(f"dist CG off the one-chip solve: {line}")
        check(line, res4, CG_TRUE_TOL, last_path("dist_spmv"))
        stamp(line, t0)
        yield line


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------
def run_phase(fn, *args) -> bool:
    """Run one phase; print its lines, or a failure line.  True on
    success."""
    t0 = start()
    try:
        for line in fn(*args):
            print(json.dumps(line), flush=True)
    except Exception as e:  # report, then go on to the next phase
        line = {"phase": fn.__name__, "failed": repr(e)[:2000]}
        stamp(line, t0)
        print(json.dumps(line), flush=True)
        return False
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip dist SpMV and CG phases")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    want = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < want:
        sys.stderr.write(
            f"chip_smoke: needs {want} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)\n")
        return 1

    import legate_sparse_tpu  # noqa: F401  (resolves x64 off on TPU)
    from legate_sparse_tpu import obs
    from legate_sparse_tpu._platform import use_compile_cache

    use_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    obs.enable()
    rng = np.random.default_rng(SEED)
    if args.four_chips:
        from legate_sparse_tpu.parallel.mesh import make_row_mesh

        mesh = make_row_mesh(4)
        ok = all([run_phase(phase_dist_band, FULL, rng, mesh),
                  run_phase(phase_dist_cg, FULL, rng, mesh)])
    else:
        ok = all([run_phase(fn, FULL, rng) for fn in PHASES])
    if not ok:
        sys.stderr.write("chip_smoke: a phase failed\n")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
