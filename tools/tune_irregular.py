# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""On-chip irregular-path shoot-out — a thin CLI over the autotuner.

Races the autotune candidate registry (``csr-rowids`` / ``ell`` /
``sliced-ell``, ``legate_sparse_tpu/autotune/registry.py``) on the
irregular configs the reference's general path serves
(``src/sparse/array/csr/spmv.cc:36-44``), records each winning verdict
into the autotune store, and additionally times the Pallas BSR kernel
(``ops/bsr.py`` — not a registry candidate: it keeps unconditional
dispatch priority) across densities plus a clustered config (dense 8x8
sub-blocks scattered randomly — the FEM-node pattern) where BSR's
per-present-block population, not global density, sets the rate
(IRREGULAR.md law).

Candidate timing goes through ``autotune.measure_candidates`` — the
same harness ``tune()`` and the bench autotune phase use, so this tool
and the runtime agree by construction.  The winner is cross-checked
with the chained-fori_loop protocol (``bench_timing.py``): a large gap
between ``<label>_ms`` and ``winner_loop_ms`` flags a sync problem
instead of hiding it.

One process, on the chip: ``python -m tools.tune_irregular``.
Prints one JSON object; exits non-zero when JAX finds no TPU.
"""

from __future__ import annotations

import json
import sys

import numpy as np


def main() -> None:
    import jax
    import jax.numpy as jnp

    from legate_sparse_tpu import autotune, gallery
    from legate_sparse_tpu.bench_timing import loop_ms_per_iter
    from legate_sparse_tpu.csr import csr_array
    from legate_sparse_tpu.ops.bsr import BsrStructure, bsr_pack
    from legate_sparse_tpu.settings import settings

    if jax.devices()[0].platform != "tpu":
        sys.stderr.write("tune_irregular: no TPU; nothing measured\n")
        sys.exit(1)
    settings.autotune = True
    out = {"platform": jax.devices()[0].platform,
           "platform_fp": autotune.platform_fingerprint(), "configs": []}
    rng = np.random.default_rng(0)

    def measure(A, label):
        A.sum_duplicates()
        rows, cols = A.shape
        nnz = A.nnz
        x = jnp.asarray(rng.standard_normal(cols).astype(A.dtype))
        cfg = {"label": label, "rows": rows, "nnz": nnz,
               "density": round(nnz / (rows * cols), 6),
               "fingerprint": A._get_fingerprint().klass}
        useful_bytes = nnz * 8  # value + col index, CSR-equivalent terms

        # Candidate race through the autotune harness (the runtime's own
        # timing path); the winner becomes a stored verdict.
        try:
            timings = autotune.measure_candidates(A, x, warmup=1, trials=5)
            for lbl, ms in timings.items():
                k = lbl.replace("-", "_")
                cfg[k + "_ms"] = round(ms, 3)
                cfg[k + "_gbs"] = round(useful_bytes / ms / 1e6, 2)
            winner = min(timings, key=timings.get)
            cfg["verdict"] = winner
            key = autotune.key_for(A, "spmv")
            if key is not None:
                autotune.get_store().record(key, winner,
                                            timings_ms=timings, trials=5)
                cfg["verdict_key"] = key.key_id
            # Dispatch-ack cross-check: the chained-loop protocol cannot
            # be fooled by an early block_until_ready return.
            run = autotune.CANDIDATES[winner].run
            ms = loop_ms_per_iter(lambda v: run(A, v, "spmv"), x,
                                  k_lo=2, k_hi=6)
            cfg["winner_loop_ms"] = round(ms, 3)
        except Exception as e:
            cfg["candidates_error"] = repr(e)[:300]

        # Pallas BSR (kept outside the registry: structure-specialized
        # priority path, measured here for the density law).
        pack = bsr_pack(np.asarray(A.data), np.asarray(A.indices),
                        np.asarray(A.indptr), A.shape, max_expand=1e9)
        if pack is not None:
            st = BsrStructure(*pack, rows, cols)
            cfg["nblocks"] = st.nblocks
            cfg["nnz_per_block"] = round(nnz / st.nblocks, 1)
            try:
                ms = loop_ms_per_iter(
                    lambda v: st.matvec(v, interpret=False), x, k_lo=3, k_hi=13)
                cfg["bsr_ms"] = round(ms, 3)
                cfg["bsr_gbs"] = round(useful_bytes / ms / 1e6, 2)
                cfg["bsr_stream_gbs"] = round(
                    (st.nblocks * 128 * 128 * 4) / ms / 1e6, 1)
            except Exception as e:
                cfg["bsr_error"] = repr(e)[:300]
        out["configs"].append(cfg)

    def from_coo(r, c, n):
        order = np.lexsort((c, r))
        vals = np.ones(r.shape[0], np.float32)
        return csr_array((vals[order], (r[order], c[order])), shape=(n, n))

    # Uniform random at increasing density, fixed 64 MB-ish footprint.
    for n, d in [(1 << 14, 0.005), (1 << 14, 0.02), (1 << 13, 0.08)]:
        nnz = int(n * n * d)
        r = rng.integers(0, n, nnz); c = rng.integers(0, n, nnz)
        measure(from_coo(r, c, n), f"uniform_{n}_{d}")

    # Power-law rows (the autotuner's home turf: flat ELL blows its
    # padding budget, sliced ELL bins the skew away).
    measure(gallery.powerlaw(1 << 18, nnz_per_row=8, rng=11),
            "powerlaw_2e18_w8")

    # Clustered: dense 8x8 sub-blocks at random positions (FEM pattern),
    # ~27 blocks per block-row like a 3-D stencil.
    n = 1 << 15
    bs, per_row = 8, 27
    nb = (n // bs) * per_row
    br = np.repeat(np.arange(n // bs), per_row)
    bc = rng.integers(0, n // bs, nb)
    rr = (br[:, None] * bs + np.arange(bs)[None, :]).ravel()
    r = np.repeat(rr, bs)
    c = ((bc[:, None] * bs + np.arange(bs)[None, :])[:, None, :]
         + np.zeros((1, bs, 1), np.int64)).ravel()
    measure(from_coo(r, c, n), "clustered_fem_8x8")

    # Hyper-sparse tail (the adversarial config): expect BSR over budget,
    # the gather candidates are the ceiling; record it honestly.
    n = 1 << 22
    W = 11
    nnz = n * W
    r = np.repeat(np.arange(n), W)
    c = rng.integers(0, n, nnz)
    measure(from_coo(r, c, n), "hyper_sparse_2e22_W11")

    out["verdicts"] = len(autotune.get_store())
    print(json.dumps(out))


if __name__ == "__main__":
    main()
