# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Headline benchmark: CSR SpMV achieved HBM bandwidth on one chip.

Prints ONE JSON line::

    {"metric": "csr_spmv_bandwidth", "value": <GB/s>, "unit": "GB/s",
     "vs_baseline": <fraction of measured stream bandwidth>, ...}

Config matches the reference's SpMV microbenchmark default (banded
matrix, nnz/row=11 — reference ``examples/spmv_microbenchmark.py:34-52``,
``examples/common.py:206-249``) at 2^24 rows.  ``vs_baseline`` is the
achieved fraction of this chip's *measured* stream (triad) bandwidth,
i.e. the roofline fraction BASELINE.md's north-star targets (>= 0.70).
The reference publishes no absolute numbers (BASELINE.md).

Timing methodology (``legate_sparse_tpu/bench_timing.py``): ops run
chained inside one jitted fori_loop at two trip counts and the delta is
divided by the trip-count difference, with a host scalar fetch as the
only trusted sync.  The stream measurement uses 2^26 lanes (512 MB working set) so it cannot hide in VMEM.

Extra keys in the same JSON object (driver contract stays one line):
``platform`` (tpu/cpu), ``stream_gbs`` (measured roofline — the MEDIAN
of 5 samples interleaved with the workload phases; ``stream_samples``
/ ``stream_gbs_min`` / ``stream_gbs_median`` / ``stream_gbs_max``
record the spread that motivated the median),
``irregular_gbs``/``irregular_frac`` (random-sparsity matrix through the
gather/segment-sum path banded never exercises), ``spmv_ms`` (per-
iteration time), ``path`` (dia/ell/csr — which kernel the dispatch
picked; "dia" means the Pallas band kernel on TPU).  A
``cpu_roofline_ratio`` below 0.7 arrives itemized
(``cpu_roofline_items``: mask / pad-allocation / segment-sum-vs-
shifted-add loss terms, each measured); the pde scale anchor carries
its own stream bound and, when more than ~1.3x off it, a ``pde_items``
decomposition.

Device: outside ``--smoke`` the bench runs on the TPU that JAX finds
and exits non-zero when it finds none — a CPU number is never reported
under a device's name.  Each phase is individually guarded so a
mid-bench device fault still emits a JSON line with whatever was
measured.

Comm/mem ledger (schema_version 7): a distributed phase over all
visible devices records ``dist_shards``/``dist_spmv_ms`` and the
STATIC interconnect predictions ``dist_spmv_comm_bytes`` /
``dist_cg_comm_bytes`` (obs/comm.py — deterministic given the mesh, so
``tools/bench_compare.py`` gates them at 1% where timing fields get
the stream-spread noise band), plus ``comm_total_bytes`` and
``mem_peak_rss_mb``.  ``--smoke`` (or LEGATE_SPARSE_TPU_BENCH_SMOKE=1)
is the hermetic CI lane: an 8-virtual-device CPU mesh, tiny sizes — the whole schema in seconds, exercised by
``tests/test_bench_smoke.py`` against ``evidence/BENCH_golden_smoke.json``.

Engine phase (schema_version 8, ``docs/ENGINE.md``): cold (plan
compile) vs warm-cache (same shape bucket, different n — the
zero-retrace hit path) vs micro-batched dispatch, recorded as
``engine_cold_ms``/``engine_warm_ms``/``engine_batched_ms_per_req``
plus the deterministic ``engine_plan_hits``/``engine_plan_misses``
that the smoke golden pins.

Resilience phase (schema_version 9, ``docs/RESILIENCE.md``): a
deterministic fault drill — inject fail-twice-then-recover, trip a
circuit breaker, shed one expired-deadline request — recording the
exact ``resil_retries``/``resil_shed``/``resil_breaker_trips``/
``resil_faults_injected`` the smoke golden pins, plus the
recovered-vs-clean latency pair ``resil_clean_ms``/
``resil_recovered_ms``.

Saturation phase (schema_version 10, obs v3 —
``docs/OBSERVABILITY.md``): a closed-loop arrival generator sweeps
offered load (concurrent closed-loop clients) against the
micro-batching request executor, recording per level the p50/p99
request latency (from the always-on ``lat.engine.request.*``
histograms), throughput, shed count, and mean batch occupancy
(``saturation`` list + top-level ``saturation_p50_ms``/
``saturation_p99_ms``), plus the golden-gated deterministic totals
``saturation_requests``/``saturation_shed``/
``saturation_batched_requests``.

Autotune phase (schema_version 11, ``docs/AUTOTUNER.md``): the
irregular-SpMV proof for the sparsity-fingerprint autotuner — a
seeded power-law matrix (``gallery.powerlaw``) is tuned, one eager
dispatch proves the verdict actually routes (``autotune.route.hits``
delta), and the winning row-binned sliced-ELL kernel is timed against
the flat CSR gather baseline: ``irregular_spmv_ms`` /
``irregular_csr_ms`` / ``irregular_spmv_speedup`` (target >= 1.3x on
the CPU lane) plus the routed-kernel label ``irregular_spmv_path``
and the golden-gated deterministic ``autotune_verdicts``.  The smoke
lane pins the verdict instead of measuring (deterministic golden);
everything restores on exit — the autotuner stays inert for every
other phase.

Gateway fairness phase (schema_version 12, ``docs/ENGINE.md``): a
3-tenant sweep against the multi-tenant admission gateway — a WFQ
packing stage (the interactive tenant's alternating same-bucket
matrices dispatch as stacked multi-matrix batches) and a flood stage
(a background tenant offers 4x its queue quota and deterministically
rejects ``queue_full`` while interactive service is unaffected) —
recording the golden-gated ``gateway_requests`` /
``gateway_dispatches`` / ``gateway_packed`` /
``gateway_rejected_queue_full`` and per-tenant served/shed totals.

Observability: with ``LEGATE_SPARSE_TPU_OBS=1`` the run additionally
writes a ``BENCH_<stamp>.trace.json`` Chrome-trace artifact (path
override: ``LEGATE_SPARSE_TPU_OBS_FILE``) containing phase spans
(``bench.spmv``/``bench.spgemm``/``bench.cg``/``bench.gmg``/... with
nnz/bytes attributes) plus every op-level span and counter the package
recorded — machine-readable per-op evidence instead of one blob (the
v5 VERDICT ask).  ``tools/trace_summary.py`` renders the per-op
table.  If tracing was requested but no spans were produced (silent
no-op wiring), the process exits nonzero.  With tracing disabled the
span API is a no-op and ``bench_wall_s`` is unaffected.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

# Global wall-clock budget: after this many seconds, remaining optional
# phases are skipped (recorded in the JSON) so the contract line always
# lands inside the caller's timeout.
DEADLINE_S = float(os.environ.get("LEGATE_SPARSE_TPU_BENCH_DEADLINE", "1800"))


def _record_stream_stats(result: dict, samples: list) -> float:
    """min/median/max of the interleaved stream samples into the JSON;
    returns the median — the denominator of record.  ``stream_gbs``
    keeps its historical key (now the median) and ``stream2_gbs`` stays
    a superset-contract alias for the second sample."""
    import statistics

    med = statistics.median(samples)
    result["stream_samples"] = [round(s, 2) for s in samples]
    result["stream_gbs_min"] = round(min(samples), 2)
    result["stream_gbs_median"] = round(med, 2)
    result["stream_gbs_max"] = round(max(samples), 2)
    result["stream_gbs"] = round(med, 2)
    if len(samples) > 1:
        result["stream2_gbs"] = round(samples[1], 2)
    return med


def _gflops_cap() -> float:
    """Measured dense-matmul FLOP rate (GFLOP/s) — the box's compute
    ceiling.  Emitted so the CPU fallback ratio is decomposable into
    "provably machine-bound" vs "implementation loss" (VERDICT r4 weak
    #1): banded SpMV at 11 FMAs/element is COMPUTE-bound on a 1-core
    box where STREAM triad (1 FMA per 12 bytes) is not.  The operand is
    an orthogonal matrix, so hundreds of chained applications keep unit
    norm with zero per-iteration normalization cost."""
    import jax.numpy as jnp

    from legate_sparse_tpu.bench_timing import loop_ms_per_iter

    m = 256
    rng = np.random.default_rng(3)
    q, _ = np.linalg.qr(rng.standard_normal((m, m)))
    Q = jnp.asarray(q, dtype=jnp.float32)
    X = jnp.asarray(
        np.linalg.qr(rng.standard_normal((m, m)))[0], dtype=jnp.float32
    )
    ms = loop_ms_per_iter(lambda v: v @ Q, X, k_lo=20, k_hi=200)
    return 2.0 * m * m * m / (ms * 1e-3) / 1e9


def _band_compute_bound_ms(n: int, nnz_per_row: int,
                           gflops: float) -> float:
    """Predicted compute-bound time for one banded SpMV: W multiplies +
    (W-1) adds per output element at the measured matmul FLOP rate."""
    flops = (2 * nnz_per_row - 1) * n
    return flops / (gflops * 1e9) * 1e3


def _banded_config(sparse, n: int, nnz_per_row: int, dtype=np.float32):
    half = nnz_per_row // 2
    offsets = list(range(-half, half + 1))
    # Row sums of 1.0 keep the chained x_{t+1} = A @ x_t magnitude-stable.
    val = np.float32(1.0 / nnz_per_row)
    diagonals = [np.full(n - abs(o), val, dtype=np.float32)
                 for o in offsets]
    return sparse.diags(diagonals, offsets, shape=(n, n), format="csr",
                        dtype=dtype)


def _engine_config(sparse, n: int, nnz_per_row: int, seed: int = 7):
    """Random-column CSR with a DETERMINISTIC nnz and one heavy row:
    random columns defeat band detection and the heavy row blows the
    ELL (and BSR) budgets, so the matrix is engine-eligible on every
    platform — on TPU the engine declines ELL-packable matrices (the
    roofline gather path wins there), and a uniform-row config would
    silently skip the whole phase.  nnz = nnz_per_row * (n + 63)
    exactly, so the shape buckets — and the golden-gated plan
    hit/miss counts — are the same on every machine.  ``seed`` varies
    the column pattern/values only, never the nnz: different seeds
    yield DISTINCT matrices in the SAME shape bucket (the gateway
    phase packs them into one stacked dispatch)."""
    rng = np.random.default_rng(seed)
    counts = np.full(n, nnz_per_row, dtype=np.int64)
    counts[0] = min(64 * nnz_per_row, n)   # ELL-budget breaker
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    nnz = int(indptr[-1])
    indices = rng.integers(0, n, size=nnz).astype(np.int32)
    row_ids = np.repeat(np.arange(n), counts)
    order = np.lexsort((indices, row_ids))
    indices = indices[order]
    data = rng.standard_normal(nnz).astype(np.float32)
    return sparse.csr_array((data, indices, indptr), shape=(n, n))


def _dist2d_config(sparse, n: int, nnz_per_row: int, seed: int = 7):
    """Random-column CSR, symmetrized and diagonally dominated: the
    random columns defeat band detection — so the 1-D baseline pays
    the all_gather x realization a non-banded matrix forces at scale,
    exactly the fight the 2-d-block layout exists to win — while
    A + A^T + 2I keeps the fixed-iteration CG drill numerically tame.
    nnz is a pure function of (n, seed), so the shard shapes — and
    the ``dist2d_*_comm_bytes`` fields derived from them — are
    deterministic and golden-pinnable."""
    import scipy.sparse as sp

    rng = np.random.default_rng(seed)
    nnz = n * max(nnz_per_row // 2, 1)
    rows = rng.integers(0, n, size=nnz)
    cols = rng.integers(0, n, size=nnz)
    vals = rng.standard_normal(nnz).astype(np.float32) / nnz_per_row
    A = sp.coo_array((vals, (rows, cols)), shape=(n, n)).tocsr()
    A = (A + A.T + 2.0 * sp.eye(n, format="csr")).tocsr()
    return sparse.csr_array(
        (A.data.astype(np.float32), A.indices.astype(np.int32),
         A.indptr), shape=A.shape)


def _irregular_config(sparse, n: int, nnz_per_row: int):
    """Random-sparsity CSR with skewed row lengths: defeats band/ELL
    detection (one heavy row) so the gather/segment-sum path runs."""
    rng = np.random.default_rng(0)
    counts = rng.integers(1, 2 * nnz_per_row, size=n).astype(np.int64)
    counts[0] = min(64 * nnz_per_row, n)  # heavy row blows the ELL budget
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    nnz = int(indptr[-1])
    indices = rng.integers(0, n, size=nnz).astype(np.int32)
    # Sort column indices within each row (canonical CSR).
    row_ids = np.repeat(np.arange(n), counts)
    order = np.lexsort((indices, row_ids))
    indices = indices[order]
    data = np.ones(nnz, dtype=np.float32)
    return sparse.csr_array((data, indices, indptr), shape=(n, n))


def _spmv_bytes(A, x) -> int:
    """Byte-traffic model matching the kernel that actually runs —
    delegates to ``csr_array.spmv_traffic_bytes`` (single source of
    truth with the obs spans) after warming the structure caches the
    dispatch would build."""
    if A._get_dia() is None:
        A._get_ell()
    return A.spmv_traffic_bytes(x)


def _time_spmv_ms(A, x, normalize: bool, k_lo: int, k_hi: int) -> float:
    """Chained A @ x per-iteration time; ``normalize`` rescales between
    iterations for matrices whose row sums aren't ~1 (adds 2n words of
    traffic, accounted by the caller)."""
    import jax
    import jax.numpy as jnp

    from legate_sparse_tpu.bench_timing import loop_ms_per_iter

    # Build structure caches eagerly (outside the trace).
    _ = A @ x

    if normalize:
        def step(v):
            y = A @ v
            return y * jax.lax.rsqrt(jnp.mean(y * y) + 1e-20)
    else:
        def step(v):
            return A @ v

    return loop_ms_per_iter(step, x, k_lo=k_lo, k_hi=k_hi)


def _cpu_roofline_items(sparse, A, x, dt_ms: float, bw_ms: float,
                        compute_ms: float) -> dict:
    """Named, MEASURED loss terms for a sub-0.7 ``cpu_roofline_ratio``
    — where the bytes actually go, instead of a bare fraction:

    - ``bound_bw_ms`` / ``bound_compute_ms``: the two roofline legs the
      ratio's numerator is the max of.
    - ``mask_ms``: hole-mask traffic + per-slot select (0.0 when the
      band has no holes — the headline config's band is full).
    - ``pad_alloc_ms``: the padded single-pass form's allocation loss
      vs the interior/edge-split kernel (what ``dia-xla-nopad`` saves).
    - ``segment_sum_ms`` vs ``shifted_add_ms`` at ``segment_sum_n``
      rows: the gather/segment-sum CSR path against the banded
      shifted-add on the same structure — the format choice the dia
      dispatch makes, quantified (measured at a reduced size; the
      segment-sum path is orders of magnitude off and would blow the
      phase budget at full n).
    """
    import jax.numpy as jnp

    from legate_sparse_tpu.bench_timing import loop_ms_per_iter
    from legate_sparse_tpu.ops import dia_ops
    from legate_sparse_tpu.ops import spmv as spmv_ops

    items = {
        "measured_ms": round(dt_ms, 4),
        "bound_bw_ms": round(bw_ms, 4),
        "bound_compute_ms": round(compute_ms, 4),
    }
    dia = A._get_dia()
    if dia is not None:
        data, offs, mask = dia
        shape = A.shape
        ms_nopad = loop_ms_per_iter(
            lambda v: dia_ops.dia_spmv_nopad(data, mask, v, offs, shape),
            x, k_lo=3, k_hi=12)
        items["shifted_add_ms"] = round(ms_nopad, 4)
        if mask is not None:
            ms_nomask = loop_ms_per_iter(
                lambda v: dia_ops.dia_spmv_nopad(data, None, v, offs,
                                                 shape),
                x, k_lo=3, k_hi=12)
            items["mask_ms"] = round(ms_nopad - ms_nomask, 4)
        else:
            items["mask_ms"] = 0.0
        dpad, mpad = A._get_dia_fused()
        ms_fused = loop_ms_per_iter(
            lambda v: dia_ops.dia_spmv_fused(dpad, mpad, v, offs, shape),
            x, k_lo=3, k_hi=12)
        items["pad_alloc_ms"] = round(ms_fused - ms_nopad, 4)
    # Segment-sum referee at a reduced size on the same band structure.
    n_seg = max(min(A.shape[0] // 64, 1 << 18), 1 << 14)
    nnz_per_row = max(len(dia[1]) if dia is not None else 11, 1)
    A_seg = _banded_config(sparse, n_seg, nnz_per_row)
    x_seg = jnp.full((n_seg,), 1.0, dtype=jnp.float32)
    rid = A_seg._get_row_ids()
    items["segment_sum_n"] = n_seg
    items["segment_sum_ms"] = round(loop_ms_per_iter(
        lambda v: spmv_ops.csr_spmv_rowids(
            A_seg.data, A_seg.indices, rid, v, n_seg),
        x_seg, k_lo=2, k_hi=6, k_cap=12), 4)
    dia_seg = A_seg._get_dia()
    if dia_seg is not None:
        items["shifted_add_seg_ms"] = round(loop_ms_per_iter(
            lambda v: dia_ops.dia_spmv_nopad(
                dia_seg[0], dia_seg[2], v, dia_seg[1], A_seg.shape),
            x_seg, k_lo=3, k_hi=12), 4)
    return items


# Bench JSON schema version: bumped whenever the key set or a key's
# meaning changes (BASELINE.md documents the history; the superset
# contract still holds within a version).  7 = comm/mem ledger fields
# + dist phase + schema_version itself.  8 = execution-engine phase
# (engine_cold_ms / engine_warm_ms / engine_batched_ms_per_req +
# golden-gated engine_plan_hits / engine_plan_misses).  9 =
# resilience phase (docs/RESILIENCE.md): deterministic fault drill
# recording golden-gated resil_retries / resil_shed /
# resil_breaker_trips / resil_faults_injected + the recovered-vs-clean
# latency pair resil_clean_ms / resil_recovered_ms.  10 = saturation
# phase (obs v3, docs/OBSERVABILITY.md): closed-loop offered-load
# sweep against the request executor — per-level p50/p99 latency,
# shed count, mean batch occupancy and throughput in ``saturation``,
# top-level ``saturation_p50_ms``/``saturation_p99_ms`` (highest
# level) and the golden-gated deterministic totals
# ``saturation_requests`` / ``saturation_shed`` /
# ``saturation_batched_requests``.  11 = autotune phase
# (docs/AUTOTUNER.md): verdict-routed irregular SpMV on a seeded
# power-law matrix — irregular_spmv_ms / irregular_csr_ms /
# irregular_spmv_speedup / irregular_spmv_path + the golden-gated
# autotune_verdicts.  12 = gateway fairness phase (docs/ENGINE.md):
# 3-tenant admission-gateway sweep (WFQ packing stage + flood stage)
# with the golden-gated deterministic totals ``gateway_requests`` /
# ``gateway_dispatches`` / ``gateway_packed`` /
# ``gateway_rejected_queue_full`` / per-tenant served/shed.  13 =
# dist-2d phase (docs/DIST.md): the same all-device mesh factored as
# a (rows, cols) grid with the auto layout router — golden-gated
# deterministic ``dist2d_spmv_comm_bytes`` /
# ``dist2d_spmv_1d_comm_bytes`` / ``dist2d_cg_comm_bytes`` /
# ``dist2d_spgemm_comm_bytes`` / ``dist2d_spgemm_1d_comm_bytes``
# (the 1-D fields are the equal-device-count baseline the 2-D layout
# must beat) plus ``dist2d_layout`` / ``dist2d_grid`` /
# ``dist2d_cg_iters`` and the timing field ``dist2d_spmv_ms``.  14 =
# obs-overhead probe (docs/OBSERVABILITY.md): the SpMV micro-loop
# re-timed with spans on vs off — ``obs_overhead_pct`` records the
# toggled tracing tax on the hot path (clamped at 0; the always-on
# counters/histograms appear in both arms by design).  15 =
# compressed-storage byte columns (``csr_array.compress``): the
# deterministic per-nnz traffic models ``spmv_bytes_per_nnz`` /
# ``spmv_bytes_per_nnz_bf16`` (golden-pinned exactly), the
# compressed pde anchor ``pde_bytes_per_iter_bf16`` /
# ``pde_ms_per_iter_bf16`` / ``pde_bytes_ratio`` (full lane), and
# the 2-D dist panel field ``dist2d_spmv_comm_bytes_bf16`` — bf16
# panels + int16 block-local indices, exactly half the f32 panel
# bytes, golden-gated through the 1% ``*_comm_bytes`` band.  16 =
# recovery phase (docs/RESILIENCE.md): a deterministic device-loss
# drill mid-``dist_cg`` on the all-device mesh — checkpoint saves at
# the conv-fetch cadence, one seeded loss, shrink -> reshard ->
# restore -> resume — recording the golden-pinned exact
# ``resil_ckpt_saves`` / ``resil_recoveries`` / ``resil_restored``
# plus the measured ``resil_reshard_bytes`` and the timing pair
# ``recovery_clean_ms`` / ``recovery_recovered_ms``.  17 = graph
# phase (docs/GRAPH.md): the four semiring algorithms (BFS or-and,
# SSSP min-plus, CC min-label, PageRank plus-times) on one seeded
# R-MAT matrix over the all-device mesh — golden-pinned exact
# ``graph_n`` / ``graph_nnz`` / ``graph_<alg>_iters`` plus the
# comm-ledger deltas ``graph_<alg>_comm_bytes`` (the
# ``*_comm_bytes`` band) and the informational timing field
# ``graph_ms``.  18 = multi-tenant attribution phase
# (docs/OBSERVABILITY.md): a 3-tenant gateway load plus dist SpMV
# dispatches under tenant contexts and a packed multi-tenant attrib
# scope, with the attribution ledger armed — golden-pinned exact
# ``attrib_requests`` / ``attrib_tenants`` / ``attrib_conserved`` /
# ``attrib_tenant_bytes`` and the comm-ledger delta
# ``attrib_comm_bytes`` (the ``*_comm_bytes`` band), plus the
# informational timing field ``attrib_ms``.  19 = elastic-placement
# phase (docs/PLACEMENT.md): two placed tenants served through the
# gateway's placement routing, a burning-tenant carve planned by the
# pure ``propose()`` over a fixed sensor snapshot and executed by the
# live-migration registry — golden-pinned exact
# ``placement_migrations`` / ``placement_reshard_bytes`` /
# ``placement_routes`` / per-tenant served counts, plus the
# informational timing field ``placement_ms``.  20 = streaming-
# mutation phase (docs/MUTATION.md): a DeltaCSR served through the
# gateway's delta routing while a seeded ``gallery.mutation_stream``
# update storm lands in the side-buffer, then one background
# compaction with an atomic version swap and a post-swap serving
# round — golden-pinned exact ``mutation_updates`` /
# ``mutation_applied`` / ``mutation_merged`` /
# ``mutation_compactions`` / ``mutation_version_swaps`` /
# ``mutation_served`` / ``mutation_routes``, plus the timing pair
# ``mutation_ms`` / ``mutation_compaction_ms`` (serve-while-mutating
# wall time and the off-path merge cost).
SCHEMA_VERSION = 20


def main() -> None:
    import argparse
    import time as _time_mod

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI smoke lane: pin an 8-virtual-device CPU mesh, skip "
             "the heavyweight phases, "
             "shrink everything to seconds — exists so the obs/comm "
             "wiring and the bench JSON schema are exercised on every "
             "tier-1 run, not once per capture round.")
    args, _ = ap.parse_known_args()
    smoke = (args.smoke
             or os.environ.get("LEGATE_SPARSE_TPU_BENCH_SMOKE") == "1")

    t_start = _time_mod.perf_counter()

    def past_deadline(result, phase: str) -> bool:
        elapsed = _time_mod.perf_counter() - t_start
        if elapsed > deadline_s:
            result.setdefault("skipped_after_deadline", []).append(phase)
            return True
        return False

    deadline_s = DEADLINE_S
    if smoke:
        # Deterministic hermetic lane: an 8-way virtual CPU mesh so the
        # dist phase moves real (predicted) bytes over a real collective
        # program.  Inherited env must not change the program away from
        # the committed golden: JAX_PLATFORMS is overridden (a tpu pin
        # would swap the backend) before the package import resolves
        # its dtype policy, the virtual device count is forced to
        # EXACTLY 8 (pin_cpu alone keeps a larger inherited count,
        # which would change dist_shards and every comm prediction),
        # and the deadline env knob is ignored (a short inherited
        # deadline would drop the dist phase and its gated fields).
        import re as _re

        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = _re.sub(r"--xla_force_host_platform_device_count=\d+",
                        "", os.environ.get("XLA_FLAGS", ""))
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
        from legate_sparse_tpu._platform import pin_cpu

        pin_cpu(8)
        deadline_s = 1800.0

    import jax
    import jax.numpy as jnp

    import legate_sparse_tpu as sparse
    from legate_sparse_tpu import obs
    from legate_sparse_tpu._platform import use_compile_cache

    platform = jax.devices()[0].platform
    if not smoke and platform != "tpu":
        sys.stderr.write(
            f"bench: no TPU (JAX found {platform}); outside --smoke the "
            f"bench measures the chip or nothing\n")
        sys.exit(1)
    use_compile_cache()
    obs_requested = obs.enabled()

    result = {
        "metric": "csr_spmv_bandwidth",
        "value": None,
        "unit": "GB/s",
        "vs_baseline": None,
        "platform": platform,
        "schema_version": SCHEMA_VERSION,
    }
    if smoke:
        result["smoke"] = True

    default_log2 = "24" if platform != "cpu" else "20"
    if smoke:
        # The hermetic lane ignores the size/skip env knobs outright:
        # an inherited LOG2_ROWS or SKIP_DIST must not change the
        # program (and so the deterministic *_comm_bytes) away from
        # the committed golden.
        n = 1 << 12
    else:
        n = 1 << int(os.environ.get("LEGATE_SPARSE_TPU_BENCH_LOG2_ROWS",
                                    default_log2))
    nnz_per_row = 11

    # Interleaved stream sampling: 2 samples before the SpMV phase, 3
    # after it, median of the 5 as the denominator of record.  A single
    # pre-workload sample (r05's method) moved 25%+ against the phases
    # it was supposed to referee; the bracketing median samples the
    # machine the numerators actually ran on.  CPU lane only: on-chip
    # HBM is stable run-to-run (r3-r5 captures), so TPU keeps the
    # single measurement.
    stream = None
    stream_samples = []
    n_pre, n_post = (2, 3) if platform == "cpu" else (1, 0)
    stream_lanes = 26
    if smoke:
        # One bracketing pair over a 16 MB working set: enough to give
        # the JSON a spread for the regression gate's noise band, small
        # enough to keep the lane in seconds.
        n_pre, n_post = 1, 1
        stream_lanes = 22

    from legate_sparse_tpu.bench_timing import triad_gbs

    def _sample_stream(k: int) -> None:
        for _ in range(k):
            try:
                stream_samples.append(triad_gbs(log2_lanes=stream_lanes))
            except Exception as e:
                sys.stderr.write(f"bench: stream sample failed: {e!r}\n")

    _sample_stream(n_pre)
    if stream_samples:
        stream = _record_stream_stats(result, stream_samples)

    A = x = dt_ms = None
    try:
        with obs.span("bench.spmv") as _sp, \
                obs.memory.watermark("bench.spmv"):
            A = _banded_config(sparse, n, nnz_per_row)
            x = jnp.full((n,), 1.0, dtype=jnp.float32)
            dt_ms = _time_spmv_ms(A, x, normalize=False, k_lo=5, k_hi=35)
            if _sp is not None:
                _sp.set(nnz=A.nnz, bytes=_spmv_bytes(A, x),
                        rows=n, spmv_ms=round(dt_ms, 4))
    except Exception as e:
        sys.stderr.write(f"bench: banded config failed: {e!r}\n")
        result["error"] = repr(e)[:300]

    _sample_stream(n_post)
    if stream_samples:
        stream = _record_stream_stats(result, stream_samples)

    if dt_ms is not None:
        bw = _spmv_bytes(A, x) / (dt_ms * 1e-3) / 1e9
        result["value"] = round(bw, 2)
        result["spmv_ms"] = round(dt_ms, 4)
        result["path"] = (
            "dia" if A._get_dia() is not None
            else "ell" if A._get_ell() is not None else "csr"
        )
        # Storage-traffic trajectory columns (schema 15): the byte
        # model per nonzero, canonical f32 vs compressed storage
        # (``csr_array.compress``: bf16 values + narrowed indices)
        # against the same f32 operand.  Deterministic — the model
        # reads actual storage itemsizes — so the smoke golden pins
        # both exactly.
        result["spmv_bytes_per_nnz"] = round(
            _spmv_bytes(A, x) / A.nnz, 4)
        try:
            C_s = A.compress()
            result["spmv_bytes_per_nnz_bf16"] = round(
                _spmv_bytes(C_s, x) / C_s.nnz, 4)
            del C_s
        except Exception as e:
            sys.stderr.write(
                f"bench: compressed spmv bytes failed: {e!r}\n")
        if stream:
            frac = round(bw / stream, 4)
            # The contract metric must not be satisfiable by the CPU
            # fallback: report null off-TPU, fallback number separately.
            if platform != "cpu":
                result["vs_baseline"] = frac
            else:
                result["cpu_vs_baseline"] = frac
        if platform == "cpu" and not smoke:
            # (Skipped in --smoke: the gflops cap + itemized roofline
            # cost seconds and the smoke golden gates only the
            # deterministic comm/schema fields.)
            # Decompose the fallback ratio (VERDICT r4 weak #1): the
            # banded SpMV is compute-bound on this box, so the honest
            # denominator for spmv_ms is max(bandwidth time, compute
            # time).  cpu_roofline_ratio ~1.0 = machine-bound; below
            # that = implementation loss.
            try:
                gf = _gflops_cap()
                result["cpu_gflops_cap"] = round(gf, 2)
                pred = _band_compute_bound_ms(n, nnz_per_row, gf)
                result["spmv_compute_bound_ms"] = round(pred, 4)
                if stream:
                    bw_ms = _spmv_bytes(A, x) / (stream * 1e9) * 1e3
                    bound = max(pred, bw_ms)
                    ratio = round(bound / dt_ms, 4)
                    result["cpu_roofline_ratio"] = ratio
                    if ratio < 0.7:
                        # Sub-roofline ratios must arrive itemized into
                        # named, measured loss terms — "0.41, shrug"
                        # (r05) is not actionable evidence.
                        try:
                            result["cpu_roofline_items"] = (
                                _cpu_roofline_items(
                                    sparse, A, x, dt_ms, bw_ms, pred))
                        except Exception as e:
                            sys.stderr.write(
                                f"bench: roofline items failed: {e!r}\n")
            except Exception as e:
                sys.stderr.write(f"bench: gflops cap failed: {e!r}\n")

    # Phase: observability overhead (obs v4, schema 14).  The same
    # SpMV micro-loop timed with spans on vs off — the explicit
    # ``bench.obs_probe`` span per iteration is the toggled cost being
    # measured (counters/histograms are always-on by design and appear
    # in both arms).  Negative deltas are measurement noise, clamped:
    # the field answers "how much does OBS=1 tax the hot path", not
    # "which arm won the coin flip".
    if A is not None and dt_ms is not None:
        try:
            from legate_sparse_tpu.bench_timing import loop_ms_per_iter

            def _obs_probe_step(v):
                with obs.span("bench.obs_probe"):
                    return A @ v

            was_on = obs.enabled()
            try:
                obs.enable()
                ms_on = loop_ms_per_iter(_obs_probe_step, x,
                                         k_lo=3, k_hi=15)
                obs.disable()
                ms_off = loop_ms_per_iter(_obs_probe_step, x,
                                          k_lo=3, k_hi=15)
            finally:
                (obs.enable if was_on else obs.disable)()
            if ms_off > 0:
                result["obs_overhead_pct"] = round(
                    max(0.0, (ms_on - ms_off) / ms_off * 100.0), 2)
        except Exception as e:
            sys.stderr.write(f"bench: obs overhead probe failed: "
                             f"{e!r}\n")

    # Solver evidence in the same JSON line: CG ms/iter on the pde
    # operator (reference examples/pde.py headline).  Two maxiter
    # variants, host-fetch synced; the delta cancels fixed costs.
    if (os.environ.get("LEGATE_SPARSE_TPU_BENCH_SKIP_CG", "0") != "1"
            and not smoke
            and not past_deadline(result, "cg")):
        try:
            import time as _time

            import legate_sparse_tpu.linalg as linalg

            grid = 1 << (10 if platform != "cpu" else 7)
            ng = grid * grid
            main = np.full(ng, 4.0, np.float32)
            off1 = np.full(ng - 1, -1.0, np.float32)
            off1[np.arange(1, grid) * grid - 1] = 0.0
            offn = np.full(ng - grid, -1.0, np.float32)
            A_cg = sparse.diags(
                [main, off1, off1, offn, offn],
                [0, 1, -1, grid, -grid],
                shape=(ng, ng), format="csr", dtype=np.float32,
            )
            b = np.ones(ng, np.float32)

            def timed(maxiter):
                best = float("inf")
                for rep in range(3):
                    t0 = _time.perf_counter()
                    xs, _ = linalg.cg(A_cg, b, rtol=0.0, maxiter=maxiter)
                    _ = float(np.asarray(xs[0]))
                    if rep:
                        best = min(best, _time.perf_counter() - t0)
                return best

            with obs.span("bench.cg") as _sp:
                if _sp is not None:
                    _sp.set(nnz=A_cg.nnz, rows=ng,
                            bytes=_spmv_bytes(
                                A_cg, jnp.ones((ng,), jnp.float32)))
                t1, t2 = timed(100), timed(300)
                if t2 > t1:
                    result["cg_grid"] = f"{grid}x{grid}"
                    result["cg_ms_per_iter"] = round(
                        (t2 - t1) / 200 * 1e3, 4
                    )
                    if _sp is not None:
                        _sp.set(ms_per_iter=result["cg_ms_per_iter"])
                else:
                    sys.stderr.write(
                        f"bench: cg timing unresolvable "
                        f"(t100={t1:.4f}s, t300={t2:.4f}s)\n"
                    )
        except Exception as e:
            sys.stderr.write(f"bench: cg config failed: {e!r}\n")

    if (os.environ.get("LEGATE_SPARSE_TPU_BENCH_SKIP_IRREGULAR", "0") != "1"
            and not smoke
            and not past_deadline(result, "irregular")):
        try:
            A_ir = _irregular_config(sparse, max(n // 16, 1 << 16),
                                     nnz_per_row)
            x_ir = jnp.ones((A_ir.shape[0],), dtype=jnp.float32)
            dt_ms = _time_spmv_ms(A_ir, x_ir, normalize=True,
                                  k_lo=2, k_hi=12)
            extra = 2 * 4 * A_ir.shape[0]  # normalize read+write
            bw_ir = (_spmv_bytes(A_ir, x_ir) + extra) / (dt_ms * 1e-3) / 1e9
            result["irregular_gbs"] = round(bw_ir, 2)
            if stream:
                result["irregular_frac"] = round(bw_ir / stream, 4)
        except Exception as e:
            sys.stderr.write(f"bench: irregular config failed: {e!r}\n")

    # Block-sparse (BSR) irregular path: moderate-density random matrix
    # through the MXU block kernel (ops/bsr.py).  TPU only — interpret
    # mode is pure-Python slow and measures nothing.
    if (platform == "tpu"
            and os.environ.get("LEGATE_SPARSE_TPU_BENCH_SKIP_BSR",
                               "0") != "1"
            and not past_deadline(result, "bsr")):
        try:
            from legate_sparse_tpu.bench_timing import loop_ms_per_iter
            from legate_sparse_tpu.ops.bsr import BsrStructure, bsr_pack

            import scipy.sparse as sp

            nb_n = 1 << 13
            A_sp = sp.random(nb_n, nb_n, density=0.05, format="csr",
                             random_state=np.random.default_rng(1),
                             dtype=np.float32)
            pack = bsr_pack(A_sp.data, A_sp.indices, A_sp.indptr,
                            A_sp.shape, max_expand=1e9)
            st = BsrStructure(*pack, nb_n, nb_n)
            xb = jnp.ones((nb_n,), jnp.float32)
            ms = loop_ms_per_iter(
                lambda v: st.matvec(v, interpret=False), xb,
                k_lo=3, k_hi=13,
            )
            result["bsr_ms"] = round(ms, 4)
            # CSR-equivalent useful bytes (value + index per nnz).
            result["bsr_gbs"] = round(
                A_sp.nnz * 8 / (ms * 1e-3) / 1e9, 2
            )
            result["bsr_stream_gbs"] = round(
                st.nblocks * 128 * 128 * 4 / (ms * 1e-3) / 1e9, 1
            )
        except Exception as e:
            sys.stderr.write(f"bench: bsr config failed: {e!r}\n")

    # Banded SpGEMM end-to-end (BASELINE config 4, reference
    # ``examples/spgemm_microbenchmark.py:74-79``).  Host-coupled (nnz
    # size oracle), so wall-time with a true result fetch.
    if (os.environ.get("LEGATE_SPARSE_TPU_BENCH_SKIP_SPGEMM", "0") != "1"
            and not smoke
            and not past_deadline(result, "spgemm")):
        try:
            import time as _time

            n_gm = 1 << (20 if platform != "cpu" else 16)
            with obs.span("bench.spgemm") as _sp, \
                    obs.memory.watermark("bench.spgemm"):
                A_gm = _banded_config(sparse, n_gm, nnz_per_row)
                best = float("inf")
                for rep in range(3):
                    t0 = _time.perf_counter()
                    C = A_gm @ A_gm
                    _ = float(np.asarray(C.data[0]))
                    if rep:
                        best = min(best, _time.perf_counter() - t0)
                if _sp is not None:
                    itm = C.dtype.itemsize
                    _sp.set(n=n_gm, nnz=C.nnz,
                            bytes=(2 * A_gm.nnz + C.nnz) * itm,
                            spgemm_ms=round(best * 1e3, 2))
            result["spgemm_n"] = n_gm
            result["spgemm_ms"] = round(best * 1e3, 2)
            # Tracked referee (VERDICT r4 weak #3): host scipy on the
            # SAME matrix, same box — the only way to tell shared-VM
            # noise from a real regression round over round.
            try:
                import scipy.sparse as _sp

                A_host = _sp.csr_matrix(
                    (np.asarray(A_gm.data), np.asarray(A_gm.indices),
                     np.asarray(A_gm.indptr)), shape=A_gm.shape)
                best_sp = float("inf")
                for rep in range(3):
                    t0 = _time.perf_counter()
                    _C = A_host @ A_host
                    if rep:
                        best_sp = min(best_sp,
                                      _time.perf_counter() - t0)
                result["spgemm_scipy_ms"] = round(best_sp * 1e3, 2)
                result["spgemm_vs_scipy"] = round(
                    best_sp / max(best, 1e-9), 4
                )
            except Exception as e:
                sys.stderr.write(f"bench: scipy spgemm ref: {e!r}\n")
        except Exception as e:
            sys.stderr.write(f"bench: spgemm config failed: {e!r}\n")

    # GMG-preconditioned CG ms/iter (BASELINE config 5, reference
    # ``examples/gmg.py:397-417``) through the package-native
    # distributed hierarchy on a 1-device mesh (the same code path that
    # scales out).  Two maxiter variants; the delta cancels fixed costs.
    if (os.environ.get("LEGATE_SPARSE_TPU_BENCH_SKIP_GMG", "0") != "1"
            and not smoke
            and not past_deadline(result, "gmg")):
        try:
            import time as _time

            from legate_sparse_tpu.parallel import (
                DistGMG, dist_cg, make_row_mesh, shard_csr,
            )

            grid = 1 << (9 if platform != "cpu" else 6)
            ngm = grid * grid
            main_d = np.full(ngm, 4.0, np.float32)
            off1 = np.full(ngm - 1, -1.0, np.float32)
            off1[np.arange(1, grid) * grid - 1] = 0.0
            offn = np.full(ngm - grid, -1.0, np.float32)
            A_g = sparse.diags(
                [main_d, off1, off1, offn, offn],
                [0, 1, -1, grid, -grid],
                shape=(ngm, ngm), format="csr", dtype=np.float32,
            )
            mesh1 = make_row_mesh(1)
            with obs.span("bench.gmg") as _sp, \
                    obs.memory.watermark("bench.gmg"):
                dA_g = shard_csr(A_g, mesh=mesh1)
                gmg = DistGMG(dA_g, levels=3)
                b_g = np.ones(ngm, np.float32)
                if _sp is not None:
                    _sp.set(nnz=A_g.nnz, rows=ngm,
                            bytes=_spmv_bytes(
                                A_g, jnp.ones((ngm,), jnp.float32)),
                            gmg_cycle_comm_bytes=gmg.cycle_comm_bytes)

                def timed_gmg(maxiter):
                    best = float("inf")
                    for rep in range(3):
                        t0 = _time.perf_counter()
                        xs, _ = dist_cg(dA_g, b_g, M=gmg.cycle,
                                        rtol=0.0, maxiter=maxiter)
                        _ = float(np.asarray(xs[0]))
                        if rep:
                            best = min(best, _time.perf_counter() - t0)
                    return best

                # Robust metric first: chained V-cycle applications (the
                # preconditioner IS the GMG work; magnitude-normalized so
                # hundreds of chained cycles stay finite).  The CG-delta
                # metric can go unresolvable when f32 GMG-CG hits an
                # exactly-zero residual before the low trip count and
                # stops despite rtol=0.
                from legate_sparse_tpu.bench_timing import loop_ms_per_iter
                from legate_sparse_tpu.parallel.dist_csr import shard_vector

                bs = shard_vector(b_g, mesh1, dA_g.rows_padded)

                def cycle_step(v):
                    y = gmg.cycle(v)
                    return y * jax.lax.rsqrt(jnp.mean(y * y) + 1e-20)

                result["gmg_grid"] = f"{grid}x{grid}"
                try:
                    ms_cycle = loop_ms_per_iter(cycle_step, bs, k_lo=3,
                                                k_hi=13)
                    result["gmg_cycle_ms"] = round(ms_cycle, 4)
                except RuntimeError as e:
                    sys.stderr.write(f"bench: gmg cycle timing: {e}\n")

                t1, t2 = timed_gmg(20), timed_gmg(60)
                if t2 > t1:
                    result["gmg_cg_ms_per_iter"] = round(
                        (t2 - t1) / 40 * 1e3, 4
                    )
                else:
                    sys.stderr.write(
                        f"bench: gmg cg timing unresolvable "
                        f"(t20={t1:.4f}s, t60={t2:.4f}s); gmg_cycle_ms "
                        f"is the metric of record for this run\n"
                    )
        except Exception as e:
            sys.stderr.write(f"bench: gmg config failed: {e!r}\n")

    # Distributed phase over ALL visible devices (virtual 8-way CPU
    # mesh in --smoke): the collective program the multi-chip scaling
    # story rides on, with its interconnect bytes priced by the comm
    # ledger (obs/comm.py) and recorded as bench fields — the
    # regression gate treats *_comm_bytes as deterministic, so a code
    # change that silently inflates the collective volume fails
    # tools/bench_compare.py even when the timing noise would hide it.
    if ((smoke
         or os.environ.get("LEGATE_SPARSE_TPU_BENCH_SKIP_DIST",
                           "0") != "1")
            and not past_deadline(result, "dist")):
        try:
            from legate_sparse_tpu.bench_timing import loop_ms_per_iter
            from legate_sparse_tpu.parallel import (
                dist_cg, make_row_mesh, shard_csr,
            )
            from legate_sparse_tpu.parallel.dist_csr import (
                cg_comm_volumes, dist_spmv, shard_vector,
                spmv_comm_volumes,
            )

            mesh_d = make_row_mesh()
            R_d = int(mesh_d.shape["rows"])
            n_d = 1 << (12 if smoke
                        else (22 if platform != "cpu" else 16))
            with obs.span("bench.dist") as _sp, \
                    obs.memory.watermark("bench.dist"):
                A_d = _banded_config(sparse, n_d, nnz_per_row)
                dA = shard_csr(A_d, mesh=mesh_d)
                x_d = shard_vector(np.ones(n_d, np.float32), mesh_d,
                                   dA.rows_padded)
                _ = float(jnp.sum(dist_spmv(dA, x_d)))  # compile+warm
                vols_d = spmv_comm_volumes(dA, dA.rows_padded // R_d, 4)
                result["dist_shards"] = R_d
                result["dist_spmv_comm_bytes"] = sum(vols_d.values())
                try:
                    ms_d = loop_ms_per_iter(
                        lambda v: dist_spmv(dA, v), x_d,
                        k_lo=2, k_hi=8 if smoke else 16,
                    )
                    result["dist_spmv_ms"] = round(ms_d, 4)
                except RuntimeError as e:
                    sys.stderr.write(f"bench: dist spmv timing: {e}\n")
                # Fixed-iteration CG (rtol=0 never converges early):
                # the iteration count — and so the predicted comm
                # volume — is deterministic across machines.
                maxit = 8 if smoke else 25
                xs_d, it_d = dist_cg(dA, np.ones(n_d, np.float32),
                                     rtol=0.0, maxiter=maxit)
                _ = float(np.asarray(xs_d[0]))
                it_d = int(it_d)
                cg_vols, _cg_calls = cg_comm_volumes(dA, 4, it_d)
                result["dist_cg_iters"] = it_d
                result["dist_cg_comm_bytes"] = sum(cg_vols.values())
                if _sp is not None:
                    _sp.set(shards=R_d, rows=n_d,
                            comm_bytes=(sum(vols_d.values())
                                        + sum(cg_vols.values())))
            result["comm_total_bytes"] = int(
                obs.counters.get("comm.total_bytes"))
        except Exception as e:
            sys.stderr.write(f"bench: dist phase failed: {e!r}\n")

    # Distributed 2-d-block phase (schema 13, docs/DIST.md): the same
    # all-device mesh factored as a (rows, cols) grid, on a NON-banded
    # matrix — the case where the 1-D layout degenerates to a full
    # all_gather of x and the communication-avoiding 2-D program
    # (x panels broadcast along mesh rows, partial products
    # reduce-scattered along mesh columns) wins.  Both the 2-D fields
    # and the equal-device-count 1-D baselines are recorded so the
    # golden pins the WIN, not just the totals; the auto router's
    # ``shard_csr.routing`` event cites both predictions.
    if ((smoke
         or os.environ.get("LEGATE_SPARSE_TPU_BENCH_SKIP_DIST",
                           "0") != "1")
            and not past_deadline(result, "dist2d")):
        try:
            from legate_sparse_tpu.bench_timing import loop_ms_per_iter
            from legate_sparse_tpu.parallel import (
                dist_cg, dist_spgemm, make_grid_mesh, make_row_mesh,
                shard_csr,
            )
            from legate_sparse_tpu.parallel.dist_csr import (
                cg_comm_volumes, dist_spmv, shard_vector,
                spmv_comm_volumes,
            )

            def _spgemm_ledger() -> int:
                return sum(
                    v for k, v in obs.counters.snapshot().items()
                    if k.startswith("comm.dist_spgemm.")
                    and k.endswith("_bytes"))

            n_2 = 1 << (10 if smoke
                        else (20 if platform != "cpu" else 14))
            mesh_g = make_grid_mesh()
            gr = int(mesh_g.shape[
                "rows"]), int(mesh_g.shape["cols"])
            with obs.span("bench.dist2d") as _sp2, \
                    obs.memory.watermark("bench.dist2d"):
                A_2 = _dist2d_config(sparse, n_2, nnz_per_row)
                # Equal-device-count 1-D baseline (recorded bytes).
                dA1 = shard_csr(A_2, mesh=make_row_mesh())
                vols1 = spmv_comm_volumes(
                    dA1, dA1.rows_padded // dA1.num_shards, 4)
                result["dist2d_spmv_1d_comm_bytes"] = sum(
                    vols1.values())
                led0 = _spgemm_ledger()
                C1 = dist_spgemm(dA1, dA1)
                result["dist2d_spgemm_1d_comm_bytes"] = (
                    _spgemm_ledger() - led0)
                del C1, dA1
                # 2-D block layout via the byte-predicting router.
                dA2 = shard_csr(A_2, mesh=mesh_g, layout="auto")
                result["dist2d_layout"] = dA2.layout
                result["dist2d_grid"] = f"{gr[0]}x{gr[1]}"
                vols2 = spmv_comm_volumes(
                    dA2, dA2.rows_padded // dA2.num_shards, 4)
                result["dist2d_spmv_comm_bytes"] = sum(vols2.values())
                x_2 = shard_vector(np.ones(n_2, np.float32), mesh_g,
                                   dA2.rows_padded, layout=dA2.layout)
                _ = float(jnp.sum(dist_spmv(dA2, x_2)))  # compile+warm
                try:
                    ms_2 = loop_ms_per_iter(
                        lambda v: dist_spmv(dA2, v), x_2,
                        k_lo=2, k_hi=8 if smoke else 16,
                    )
                    result["dist2d_spmv_ms"] = round(ms_2, 4)
                except RuntimeError as e:
                    sys.stderr.write(
                        f"bench: dist2d spmv timing: {e}\n")
                # Compressed panels (schema 15): the same matrix
                # through ``compress()`` — bf16 panel values, int16
                # block-local indices — with a bf16 x, priced by the
                # SAME ledger formulas as the f32 field (itemsize 2):
                # the all_gather panel bytes exactly halve, and the
                # golden pins the halved total through the 1%
                # ``*_comm_bytes`` gate.  One dispatch exercises the
                # low-precision 2-D kernel for real.
                try:
                    dC2 = shard_csr(A_2.compress(), mesh=mesh_g,
                                    layout=dA2.layout)
                    volsb = spmv_comm_volumes(
                        dC2, dC2.rows_padded // dC2.num_shards, 2)
                    result["dist2d_spmv_comm_bytes_bf16"] = sum(
                        volsb.values())
                    xb_2 = shard_vector(
                        jnp.ones(n_2, jnp.bfloat16), mesh_g,
                        dC2.rows_padded, layout=dC2.layout)
                    _ = float(jnp.sum(dist_spmv(dC2, xb_2)))
                    del dC2, xb_2
                except Exception as e:
                    sys.stderr.write(
                        f"bench: dist2d compressed failed: {e!r}\n")
                # Fixed-iteration CG, as in the 1-D dist phase: the
                # iteration count and so the comm volume are
                # deterministic across machines.
                maxit2 = 8 if smoke else 25
                xs2, it2 = dist_cg(dA2, np.ones(n_2, np.float32),
                                   rtol=0.0, maxiter=maxit2)
                _ = float(np.asarray(xs2[0]))
                it2 = int(it2)
                cg2_vols, _cg2_calls = cg_comm_volumes(dA2, 4, it2)
                result["dist2d_cg_iters"] = it2
                result["dist2d_cg_comm_bytes"] = sum(cg2_vols.values())
                led1 = _spgemm_ledger()
                C2 = dist_spgemm(dA2, dA2)
                result["dist2d_spgemm_comm_bytes"] = (
                    _spgemm_ledger() - led1)
                del C2
                if _sp2 is not None:
                    _sp2.set(grid=gr, layout=dA2.layout,
                             comm_bytes=(sum(vols2.values())
                                         + sum(cg2_vols.values())))
            result["comm_total_bytes"] = int(
                obs.counters.get("comm.total_bytes"))
        except Exception as e:
            sys.stderr.write(f"bench: dist2d phase failed: {e!r}\n")

    # Execution-engine phase (docs/ENGINE.md): cold (plan compile) vs
    # warm-cache (same bucket, DIFFERENT n — the zero-retrace hit
    # path) vs micro-batched dispatch, on a fixed-nnz random matrix.
    # Runs in --smoke too: the plan hit/miss deltas are deterministic
    # given the call sequence below, so the smoke golden pins them and
    # the *_ms fields join the bench_compare trajectory gate.
    if ((smoke
         or os.environ.get("LEGATE_SPARSE_TPU_BENCH_SKIP_ENGINE",
                           "0") != "1")
            and not past_deadline(result, "engine")):
        try:
            import time as _time

            from legate_sparse_tpu.engine import Engine, RequestExecutor

            n_cold = (1 << 12 if smoke else 1 << 16) - 37
            n_warm = (1 << 12 if smoke else 1 << 16) - 101
            with obs.span("bench.engine") as _sp, \
                    obs.memory.watermark("bench.engine"):
                A_cold = _engine_config(sparse, n_cold, nnz_per_row)
                A_warm = _engine_config(sparse, n_warm, nnz_per_row)
                x_cold = jnp.ones((n_cold,), jnp.float32)
                x_warm = jnp.ones((n_warm,), jnp.float32)
                # Fresh engine: the cold number really is a plan build
                # even when the routing flag was on earlier.
                eng = Engine()
                hm0 = (obs.counters.get("engine.plan.hits"),
                       obs.counters.get("engine.plan.misses"))
                t0 = _time.perf_counter()
                y = eng.matvec(A_cold, x_cold)
                if y is None:
                    # A silent decline must be a recorded phase error,
                    # not a TypeError swallowed as one.
                    raise RuntimeError(
                        "engine declined the bench matrix "
                        "(eligibility drifted?)")
                _ = float(np.asarray(y[0]))
                cold_ms = (_time.perf_counter() - t0) * 1e3
                # One untimed hit absorbs A_warm's pack build + the
                # tail-pad op compile; the timed calls are the pure
                # cached-executable path.
                _ = float(np.asarray(eng.matvec(A_warm, x_warm)[0]))
                warm_ms = float("inf")
                for _rep in range(5):
                    t0 = _time.perf_counter()
                    y = eng.matvec(A_warm, x_warm)
                    _ = float(np.asarray(y[0]))
                    warm_ms = min(warm_ms,
                                  (_time.perf_counter() - t0) * 1e3)
                # Batched: 8 same-matrix requests -> ONE stacked SpMM
                # dispatch (deterministic: timeout 0 = flush-only).
                ex = RequestExecutor(eng, max_batch=8, queue_depth=64,
                                     timeout_ms=0)
                reqs = 8
                t0 = _time.perf_counter()
                futs = [ex.submit(A_warm, x_warm) for _r in range(reqs)]
                _ = [float(np.asarray(f.result()[0])) for f in futs]
                batched_ms = (_time.perf_counter() - t0) * 1e3 / reqs
                ex.shutdown()
                result["engine_cold_ms"] = round(cold_ms, 4)
                result["engine_warm_ms"] = round(warm_ms, 4)
                result["engine_warm_speedup"] = round(
                    cold_ms / max(warm_ms, 1e-9), 2)
                result["engine_batched_ms_per_req"] = round(batched_ms,
                                                            4)
                result["engine_batch_requests"] = reqs
                result["engine_plan_hits"] = int(
                    obs.counters.get("engine.plan.hits") - hm0[0])
                result["engine_plan_misses"] = int(
                    obs.counters.get("engine.plan.misses") - hm0[1])
                if _sp is not None:
                    _sp.set(nnz=A_cold.nnz + A_warm.nnz,
                            cold_ms=result["engine_cold_ms"],
                            warm_ms=result["engine_warm_ms"])
        except Exception as e:
            sys.stderr.write(f"bench: engine phase failed: {e!r}\n")

    # Resilience phase (docs/RESILIENCE.md): a deterministic fault
    # drill — fail-twice-then-recover on the csr.dot site (2 retries),
    # a K=3 breaker trip with the typed fast-fail, and one deadline
    # shed through the executor.  The counter deltas are exact given
    # the call sequence, so the smoke golden pins them
    # (resil_retries / resil_shed / resil_breaker_trips /
    # resil_faults_injected) and the recovered-vs-clean latency pair
    # joins the trajectory.  Everything restores on exit: the phase
    # must not leak armed faults or flipped settings into later
    # phases.
    if ((smoke
         or os.environ.get("LEGATE_SPARSE_TPU_BENCH_SKIP_RESIL",
                           "0") != "1")
            and not past_deadline(result, "resil")):
        try:
            import time as _time

            from legate_sparse_tpu import resilience as _resil
            from legate_sparse_tpu.engine import Engine as _REngine
            from legate_sparse_tpu.engine import \
                RequestExecutor as _RExecutor
            from legate_sparse_tpu.resilience import deadline as _rdl
            from legate_sparse_tpu.settings import settings as _rst

            n_r = (1 << 12 if smoke else 1 << 16) - 57
            saved = (_rst.resil, _rst.resil_retries,
                     _rst.resil_backoff_ms, _rst.resil_breaker_k,
                     _rst.resil_breaker_cooldown_ms)
            with obs.span("bench.resil") as _sp:
                try:
                    _rst.resil = True
                    _rst.resil_retries = 2
                    _rst.resil_backoff_ms = 0.0
                    _rst.resil_breaker_k = 3
                    _rst.resil_breaker_cooldown_ms = 50.0
                    _resil.reset()
                    c0 = {k: obs.counters.get(k) for k in (
                        "resil.retry.attempts", "resil.shed",
                        "resil.breaker.trips", "resil.fault.injected")}
                    A_r = _engine_config(sparse, n_r, nnz_per_row)
                    x_r = jnp.ones((n_r,), jnp.float32)
                    _ = float(np.asarray(A_r.dot(x_r)[0]))  # compile
                    t0 = _time.perf_counter()
                    _ = float(np.asarray(A_r.dot(x_r)[0]))
                    clean_ms = (_time.perf_counter() - t0) * 1e3
                    # Drill 1: fail-twice-then-succeed, same path.
                    _resil.inject("csr.dot", kind="error", count=2)
                    t0 = _time.perf_counter()
                    _ = float(np.asarray(A_r.dot(x_r)[0]))
                    recovered_ms = (_time.perf_counter() - t0) * 1e3
                    _resil.faults.clear()
                    # Drill 2: K consecutive failures trip the
                    # breaker; the open breaker fast-fails typed.
                    _rst.resil_retries = 0
                    _resil.inject("csr.dot", kind="error", count=3)
                    for _i in range(4):   # 3 faults + 1 short-circuit
                        try:
                            A_r.dot(x_r)
                        except _resil.ResilienceError:
                            pass
                    _resil.faults.clear()
                    _rst.resil_retries = 2
                    # Drill 3: expired-deadline submit is shed with
                    # the typed Rejected outcome, never dispatched.
                    eng_r = _REngine()
                    ex_r = _RExecutor(eng_r, max_batch=8,
                                      queue_depth=64, timeout_ms=0)
                    with _rdl.scope(0.0):
                        fut = ex_r.submit(A_r, x_r)
                    shed_out = fut.result(timeout=10)
                    ex_r.shutdown()
                    if type(shed_out).__name__ != "Rejected":
                        raise RuntimeError(
                            f"expected Rejected outcome, got "
                            f"{type(shed_out).__name__}")
                    result["resil_clean_ms"] = round(clean_ms, 4)
                    result["resil_recovered_ms"] = round(recovered_ms,
                                                         4)
                    result["resil_recovery_delta_ms"] = round(
                        recovered_ms - clean_ms, 4)
                    result["resil_retries"] = int(obs.counters.get(
                        "resil.retry.attempts")
                        - c0["resil.retry.attempts"])
                    result["resil_shed"] = int(obs.counters.get(
                        "resil.shed") - c0["resil.shed"])
                    result["resil_breaker_trips"] = int(
                        obs.counters.get("resil.breaker.trips")
                        - c0["resil.breaker.trips"])
                    result["resil_faults_injected"] = int(
                        obs.counters.get("resil.fault.injected")
                        - c0["resil.fault.injected"])
                    if _sp is not None:
                        _sp.set(retries=result["resil_retries"],
                                shed=result["resil_shed"],
                                trips=result["resil_breaker_trips"])
                finally:
                    (_rst.resil, _rst.resil_retries,
                     _rst.resil_backoff_ms, _rst.resil_breaker_k,
                     _rst.resil_breaker_cooldown_ms) = saved
                    _resil.reset()
        except Exception as e:
            sys.stderr.write(f"bench: resil phase failed: {e!r}\n")

    # Recovery phase (schema_version 16, docs/RESILIENCE.md): a
    # deterministic device-loss drill mid-``dist_cg`` on the
    # all-device mesh.  Checkpoints ride the conv-fetch cadence
    # (every 10 iterations), a seeded loss fires at the second fetch,
    # and the ladder shrinks the mesh, reshards the operands, restores
    # the it=20 snapshot and resumes the remaining budget.  With
    # rtol=0 the iteration plan is fixed, so the counter deltas are
    # exact and the smoke golden pins them: 4 checkpoint saves
    # (two pre-loss + two post-restore), 1 recovery restoring 20
    # iterations, and the measured survivor-repartition bytes.
    if ((smoke
         or os.environ.get("LEGATE_SPARSE_TPU_BENCH_SKIP_RECOVERY",
                           "0") != "1")
            and not past_deadline(result, "recovery")):
        try:
            import time as _time

            from legate_sparse_tpu import resilience as _resil
            from legate_sparse_tpu.parallel import (
                dist_cg, make_row_mesh, shard_csr,
            )
            from legate_sparse_tpu.settings import settings as _rst

            mesh_rec = make_row_mesh()
            if int(mesh_rec.shape["rows"]) >= 2:
                n_rec = 1 << (12 if smoke else 16)
                maxit_rec, cti_rec = 40, 10
                saved = (_rst.resil, _rst.resil_ckpt_iters,
                         _rst.resil_backoff_ms)
                with obs.span("bench.recovery") as _sp:
                    try:
                        _rst.resil = True
                        _rst.resil_ckpt_iters = cti_rec
                        _rst.resil_backoff_ms = 0.0
                        _resil.reset()
                        A_rec = _banded_config(sparse, n_rec,
                                               nnz_per_row)
                        dA_rec = shard_csr(A_rec, mesh=mesh_rec)
                        b_rec = np.ones(n_rec, np.float32)
                        _ = dist_cg(dA_rec, b_rec, rtol=0.0,
                                    maxiter=maxit_rec,
                                    conv_test_iters=cti_rec)  # compile
                        t0 = _time.perf_counter()
                        _ = dist_cg(dA_rec, b_rec, rtol=0.0,
                                    maxiter=maxit_rec,
                                    conv_test_iters=cti_rec)
                        clean_ms = (_time.perf_counter() - t0) * 1e3
                        c0 = {k: obs.counters.get(k) for k in (
                            "resil.ckpt.saves",
                            "resil.recovery.attempts",
                            "resil.recovery.restored_iters",
                            "resil.recovery.reshard_bytes")}
                        _resil.inject("solver.cg.conv", "device_loss",
                                      after=2, device=1)
                        t0 = _time.perf_counter()
                        _x, it_rec = dist_cg(dA_rec, b_rec, rtol=0.0,
                                             maxiter=maxit_rec,
                                             conv_test_iters=cti_rec)
                        recovered_ms = (_time.perf_counter() - t0) * 1e3
                        result["recovery_clean_ms"] = round(clean_ms, 4)
                        result["recovery_recovered_ms"] = round(
                            recovered_ms, 4)
                        result["resil_ckpt_saves"] = int(
                            obs.counters.get("resil.ckpt.saves")
                            - c0["resil.ckpt.saves"])
                        result["resil_recoveries"] = int(
                            obs.counters.get("resil.recovery.attempts")
                            - c0["resil.recovery.attempts"])
                        result["resil_restored"] = int(
                            obs.counters.get(
                                "resil.recovery.restored_iters")
                            - c0["resil.recovery.restored_iters"])
                        result["resil_reshard_bytes"] = int(
                            obs.counters.get(
                                "resil.recovery.reshard_bytes")
                            - c0["resil.recovery.reshard_bytes"])
                        if _sp is not None:
                            _sp.set(
                                saves=result["resil_ckpt_saves"],
                                recoveries=result["resil_recoveries"],
                                reshard_bytes=result[
                                    "resil_reshard_bytes"],
                                iters=int(it_rec))
                    finally:
                        (_rst.resil, _rst.resil_ckpt_iters,
                         _rst.resil_backoff_ms) = saved
                        _resil.reset()
        except Exception as e:
            sys.stderr.write(f"bench: recovery phase failed: {e!r}\n")

    # Graph phase (schema_version 17, docs/GRAPH.md): the four
    # semiring algorithms on one seeded R-MAT matrix over the
    # all-device mesh.  Every input is deterministic (fixed rng,
    # fixed shapes) and the host loops run a fixed number of sweeps
    # given the structure (BFS/CC to their fixed points, SSSP to the
    # Bellman-Ford fixed point, PageRank with tol=0 to exactly
    # ``pr_iters``), so the smoke golden pins the per-algorithm
    # iteration counts exactly and the per-algorithm
    # ``graph_<alg>_comm_bytes`` (delta of ``comm.total_bytes``
    # around each run) through the ``*_comm_bytes`` band.  Timings
    # stay informational.
    if ((smoke
         or os.environ.get("LEGATE_SPARSE_TPU_BENCH_SKIP_GRAPH",
                           "0") != "1")
            and not past_deadline(result, "graph")):
        try:
            import time as _time

            from legate_sparse_tpu import gallery as _gallery
            from legate_sparse_tpu import graph as _graph

            scale_g = 9 if smoke else 13
            pr_iters = 20
            A_g = _gallery.rmat(scale_g, nnz_per_row=4,
                                rng=np.random.default_rng(1234),
                                directed=True)
            result["graph_n"] = int(A_g.shape[0])
            result["graph_nnz"] = int(A_g.nnz)
            runs = (
                ("bfs", lambda: _graph.bfs(A_g, source=0)),
                ("sssp", lambda: _graph.sssp(A_g, source=0)),
                ("cc", lambda: _graph.connected_components(A_g)),
                ("pagerank", lambda: _graph.pagerank(
                    A_g, tol=0.0, max_iters=pr_iters)),
            )
            with obs.span("bench.graph") as _sp:
                t0 = _time.perf_counter()
                for name_g, run_g in runs:
                    it_key = f"graph.{name_g}.iters"
                    it0 = obs.counters.get(it_key)
                    b0 = obs.counters.get("comm.total_bytes")
                    out_g = run_g()
                    jax.block_until_ready(
                        out_g[1] if isinstance(out_g, tuple)
                        else out_g)
                    result[f"graph_{name_g}_iters"] = int(
                        obs.counters.get(it_key) - it0)
                    result[f"graph_{name_g}_comm_bytes"] = int(
                        obs.counters.get("comm.total_bytes") - b0)
                result["graph_ms"] = round(
                    (_time.perf_counter() - t0) * 1e3, 4)
                if _sp is not None:
                    _sp.set(n=result["graph_n"],
                            nnz=result["graph_nnz"],
                            bfs_iters=result["graph_bfs_iters"],
                            pagerank_iters=result[
                                "graph_pagerank_iters"])
        except Exception as e:
            sys.stderr.write(f"bench: graph phase failed: {e!r}\n")

    # Saturation phase (schema_version 10, obs v3): offered load vs
    # the request executor — the p50/p99-vs-load curve ROADMAP item 1
    # (the serving gateway) is judged by.  A closed-loop arrival
    # generator (``clients`` threads, each submit -> wait -> resubmit)
    # sweeps concurrency levels against one executor; per level the
    # always-on ``lat.engine.request.*`` histograms yield p50/p99 and
    # the counter deltas yield throughput and mean batch occupancy.
    # SpMM plans for every batch width are warmed first, so the curve
    # measures queueing + dispatch, not compiles.  Totals are
    # deterministic given the fixed sweep (request count, occupancy
    # total = every request batched exactly once, and one
    # deadline-shed drill request), so the smoke golden pins
    # ``saturation_requests`` / ``saturation_shed`` /
    # ``saturation_batched_requests``; per-level timings stay
    # informational (thread-timing dependent).
    if ((smoke
         or os.environ.get("LEGATE_SPARSE_TPU_BENCH_SKIP_SATURATION",
                           "0") != "1")
            and not past_deadline(result, "saturation")):
        try:
            import threading as _threading
            import time as _time

            from legate_sparse_tpu.engine import Engine as _SEngine
            from legate_sparse_tpu.engine import \
                RequestExecutor as _SExecutor
            from legate_sparse_tpu.obs import latency as _lat_s
            from legate_sparse_tpu.resilience import deadline as _sdl
            from legate_sparse_tpu.settings import settings as _sst

            n_s = (1 << 12 if smoke else 1 << 16) - 73
            levels = [1, 2, 4, 8] if smoke else [1, 2, 4, 8, 16]
            per_client = 4 if smoke else 8
            with obs.span("bench.saturation") as _sp:
                A_s = _engine_config(sparse, n_s, nnz_per_row)
                x_s = jnp.ones((n_s,), jnp.float32)
                eng_s = _SEngine()
                ex_s = _SExecutor(eng_s, max_batch=8, queue_depth=64,
                                  timeout_ms=0.5)
                # Pre-compile every plan the sweep can hit (spmv for
                # width-1 flushes, spmm per pow2 batch width): the
                # latency curve must measure queueing + dispatch, not
                # XLA compiles.  The whole sweep runs under
                # try/finally: a failed level or drill must not leak
                # the executor (daemon worker + anchored matrix) into
                # the phases that follow.
                try:
                    eng_s.warmup(
                        [{"op": "spmv", "rows": n_s, "nnz": A_s.nnz}]
                        + [{"op": "spmm", "rows": n_s,
                            "nnz": A_s.nnz, "k": k} for k in levels
                           if 1 < k <= 8])  # widths cap at max_batch=8
                    _ = np.asarray(ex_s.submit(A_s, x_s).result(
                        timeout=60))     # pack build outside the sweep
                    c0 = {k: obs.counters.get(k) for k in (
                        "engine.exec.outcome.resolved",
                        "engine.exec.batched_requests",
                        "resil.shed")}
                    sat_levels = []
                    for clients in levels:
                        _lat_s.reset("lat.engine.request")
                        b0_breq = obs.counters.get(
                            "engine.exec.batched_requests")
                        b0_bat = obs.counters.get(
                            "engine.exec.batches")
                        errors = []

                        def _client():
                            try:
                                for _r in range(per_client):
                                    f = ex_s.submit(A_s, x_s)
                                    _ = np.asarray(
                                        f.result(timeout=120))
                            except Exception as e:  # raised after join
                                errors.append(e)

                        t0 = _time.perf_counter()
                        ts = [_threading.Thread(target=_client)
                              for _c in range(clients)]
                        for t in ts:
                            t.start()
                        for t in ts:
                            t.join()
                        wall = _time.perf_counter() - t0
                        if errors:
                            raise errors[0]
                        merged = None
                        for h in _lat_s.snapshot(
                                "lat.engine.request").values():
                            merged = (h if merged is None
                                      else merged.merge(h))
                        d_bat = (obs.counters.get(
                            "engine.exec.batches") - b0_bat)
                        d_breq = (obs.counters.get(
                            "engine.exec.batched_requests") - b0_breq)
                        reqs = clients * per_client
                        sat_levels.append({
                            "clients": clients,
                            "requests": reqs,
                            "p50_ms": round(merged.quantile(0.5), 4),
                            "p99_ms": round(merged.quantile(0.99), 4),
                            "throughput_rps": round(
                                reqs / max(wall, 1e-9), 1),
                            "mean_batch_occupancy": round(
                                d_breq / max(d_bat, 1), 2),
                            "shed": 0,   # no deadlines in the sweep
                        })
                    # Deadline-shed drill: one pre-expired request
                    # proves the shed path records its wait and the
                    # shed total moves — deterministic (+1),
                    # golden-gated.
                    saved_res = _sst.resil
                    try:
                        _sst.resil = True
                        with _sdl.scope(0.0):
                            fut = ex_s.submit(A_s, x_s)
                        out_shed = fut.result(timeout=10)
                        if type(out_shed).__name__ != "Rejected":
                            raise RuntimeError(
                                f"expected Rejected outcome, got "
                                f"{type(out_shed).__name__}")
                    finally:
                        _sst.resil = saved_res
                finally:
                    # A failed level/drill must not leak the executor
                    # (daemon worker + anchored 65k-row matrix) into
                    # the phases that follow.
                    ex_s.shutdown()
                result["saturation"] = sat_levels
                result["saturation_requests"] = int(
                    obs.counters.get("engine.exec.outcome.resolved")
                    - c0["engine.exec.outcome.resolved"])
                result["saturation_shed"] = int(
                    obs.counters.get("resil.shed") - c0["resil.shed"])
                result["saturation_batched_requests"] = int(
                    obs.counters.get("engine.exec.batched_requests")
                    - c0["engine.exec.batched_requests"])
                result["saturation_p50_ms"] = sat_levels[-1]["p50_ms"]
                result["saturation_p99_ms"] = sat_levels[-1]["p99_ms"]
                if _sp is not None:
                    _sp.set(levels=len(levels),
                            requests=result["saturation_requests"],
                            p99_ms=result["saturation_p99_ms"])
        except Exception as e:
            sys.stderr.write(f"bench: saturation phase failed: {e!r}\n")

    # Gateway fairness phase (schema_version 12, docs/ENGINE.md): the
    # multi-tenant admission gateway under a 3-tenant load, in two
    # stages.  Stage A (max_batch=4) proves WFQ batch formation and
    # cross-matrix packing: the interactive tenant alternates two
    # distinct same-bucket matrices, so its batches dispatch as ONE
    # stacked multi-matrix kernel (gateway.packed moves).  Stage B
    # (flush-only, wide batch, tenant_quota=8) proves overload
    # isolation: a background tenant floods 32 requests against an
    # 8-deep quota — deterministically 24 ``queue_full`` rejections —
    # while the interactive tenant's served count is unaffected.  All
    # totals are deterministic given the fixed submission sequence, so
    # the smoke golden pins them.
    if ((smoke
         or os.environ.get("LEGATE_SPARSE_TPU_BENCH_SKIP_GATEWAY",
                           "0") != "1")
            and not past_deadline(result, "gateway")):
        try:
            from legate_sparse_tpu.engine import Engine as _GEngine
            from legate_sparse_tpu.engine import Gateway as _GGateway
            from legate_sparse_tpu.settings import settings as _gst

            n_g = (1 << 12 if smoke else 1 << 14) - 91
            with obs.span("bench.gateway") as _sp:
                A_g1 = _engine_config(sparse, n_g, nnz_per_row)
                A_g2 = _engine_config(sparse, n_g, nnz_per_row,
                                      seed=13)
                A_g3 = _engine_config(sparse, n_g, nnz_per_row,
                                      seed=29)
                x_g = jnp.ones((n_g,), jnp.float32)
                gw_counters = (
                    "gateway.submitted",
                    "gateway.dispatches",
                    "gateway.packed",
                    "gateway.rejected.queue_full",
                    "gateway.tenant.interactive.served",
                    "gateway.tenant.interactive.shed",
                    "gateway.tenant.batch.served",
                    "gateway.tenant.background.served",
                    "gateway.tenant.background.shed",
                )
                c0g = {k: obs.counters.get(k) for k in gw_counters}
                saved_gw = _gst.gateway
                try:
                    _gst.gateway = True

                    def _load(gw):
                        futs = []
                        for i in range(8):
                            futs.append(gw.submit(
                                A_g1 if i % 2 == 0 else A_g2, x_g,
                                tenant="interactive",
                                qos="interactive"))
                        for _i in range(8):
                            futs.append(gw.submit(
                                A_g3, x_g, tenant="batch",
                                qos="batch"))
                        for _i in range(32):
                            futs.append(gw.submit(
                                A_g1, x_g, tenant="background",
                                qos="background"))
                        gw.flush()
                        for f in futs:
                            _ = f.result(timeout=120)

                    # Stage A: tight batches — the 4th pending request
                    # triggers dispatch in the submitting thread, so
                    # the interactive tenant's alternating matrices
                    # land in packed multi-matrix batches.
                    gw_a = _GGateway(
                        _GEngine(), max_batch=4, queue_depth=128,
                        tenant_quota=64, rate=0.0, burst=16.0,
                        slack_ms=5.0, timeout_ms=0.0)
                    try:
                        _load(gw_a)
                    finally:
                        gw_a.shutdown()
                    # Stage B: flood — nothing dispatches during
                    # submission (max_batch exceeds the offered load),
                    # so the background tenant fills its 8-deep quota
                    # and the remaining 24 submissions reject.
                    gw_b = _GGateway(
                        _GEngine(), max_batch=32, queue_depth=128,
                        tenant_quota=8, rate=0.0, burst=16.0,
                        slack_ms=5.0, timeout_ms=0.0)
                    try:
                        _load(gw_b)
                    finally:
                        gw_b.shutdown()
                finally:
                    _gst.gateway = saved_gw

                def _dg(name):
                    return int(obs.counters.get(name) - c0g[name])

                result["gateway_requests"] = _dg("gateway.submitted")
                result["gateway_dispatches"] = _dg(
                    "gateway.dispatches")
                result["gateway_packed"] = _dg("gateway.packed")
                result["gateway_rejected_queue_full"] = _dg(
                    "gateway.rejected.queue_full")
                result["gateway_interactive_served"] = _dg(
                    "gateway.tenant.interactive.served")
                result["gateway_interactive_shed"] = _dg(
                    "gateway.tenant.interactive.shed")
                result["gateway_batch_served"] = _dg(
                    "gateway.tenant.batch.served")
                result["gateway_background_served"] = _dg(
                    "gateway.tenant.background.served")
                result["gateway_background_shed"] = _dg(
                    "gateway.tenant.background.shed")
                if _sp is not None:
                    _sp.set(requests=result["gateway_requests"],
                            packed=result["gateway_packed"],
                            rejected=result[
                                "gateway_rejected_queue_full"])
        except Exception as e:
            sys.stderr.write(f"bench: gateway phase failed: {e!r}\n")

    # Multi-tenant attribution phase (schema 18,
    # docs/OBSERVABILITY.md): the elastic-placement sensor proof.
    # With the attribution ledger armed (restored on exit — it must
    # stay inert for every other phase): (a) a 2-tenant gateway load
    # whose alternating matrices land in packed multi-tenant batches,
    # exercising the declared split rule on real dispatches; (b) two
    # dist SpMV dispatches — one under a single-tenant TraceContext,
    # one under a packed 3-member attrib scope — pushing real
    # comm-ledger bytes (remainder included) through the apportioner.
    # The conservation verdict (per-tenant byte sum == the untagged
    # comm.total_bytes delta, exactly) and the deterministic totals
    # are golden-pinned in smoke.
    if ((smoke
         or os.environ.get("LEGATE_SPARSE_TPU_BENCH_SKIP_ATTRIB",
                           "0") != "1")
            and not past_deadline(result, "attrib")):
        try:
            from legate_sparse_tpu.engine import Engine as _AEngine
            from legate_sparse_tpu.engine import Gateway as _AGateway
            from legate_sparse_tpu.obs import attrib as _attrib_mod
            from legate_sparse_tpu.obs import context as _actx
            from legate_sparse_tpu.parallel import (
                make_row_mesh as _a_mesh, shard_csr as _a_shard,
            )
            from legate_sparse_tpu.parallel.dist_csr import (
                dist_spmv as _a_spmv, shard_vector as _a_shard_vec,
            )
            from legate_sparse_tpu.settings import settings as _ast2

            t_attr0 = _time_mod.perf_counter()
            n_a = (1 << 12 if smoke else 1 << 14) - 91
            with obs.span("bench.attrib") as _sp:
                A_a1 = _engine_config(sparse, n_a, nnz_per_row)
                A_a2 = _engine_config(sparse, n_a, nnz_per_row,
                                      seed=13)
                x_a = jnp.ones((n_a,), jnp.float32)
                at_tenants = ("interactive", "batch", "background")
                comm0 = int(obs.counters.get("comm.total_bytes"))
                at_counters = ["attrib.total.comm_bytes",
                               "gateway.packed", "gateway.submitted"]
                at_counters += [f"attrib.tenant.{t}.comm_bytes"
                                for t in at_tenants
                                + ("__untagged__",)]
                c0a = {k: obs.counters.get(k) for k in at_counters}
                saved_gw2 = _ast2.gateway
                saved_attr = _ast2.obs_attrib
                try:
                    _ast2.gateway = True
                    _ast2.obs_attrib = True
                    gw_at = _AGateway(
                        _AEngine(), max_batch=4, queue_depth=128,
                        tenant_quota=64, rate=0.0, burst=16.0,
                        slack_ms=5.0, timeout_ms=0.0)
                    try:
                        futs = []
                        for i in range(8):
                            futs.append(gw_at.submit(
                                A_a1 if i % 2 == 0 else A_a2, x_a,
                                tenant="interactive",
                                qos="interactive"))
                        for _i in range(8):
                            futs.append(gw_at.submit(
                                A_a2, x_a, tenant="batch",
                                qos="batch"))
                        gw_at.flush()
                        for f in futs:
                            _ = f.result(timeout=120)
                    finally:
                        gw_at.shutdown()
                    # Dist segment: real collective bytes through the
                    # apportioner — the conservation proof is only
                    # meaningful on non-zero volumes.
                    mesh_a = _a_mesh()
                    A_ad = _banded_config(
                        sparse, 1 << (12 if smoke else 14),
                        nnz_per_row)
                    dA_a = _a_shard(A_ad, mesh=mesh_a)
                    x_ad = _a_shard_vec(
                        np.ones(A_ad.shape[0], np.float32), mesh_a,
                        dA_a.rows_padded)
                    with _actx.use(_actx.TraceContext(
                            "bench-attrib-one", tenant="interactive",
                            qos="interactive")):
                        _ = float(jnp.sum(_a_spmv(dA_a, x_ad)))
                    with _attrib_mod.scope([(t, t)
                                            for t in at_tenants]):
                        _ = float(jnp.sum(_a_spmv(dA_a, x_ad)))
                finally:
                    _ast2.gateway = saved_gw2
                    _ast2.obs_attrib = saved_attr

                def _da(name):
                    return int(obs.counters.get(name) - c0a[name])

                comm_delta = int(
                    obs.counters.get("comm.total_bytes")) - comm0
                # Conservation sums over EVERY attribution target —
                # the named tenants plus the __untagged__ sink — so
                # the invariant stays exact even if an untagged comm
                # source ever lands inside the armed window.
                tenant_bytes = sum(
                    _da(f"attrib.tenant.{t}.comm_bytes")
                    for t in at_tenants + ("__untagged__",))
                result["attrib_requests"] = _da("gateway.submitted")
                result["attrib_packed"] = _da("gateway.packed")
                result["attrib_comm_bytes"] = comm_delta
                result["attrib_tenant_comm_bytes"] = tenant_bytes
                result["attrib_tenants"] = sum(
                    1 for t in at_tenants
                    if _da(f"attrib.tenant.{t}.comm_bytes"))
                result["attrib_conserved"] = int(
                    tenant_bytes == _da("attrib.total.comm_bytes")
                    == comm_delta and comm_delta > 0)
                result["attrib_ms"] = round(
                    (_time_mod.perf_counter() - t_attr0) * 1e3, 3)
                if _sp is not None:
                    _sp.set(requests=result["attrib_requests"],
                            comm_bytes=comm_delta,
                            conserved=result["attrib_conserved"])
        except Exception as e:
            sys.stderr.write(f"bench: attrib phase failed: {e!r}\n")

    # Elastic-placement phase (schema 19, docs/PLACEMENT.md): the
    # planner + actuator proof.  Two placed tenants serve through the
    # gateway's placement routing (pre-carve on the plain local path),
    # then a burning-tenant plan from the pure ``propose()`` carves
    # the noisy tenant a 7-device submesh and live-migrates both —
    # declared ``comm.dist_reshard.*`` bytes equal the priced plan by
    # construction — and a second round serves on the new carve.  The
    # snapshot is FIXED, not sensed: the live attribution ledger's
    # busy/wait numbers are timing-noisy and would flap the carve
    # (and so the golden-pinned priced bytes); the sensed closed loop
    # is pinned end-to-end by tests/test_placement.py instead.  All
    # counted totals are deterministic, so the smoke golden pins them.
    if ((smoke
         or os.environ.get("LEGATE_SPARSE_TPU_BENCH_SKIP_PLACEMENT",
                           "0") != "1")
            and not past_deadline(result, "placement")):
        try:
            import jax as _pjax

            from legate_sparse_tpu import placement as _placement
            from legate_sparse_tpu.engine import Engine as _PEngine
            from legate_sparse_tpu.engine import Gateway as _PGateway
            from legate_sparse_tpu.engine.gateway import (
                QOS_WEIGHTS as _p_weights,
            )
            from legate_sparse_tpu.settings import settings as _pst

            t_p0 = _time_mod.perf_counter()
            n_p = (1 << 12 if smoke else 1 << 14) - 91
            with obs.span("bench.placement") as _sp:
                A_p1 = _engine_config(sparse, n_p, nnz_per_row)
                A_p2 = _engine_config(sparse, n_p, nnz_per_row,
                                      seed=13)
                x_p = jnp.ones((n_p,), jnp.float32)
                p_counters = (
                    "placement.migrations",
                    "placement.migration.bytes",
                    "placement.routes",
                    "gateway.tenant.noisy.served",
                    "gateway.tenant.noisy.shed",
                    "gateway.tenant.quiet.served",
                    "gateway.tenant.quiet.shed",
                )
                c0p = {k: obs.counters.get(k) for k in p_counters}
                saved_p = (_pst.gateway, _pst.placement)
                try:
                    _pst.gateway = True
                    _pst.placement = True
                    _placement.reset()
                    _placement.place("noisy", A_p1)
                    _placement.place("quiet", A_p2)

                    def _pload(gw, n_noisy, n_quiet):
                        futs = [gw.submit(A_p1, x_p, tenant="noisy",
                                          qos="interactive")
                                for _i in range(n_noisy)]
                        futs += [gw.submit(A_p2, x_p, tenant="quiet",
                                           qos="background")
                                 for _i in range(n_quiet)]
                        gw.flush()
                        for f in futs:
                            _ = f.result(timeout=120)

                    gw_p = _PGateway(
                        _PEngine(), max_batch=4, queue_depth=128,
                        tenant_quota=64, rate=0.0, burst=16.0,
                        slack_ms=5.0, timeout_ms=0.0)
                    try:
                        _pload(gw_p, 16, 4)
                        devs = _pjax.devices()
                        reg = _placement.registry()
                        snap = _placement.PlacementSnapshot(
                            demand={
                                "noisy": {"busy_ns": 8_000_000_000,
                                          "qos": "interactive"},
                                "quiet": {"busy_ns": 1_000_000_000,
                                          "qos": "background"},
                            },
                            qos_weights=dict(_p_weights),
                            burns={"interactive": 1000.0},
                            devices=len(devs),
                            current=reg.slices(),
                            payload_bytes=reg.payload_bytes(),
                            shrink=())
                        decision = _placement.propose(snap)
                        if decision.act:
                            reg.apply(decision.moves, devs)
                        # Warm the post-migration dist path outside
                        # the serving round (the first submesh
                        # dist_spmv compiles).
                        for t_p, A_t in (("noisy", A_p1),
                                         ("quiet", A_p2)):
                            h_p = _placement.route(A_t, t_p)
                            _ = np.asarray(h_p.dot(x_p))
                        _pload(gw_p, 8, 2)   # serve on the new carve
                    finally:
                        gw_p.shutdown()
                finally:
                    _pst.gateway, _pst.placement = saved_p
                    _placement.reset()

                def _dp(name):
                    return int(obs.counters.get(name) - c0p[name])

                result["placement_migrations"] = _dp(
                    "placement.migrations")
                result["placement_reshard_bytes"] = _dp(
                    "placement.migration.bytes")
                result["placement_routes"] = _dp("placement.routes")
                result["placement_noisy_served"] = _dp(
                    "gateway.tenant.noisy.served")
                result["placement_quiet_served"] = _dp(
                    "gateway.tenant.quiet.served")
                result["placement_ms"] = round(
                    (_time_mod.perf_counter() - t_p0) * 1e3, 3)
                if _sp is not None:
                    _sp.set(migrations=result["placement_migrations"],
                            reshard_bytes=result[
                                "placement_reshard_bytes"],
                            routes=result["placement_routes"])
        except Exception as e:
            sys.stderr.write(f"bench: placement phase failed: {e!r}\n")

    # Streaming-mutation phase (schema 20, docs/MUTATION.md): the
    # serve-while-mutating proof.  A DeltaCSR serves through the
    # gateway's delta routing while the seeded update storm lands in
    # the side-buffer (two-term dispatch, pinned views), then one
    # background compaction merges the buffer into a fresh base with
    # an atomic version swap and a final round serves the merged
    # base.  The stream is ``gallery.mutation_stream`` under a fixed
    # seed over a fixed pattern, so every counted total is
    # deterministic and the smoke golden pins them exactly.
    if ((smoke
         or os.environ.get("LEGATE_SPARSE_TPU_BENCH_SKIP_MUTATION",
                           "0") != "1")
            and not past_deadline(result, "mutation")):
        try:
            from legate_sparse_tpu import gallery as _mgallery
            from legate_sparse_tpu.delta import DeltaCSR as _MDelta
            from legate_sparse_tpu.engine import Engine as _MEngine
            from legate_sparse_tpu.engine import Gateway as _MGateway
            from legate_sparse_tpu.settings import settings as _mst

            t_m0 = _time_mod.perf_counter()
            n_m = (1 << 12 if smoke else 1 << 14) - 91
            with obs.span("bench.mutation") as _msp:
                A_m = _engine_config(sparse, n_m, nnz_per_row,
                                     seed=29)
                x_m = jnp.ones((n_m,), jnp.float32)
                m_counters = (
                    "delta.updates",
                    "delta.applied",
                    "delta.compaction.merged",
                    "delta.compactions",
                    "delta.swap.versions",
                    "delta.served",
                    "delta.routes",
                )
                c0m = {k: obs.counters.get(k) for k in m_counters}
                saved_m = (_mst.gateway, _mst.delta)
                t_compact = 0.0
                try:
                    _mst.gateway = True
                    _mst.delta = True
                    D_m = _MDelta(A_m, capacity=256)
                    gw_m = _MGateway(
                        _MEngine(), max_batch=4, queue_depth=128,
                        tenant_quota=64, rate=0.0, burst=16.0,
                        slack_ms=5.0, timeout_ms=0.0)
                    try:
                        def _mserve(k):
                            futs = [gw_m.submit(D_m, x_m,
                                                tenant="mut",
                                                qos="interactive")
                                    for _i in range(k)]
                            gw_m.flush()
                            for f in futs:
                                _ = f.result(timeout=120)

                        # Warm the two delta compiles outside the
                        # timed serving rounds (base bucket + the
                        # coo-segment capacity bucket).
                        _ = np.asarray(D_m.dot(x_m))
                        D_m.update([0], [0], [1.0])
                        _ = np.asarray(D_m.dot(x_m))
                        # Serve-while-mutating: 10 seeded update
                        # batches (100 entry updates) interleaved
                        # with gateway rounds on the live buffer.
                        for rows_m, cols_m, vals_m in (
                                _mgallery.mutation_stream(
                                    23, A_m, 100, batch=10)):
                            D_m.update(rows_m, cols_m, vals_m)
                            _mserve(2)
                        # Background compaction + atomic version
                        # swap, off the serving path.
                        t_c0 = _time_mod.perf_counter()
                        D_m.compact()
                        t_compact = (_time_mod.perf_counter()
                                     - t_c0) * 1e3
                        # Post-swap round serves the merged base
                        # (empty buffer — base dispatch alone).
                        _mserve(4)
                    finally:
                        gw_m.shutdown()
                finally:
                    _mst.gateway, _mst.delta = saved_m

                def _dm(name):
                    return int(obs.counters.get(name) - c0m[name])

                result["mutation_updates"] = _dm("delta.updates")
                result["mutation_applied"] = _dm("delta.applied")
                result["mutation_merged"] = _dm(
                    "delta.compaction.merged")
                result["mutation_compactions"] = _dm(
                    "delta.compactions")
                result["mutation_version_swaps"] = _dm(
                    "delta.swap.versions")
                result["mutation_served"] = _dm("delta.served")
                result["mutation_routes"] = _dm("delta.routes")
                result["mutation_compaction_ms"] = round(t_compact, 3)
                result["mutation_ms"] = round(
                    (_time_mod.perf_counter() - t_m0) * 1e3, 3)
                if _msp is not None:
                    _msp.set(updates=result["mutation_updates"],
                             merged=result["mutation_merged"],
                             swaps=result["mutation_version_swaps"])
        except Exception as e:
            sys.stderr.write(f"bench: mutation phase failed: {e!r}\n")

    # Autotune phase (schema_version 11, docs/AUTOTUNER.md): the
    # irregular-SpMV speedup proof.  A seeded power-law matrix gets a
    # sliced-ELL verdict (measured here in the full lane, PINNED in
    # smoke so the golden stays deterministic), one eager dispatch
    # proves the verdict routes (autotune.route.hits delta), and the
    # routed kernel races the flat CSR gather baseline.  Settings and
    # the process verdict store restore on exit: the autotuner must
    # stay inert for every other phase.
    if ((smoke
         or os.environ.get("LEGATE_SPARSE_TPU_BENCH_SKIP_AUTOTUNE",
                           "0") != "1")
            and not past_deadline(result, "autotune")):
        try:
            from legate_sparse_tpu import autotune as _at
            from legate_sparse_tpu import gallery as _gallery
            from legate_sparse_tpu.bench_timing import loop_ms_per_iter
            from legate_sparse_tpu.ops import spmv as _at_spmv
            from legate_sparse_tpu.settings import settings as _ast

            n_at = 1 << 10 if smoke else 1 << 18
            saved_at = _ast.autotune
            with obs.span("bench.autotune") as _sp, \
                    obs.memory.watermark("bench.autotune"):
                try:
                    _at.reset()
                    _ast.autotune = True
                    A_at = _gallery.powerlaw(
                        n_at, nnz_per_row=4 if smoke else 8, rng=11)
                    A_at.sum_duplicates()
                    x_at = jnp.ones((n_at,), dtype=A_at.dtype)
                    rec0 = obs.counters.get("autotune.verdict.records")
                    if smoke:
                        # Pinned verdict: no measurement, so the
                        # golden totals stay exact.
                        key_at = _at.key_for(A_at, "spmv")
                        _at.get_store().record(key_at, "sliced-ell", {})
                        label_at = "sliced-ell"
                    else:
                        verdict_at = _at.tune(A_at, x_at)
                        label_at = verdict_at.label
                    hits0 = obs.counters.get("autotune.route.hits")
                    y_at = A_at @ x_at    # eager: the verdict routes
                    _ = float(np.asarray(y_at[0]))
                    if obs.counters.get("autotune.route.hits") <= hits0:
                        raise RuntimeError(
                            "autotune verdict did not route "
                            "(decline ladder drifted?)")
                    # Kernel race at honest iteration counts: routing
                    # declines inside jitted loop bodies by design
                    # (tracer contexts), so the proof times the routed
                    # kernel and the CSR baseline directly.
                    bins_at = A_at._get_sliced_ell()
                    rid_at = A_at._get_row_ids()
                    # deadline_s bounds escalation per kernel: on the
                    # CPU lane the flat-CSR baseline runs seconds per
                    # iteration at 1<<18, and this phase must not eat
                    # the whole bench budget.
                    k_hi_at = 4 if smoke else None
                    sliced_ms = loop_ms_per_iter(
                        lambda v: _at_spmv.sliced_ell_spmv(
                            bins_at, v, n_at),
                        x_at, k_lo=2 if smoke else 5, k_hi=k_hi_at,
                        deadline_s=None if smoke else 90.0)
                    csr_ms = loop_ms_per_iter(
                        lambda v: _at_spmv.csr_spmv_rowids(
                            A_at.data, A_at.indices, rid_at, v, n_at),
                        x_at, k_lo=2 if smoke else 5, k_hi=k_hi_at,
                        deadline_s=None if smoke else 90.0)
                    result["irregular_spmv_n"] = n_at
                    result["irregular_spmv_nnz"] = A_at.nnz
                    result["irregular_spmv_ms"] = round(sliced_ms, 4)
                    result["irregular_csr_ms"] = round(csr_ms, 4)
                    result["irregular_spmv_speedup"] = round(
                        csr_ms / max(sliced_ms, 1e-9), 2)
                    result["irregular_spmv_path"] = label_at
                    result["autotune_verdicts"] = int(
                        obs.counters.get("autotune.verdict.records")
                        - rec0)
                    if _sp is not None:
                        _sp.set(n=n_at, nnz=A_at.nnz, path=label_at,
                                speedup=result[
                                    "irregular_spmv_speedup"])
                finally:
                    _ast.autotune = saved_at
                    _at.reset()
        except Exception as e:
            sys.stderr.write(f"bench: autotune phase failed: {e!r}\n")

    # Non-toy scale anchors (VERDICT r4 weak #6): one 1e6-row CG and
    # one 4096^2 pde datapoint, recorded on every run so
    # every round carries a scaling story (the r4 configs above are
    # deliberately small for the 1-core fallback; these two are the
    # BASELINE.md bring-up configs 2-3 at honest size).
    if (os.environ.get("LEGATE_SPARSE_TPU_BENCH_SKIP_SCALE", "0") != "1"
            and not smoke
            and not past_deadline(result, "cg_1m")):
        try:
            import time as _time

            import legate_sparse_tpu.linalg as linalg

            grid_m = 1000                    # 1e6 unknowns
            ngm2 = grid_m * grid_m
            main2 = np.full(ngm2, 4.0, np.float32)
            o1 = np.full(ngm2 - 1, -1.0, np.float32)
            o1[np.arange(1, grid_m) * grid_m - 1] = 0.0
            oN = np.full(ngm2 - grid_m, -1.0, np.float32)
            A_1m = sparse.diags(
                [main2, o1, o1, oN, oN], [0, 1, -1, grid_m, -grid_m],
                shape=(ngm2, ngm2), format="csr", dtype=np.float32,
            )
            b_1m = np.ones(ngm2, np.float32)

            def timed_1m(maxiter):
                best = float("inf")
                for rep in range(3):
                    t0 = _time.perf_counter()
                    xs, _ = linalg.cg(A_1m, b_1m, rtol=0.0,
                                      maxiter=maxiter)
                    _ = float(np.asarray(xs[0]))
                    if rep:
                        best = min(best, _time.perf_counter() - t0)
                return best

            t1, t2 = timed_1m(50), timed_1m(150)
            if t2 > t1:
                result["cg_1m_rows"] = ngm2
                result["cg_1m_ms_per_iter"] = round(
                    (t2 - t1) / 100 * 1e3, 4
                )
            else:
                sys.stderr.write(
                    f"bench: cg_1m timing unresolvable "
                    f"(t50={t1:.3f}s, t150={t2:.3f}s)\n")
        except Exception as e:
            sys.stderr.write(f"bench: cg_1m config failed: {e!r}\n")

    if (os.environ.get("LEGATE_SPARSE_TPU_BENCH_SKIP_SCALE", "0") != "1"
            and not smoke
            and not past_deadline(result, "pde_4096")):
        try:
            from legate_sparse_tpu.bench_timing import loop_ms_per_iter
            from legate_sparse_tpu.ops import dia_ops as _dops

            grid_p = 4096                    # BASELINE config 3
            np2 = grid_p * grid_p
            main3 = np.full(np2, 4.0, np.float32)
            p1 = np.full(np2 - 1, -1.0, np.float32)
            p1[np.arange(1, grid_p) * grid_p - 1] = 0.0
            pN = np.full(np2 - grid_p, -1.0, np.float32)
            A_p = sparse.diags(
                [main3, p1, p1, pN, pN], [0, 1, -1, grid_p, -grid_p],
                shape=(np2, np2), format="csr", dtype=np.float32,
            )
            x_p = jnp.ones((np2,), dtype=jnp.float32)
            b_p = jnp.full((np2,), 1e-6, dtype=jnp.float32)
            _ = A_p @ x_p        # build structure caches outside timing

            # The pde example's hot loop is the explicit update: ONE
            # SpMV + axpy per step — which is what this measures now.
            # rho(I - 0.25 A) <= 1 for this operator (spec(A) in
            # [0, 8]), so the chain is magnitude-stable by itself; the
            # r5 rsqrt-normalize pass was bench harness, not pde work,
            # and cost ~40% of the reported iteration.
            def pde_step(v):
                return v - 0.25 * (A_p @ v) + b_p

            ms_p = loop_ms_per_iter(pde_step, x_p, k_lo=2, k_hi=8)
            by_p = _spmv_bytes(A_p, x_p) + 4 * np2  # + the b read
            result["pde_grid"] = f"{grid_p}x{grid_p}"
            result["pde_ms_per_iter"] = round(ms_p, 3)
            result["pde_bytes_per_iter"] = by_p
            # Compressed-pipeline anchor (schema 15): the same
            # explicit update with bf16 operator AND state — the
            # magnitude-stable chain tolerates rounded state, and
            # compressed banded storage drops the DIA hole mask
            # (zero-filled band, ``compress()`` docstring), so the
            # iteration streams 16 bytes/row against f32's 37:
            # the recorded ratio is the tentpole's byte win.
            try:
                C_p = A_p.compress()
                vb_p = x_p.astype(jnp.bfloat16)
                bb_p = b_p.astype(jnp.bfloat16)

                def pde_step_bf16(v):
                    return v - 0.25 * (C_p @ v) + bb_p

                ms_pb = loop_ms_per_iter(pde_step_bf16, vb_p,
                                         k_lo=2, k_hi=8)
                by_pb = _spmv_bytes(C_p, vb_p) + 2 * np2
                result["pde_ms_per_iter_bf16"] = round(ms_pb, 3)
                result["pde_bytes_per_iter_bf16"] = by_pb
                result["pde_bytes_ratio"] = round(by_p / by_pb, 4)
                del C_p, vb_p, bb_p
            except Exception as e:
                sys.stderr.write(
                    f"bench: compressed pde failed: {e!r}\n")
            if stream:
                bound_p = by_p / (stream * 1e9) * 1e3
                result["pde_stream_bound_ms"] = round(bound_p, 3)
                result["pde_roofline_ratio"] = round(bound_p / ms_p, 4)
                if ms_p > 1.3 * bound_p:
                    # Itemize the residual: which part of the explicit
                    # update is off its bound, measured not asserted.
                    # Kernel-split terms only on the CPU lane — there
                    # the dispatch runs the XLA kernels being A/B'd
                    # below; on TPU the dispatch is the Pallas kernel,
                    # and subtracting an XLA-kernel loop from a
                    # Pallas-kernel loop would label the pallas-vs-XLA
                    # delta "axpy cost" (possibly negative).  The
                    # referee for axpy_b_ms/mask_ms is the SAME
                    # lowering the dispatch picked (settings can pin
                    # it to fused); pad_alloc_ms is always the
                    # fused-minus-nopad counterfactual.
                    try:
                        from legate_sparse_tpu.csr import _dia_xla_nopad

                        dia_p = A_p._get_dia()
                        pit = {
                            "measured_ms": round(ms_p, 3),
                            "bound_bw_ms": round(bound_p, 3),
                        }
                        if dia_p is not None and platform == "cpu":
                            datp, offp, mskp = dia_p
                            dpp, mpp = A_p._get_dia_fused()
                            use_nopad = _dia_xla_nopad()

                            def spmv_as_dispatched(v, mask_on=True):
                                if use_nopad:
                                    return _dops.dia_spmv_nopad(
                                        datp, mskp if mask_on else None,
                                        v, offp, A_p.shape)
                                return _dops.dia_spmv_fused(
                                    dpp, mpp if mask_on else None,
                                    v, offp, A_p.shape)

                            ms_sp = loop_ms_per_iter(
                                lambda v: v - 0.25 * spmv_as_dispatched(v),
                                x_p, k_lo=2, k_hi=8)
                            pit["axpy_b_ms"] = round(ms_p - ms_sp, 3)
                            if mskp is not None:
                                ms_nm = loop_ms_per_iter(
                                    lambda v: v - 0.25
                                    * spmv_as_dispatched(v, mask_on=False),
                                    x_p, k_lo=2, k_hi=8)
                                pit["mask_ms"] = round(ms_sp - ms_nm, 3)
                            ms_np = loop_ms_per_iter(
                                lambda v: v - 0.25 * _dops.dia_spmv_nopad(
                                    datp, mskp, v, offp, A_p.shape),
                                x_p, k_lo=2, k_hi=8) if not use_nopad \
                                else ms_sp
                            ms_fu = loop_ms_per_iter(
                                lambda v: v - 0.25 * _dops.dia_spmv_fused(
                                    dpp, mpp, v, offp, A_p.shape),
                                x_p, k_lo=2, k_hi=8) if use_nopad \
                                else ms_sp
                            pit["pad_alloc_ms"] = round(ms_fu - ms_np, 3)
                        result["pde_items"] = pit
                    except Exception as e:
                        sys.stderr.write(
                            f"bench: pde items failed: {e!r}\n")
        except Exception as e:
            sys.stderr.write(f"bench: pde_4096 config failed: {e!r}\n")

    # bfloat16 banded SpMV -- the TPU-native extension beyond the
    # reference's f32/f64 gate (README "dtype policy"): SpMV is
    # bandwidth-bound, so bf16 storage halves the traffic and should
    # land near 2x the f32 rate on chip.  Reported as its own keys;
    # the contract metric stays f32.  In this process: a child would
    # need the chip this process already holds.
    if (os.environ.get("LEGATE_SPARSE_TPU_BENCH_SKIP_BF16", "0") != "1"
            and platform != "cpu"      # no native bf16 off-TPU
            and not past_deadline(result, "bf16")):
        try:
            A16 = _banded_config(sparse, n, nnz_per_row, dtype=jnp.bfloat16)
            x16 = jnp.full((n,), 1.0, dtype=jnp.bfloat16)
            ms16 = _time_spmv_ms(A16, x16, normalize=False, k_lo=5,
                                 k_hi=35)
            result["bf16_ms"] = round(ms16, 4)
            result["bf16_gbs"] = round(
                _spmv_bytes(A16, x16) / (ms16 * 1e-3) / 1e9, 2)
            del A16, x16
        except Exception as e:
            result["bf16_error"] = repr(e)[:200]

    # Memory watermark of the whole run (the per-phase deltas live as
    # mem.* events in the trace artifact; the JSON keeps the headline).
    mem_final = obs.memory.snapshot()
    if "peak_rss_mb" in mem_final:
        result["mem_peak_rss_mb"] = mem_final["peak_rss_mb"]
    if "device_peak_mb" in mem_final:
        result["mem_device_peak_mb"] = mem_final["device_peak_mb"]

    result["bench_wall_s"] = round(_time_mod.perf_counter() - t_start, 1)

    if obs_requested or obs.enabled():
        # Structured perf artifact: every span/counter recorded by the
        # package during this run, Chrome-trace format (Perfetto /
        # tools/trace_summary.py both read it).
        import time as _ts

        trace_path = os.environ.get("LEGATE_SPARSE_TPU_OBS_FILE")
        if not trace_path:
            stamp = _ts.strftime("%Y%m%dT%H%M%S", _ts.gmtime())
            trace_path = f"BENCH_{stamp}.trace.json"
        n_spans = sum(1 for r in obs.records() if r["type"] == "span")
        try:
            obs.write_chrome_trace(
                trace_path,
                extra_metadata={"platform": platform,
                                "bench_result": result},
            )
            result["trace_file"] = trace_path
        except OSError as e:
            # The export must never cost the measurements (the round-2
            # lost-data failure mode): record the error, still print.
            sys.stderr.write(f"bench: trace export failed: {e!r}\n")
            result["trace_error"] = repr(e)[:200]
        result["trace_spans"] = n_spans
        print(json.dumps(result))
        if n_spans == 0:
            # Tracing was requested but produced nothing: the wiring
            # silently no-opped (e.g. a refactor dropped the spans).
            # Fail loudly so the driver can't archive empty evidence.
            sys.stderr.write(
                "bench: tracing requested but 0 spans recorded "
                f"({trace_path})\n"
            )
            sys.exit(1)
        return

    print(json.dumps(result))


if __name__ == "__main__":
    main()
