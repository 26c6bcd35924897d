# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Environment-driven settings.

Parity with the reference's settings layer (reference:
``legate_sparse/settings.py:22-48``), re-expressed without Legate's
``PrioritizedSetting`` machinery: each setting reads an environment
variable once at import, and can be overridden programmatically.

Settings
--------
``precise_images`` (``LEGATE_SPARSE_PRECISE_IMAGES``)
    Reference semantics: use precise Legion image partitions instead of
    min/max bounding-box approximations (reference ``settings.py:23-33``).
    Here: ``shard_csr`` builds a per-shard exact gather plan (the unique
    x entries each shard reads, exchanged via ``all_to_all``) instead of
    the min/max column-window/halo realization — communication and
    gather working set shrink from O(window) to O(unique columns).
    Per-matrix override: ``shard_csr(..., precise=True/False)``.

``fast_spgemm`` (``LEGATE_SPARSE_FAST_SPGEMM``)
    Reference semantics: pick cuSPARSE SpGEMM ALG1 (fast, memory hungry)
    over ALG3 (reference ``settings.py:35-45``).  Here: ``True`` forces
    the single-shot (T,)-sized ESC expansion; ``False`` (default) caps
    the expansion at ``spgemm_chunk_products`` products per chunk
    (``LEGATE_SPARSE_SPGEMM_CHUNK``), bounding peak memory at
    O(chunk + nnz_C) for product-heavy multiplies.

``x64`` (``LEGATE_SPARSE_TPU_X64``)
    ``1``/``0`` force float64 support on/off; unset (or ``auto``)
    resolves by platform: the one ``jax.config.jax_platforms`` names
    first (``JAX_PLATFORMS`` or a ``pin_cpu``), else JAX's default
    backend — which initializes it, so a process that wants the CPU
    pin names it before importing the package.  The CPU platform
    (the test suite, the multichip dryrun) gets scipy-parity float64;
    an accelerator gets float32/int32 — on TPU float64 is emulated
    (~10x slower) and Mosaic (Pallas) kernels are 32-bit.

``obs`` (``LEGATE_SPARSE_TPU_OBS``)
    Observability: op-level span tracing (``legate_sparse_tpu.obs``).
    Off by default — the span API is a no-op context manager and the
    hot paths pay only a module-global check.  Exposed here as a
    property delegating to ``obs.trace`` so ``settings.obs = True``
    and the env var are equivalent switches.
    ``LEGATE_SPARSE_TPU_OBS_FILE`` names the default trace artifact
    (``bench.py`` derives its ``BENCH_*.trace.json`` from it).

``check_bounds`` (``LEGATE_SPARSE_TPU_CHECK_BOUNDS``)
    Debug mode, the analog of the reference's ``--check-bounds``
    build flag (reference ``install.py:375-381`` wiring
    ``Legion_BOUNDS_CHECKS``): validates index invariants (indices
    within [0, cols), indptr monotone and consistent) at array
    construction, and turns on ``jax_debug_nans`` so the first NaN
    produced by any kernel raises with a traceback.

``engine`` (``LEGATE_SPARSE_TPU_ENGINE``)
    Execution engine (``legate_sparse_tpu.engine``): shape-bucketed
    plan cache + micro-batching request executor.  Off by default —
    with it on, eligible matvec/solve hot paths run through cached
    executables whose shapes are padded to policy buckets, so nearby
    ``n``/``nnz`` hit one compiled program instead of retracing.
    Knobs (all env-overridable, see ``docs/ENGINE.md``):

    - ``engine_bucket_ladder`` (``LEGATE_SPARSE_TPU_ENGINE_BUCKETS``):
      comma-separated ascending sizes; empty = power-of-two buckets.
    - ``engine_min_bucket`` (``..._ENGINE_MIN_BUCKET``): floor bucket,
      bounds tiny-matrix plan proliferation.
    - ``engine_plan_cache_size`` (``..._ENGINE_PLANS``): LRU capacity.
    - ``engine_max_batch`` / ``engine_queue_depth`` /
      ``engine_batch_timeout_ms`` (``..._ENGINE_BATCH`` / ``..._QUEUE``
      / ``..._BATCH_TIMEOUT_MS``): executor micro-batching limits and
      backpressure bound.
    - ``engine_persist_dir`` (``..._ENGINE_PERSIST``): when set, plans
      additionally back onto JAX's persistent compilation cache there
      (process-global: it captures every XLA compile, not only engine
      plans — scope caveat in ``docs/ENGINE.md``).

``resil`` (``LEGATE_SPARSE_TPU_RESIL``)
    Resilience subsystem (``legate_sparse_tpu.resilience``,
    ``docs/RESILIENCE.md``): fault injection, retry/backoff ladders,
    circuit breakers, deadline propagation and load shedding for the
    engine and distributed ops.  Off by default — every instrumented
    site is then one flag read with zero behavior change.  Knobs (all
    env-overridable, prefix ``LEGATE_SPARSE_TPU_RESIL_``):

    - ``resil_retries`` (``_RETRIES``, 2): re-executions per failed
      site call.
    - ``resil_backoff_ms`` / ``resil_backoff_mult`` /
      ``resil_backoff_max_ms`` (``_BACKOFF_MS``/``_BACKOFF_MULT``/
      ``_BACKOFF_MAX_MS``): deterministic exponential backoff
      schedule between retries.
    - ``resil_retry_budget`` (``_RETRY_BUDGET``, 64): per-site
      per-process cap on total retries (amplification bound).
    - ``resil_breaker_k`` / ``resil_breaker_cooldown_ms``
      (``_BREAKER_K``/``_BREAKER_COOLDOWN_MS``): consecutive failures
      that trip a site's circuit breaker, and the open->half-open
      cooldown.
    - ``resil_health`` (``_HEALTH``): opt-in solver health detection
      (non-finite / divergence / stagnation raised as structured
      outcomes); ``resil_stagnation_cycles`` (``_STAGNATION_CYCLES``,
      0 = off) and ``resil_divergence_mult`` (``_DIVERGENCE_MULT``)
      tune it.
    - ``resil_ckpt_iters`` (``_CKPT_ITERS``, 0 = off): default
      solver checkpoint cadence — snapshot the solve state every k
      convergence fetches (``resilience.checkpoint``); the recovery
      ladder restores the last snapshot after a device loss.
    - ``resil_abft`` (``_ABFT``): opt-in ABFT-checksummed eager
      distributed SpMV (column-checksum verification of y; mismatch
      raises a retryable ``ChecksumError``).

``gateway`` (``LEGATE_SPARSE_TPU_GATEWAY``)
    Multi-tenant admission gateway (``legate_sparse_tpu.engine.gateway``,
    ``docs/ENGINE.md``): per-tenant QoS classes, token-bucket rate
    limits, queue quotas, weighted-fair-queueing batch formation and
    deadline-aware dispatch in front of the execution engine.  Off by
    default — no existing call path routes through the gateway, and
    ``Gateway.submit`` degrades to a transparent inline dispatch, so
    behavior and counters stay bit-for-bit those of the engine alone.
    Knobs (all env-overridable, prefix ``LEGATE_SPARSE_TPU_GATEWAY_``):

    - ``gateway_max_batch`` (``_BATCH``, 8): requests packed per
      stacked dispatch.
    - ``gateway_queue_depth`` (``_QUEUE``, 128): global pending bound —
      beyond it admission evicts by least-slack/lowest-class.
    - ``gateway_tenant_quota`` (``_TENANT_QUOTA``, 32): per-tenant
      queued-request cap (reason ``queue_full`` beyond it).
    - ``gateway_rate`` / ``gateway_burst`` (``_RATE``/``_BURST``):
      per-tenant token-bucket refill (requests/s, 0 = unlimited) and
      capacity (reason ``quota`` when empty).
    - ``gateway_slack_ms`` (``_SLACK_MS``, 5.0): deadline slack below
      which a request is dispatched immediately, never held for a
      fuller batch.
    - ``gateway_timeout_ms`` (``_TIMEOUT_MS``, 2.0): background drain
      cadence; ``<= 0`` = deterministic flush-only mode (tests).

``obs_slo`` (``LEGATE_SPARSE_TPU_OBS_SLO``)
    Declarative SLO burn-rate evaluation (``legate_sparse_tpu.obs.slo``,
    ``docs/OBSERVABILITY.md``): per-(op, QoS) latency objectives with
    error budgets, evaluated as multi-window burn rates over rebased
    snapshots of the always-on ``lat.*`` histograms.  Off by default —
    ``slo.evaluate()`` is then a single flag read returning ``[]``,
    and no ``slo.*`` counter ever moves (inertness pinned by test).
    ``obs_slo_watchdog_ms`` (``LEGATE_SPARSE_TPU_OBS_SLO_WATCHDOG_MS``,
    0 = off) arms a daemon watchdog thread evaluating on a
    monotonic-clock cadence.

``obs_attrib`` (``LEGATE_SPARSE_TPU_OBS_ATTRIB``)
    Per-tenant resource attribution + capacity advisor
    (``legate_sparse_tpu.obs.attrib`` / ``.capacity``,
    ``docs/OBSERVABILITY.md``): charges dispatch wall time, ``comm.*``
    bytes, queue wait, and memory-watermark growth to the
    ``(tenant, qos)`` identity minted at ``Gateway.submit``, with a
    deterministic split rule for packed multi-tenant batches so
    per-tenant sums conserve exactly against the untagged totals.
    Off by default — every hook is then one flag read, no
    ``attrib.*``/``util.*``/``capacity.*`` counter ever moves, and
    results are bit-for-bit identical (inertness pinned by test).
    ``obs_tenant_cap`` (``LEGATE_SPARSE_TPU_OBS_TENANT_CAP``, 64)
    bounds distinct tenant labels; overflow folds into ``__other__``.

``placement`` (``LEGATE_SPARSE_TPU_PLACEMENT``)
    Closed-loop elastic placement (``legate_sparse_tpu.placement``,
    ``docs/PLACEMENT.md``): carves the global device grid into
    contiguous per-tenant submeshes sized from QoS weight and observed
    demand (``capacity.recommend``), with an SLO-burn-driven
    controller that prices every migration via ``reshard_volumes``
    and live-migrates tenant matrices behind the gateway.  Off by
    default — the gateway pays one flag read per armed admission, no
    ``placement.*`` counter ever moves, and results are bit-for-bit
    those of the shared global mesh (inertness pinned by test).
    Knobs (all env-overridable, prefix ``LEGATE_SPARSE_TPU_PLACEMENT_``):

    - ``placement_cooldown_ms`` (``_COOLDOWN_MS``, 1000.0): minimum
      wall time between executed migrations (anti-flap hysteresis;
      breaker-driven shrinks override it).
    - ``placement_watchdog_ms`` (``_WATCHDOG_MS``, 0 = off): arms a
      daemon controller thread stepping on a monotonic-clock cadence
      (mirrors the SLO watchdog).
    - ``placement_amortize`` (``_AMORTIZE``, 1.0): predicted savings
      must reach this multiple of the priced migration cost before an
      efficiency-driven move executes.
    - ``placement_bw_gbps`` (``_BW_GBPS``, 10.0): assumed migration
      bandwidth converting priced bytes into amortization cost time.

``delta`` (``LEGATE_SPARSE_TPU_DELTA``)
    Streaming matrix mutation under live traffic
    (``legate_sparse_tpu.delta``, ``docs/MUTATION.md``): a
    ``DeltaCSR`` wrapper serving an immutable base ``csr_array`` plus
    a bounded COO side-buffer of entry updates as ``base @ x +
    delta @ x``, with background compaction merging the buffer into a
    fresh base and atomically swapping versions behind the gateway.
    Off by default — the gateway pays one flag read per armed
    admission, no ``delta.*`` counter ever moves, and results are
    bit-for-bit those of the immutable path (inertness pinned by
    test).  Knobs (prefix ``LEGATE_SPARSE_TPU_DELTA_``):

    - ``delta_capacity`` (``_CAPACITY``, 1024): distinct (row, col)
      update slots before ``update()`` raises ``DeltaCapacityError``.
    - ``delta_watermark`` (``_WATERMARK``, 0.75): pending/capacity
      fraction that flags the matrix for background compaction.
    - ``delta_worker_ms`` (``_WORKER_MS``, 0 = off): arms a daemon
      compaction worker stepping on a monotonic-clock cadence.

``autotune`` (``LEGATE_SPARSE_TPU_AUTOTUNE``)
    Sparsity-fingerprint autotuner (``legate_sparse_tpu.autotune``,
    ``docs/AUTOTUNER.md``): measured kernel selection for the
    gather-class SpMV/SpMM paths, keyed on a structure fingerprint.
    Off by default — every dispatch site then pays one attribute read
    and nothing else.  Knobs (all env-overridable):

    - ``autotune_store_path`` (``..._AUTOTUNE_STORE``): optional JSON
      file verdicts persist to / warm-start from (epoch- and
      platform-invalidated on load).
    - ``autotune_store_size`` (``..._AUTOTUNE_VERDICTS``, 256): verdict
      LRU capacity.
    - ``autotune_trials`` (``..._AUTOTUNE_TRIALS``, 5) and
      ``autotune_warmup`` (``..._AUTOTUNE_WARMUP``, 1): median-of-k
      measurement budget per candidate.

``graph`` knobs (``legate_sparse_tpu.graph``)
    - ``graph_max_iters`` (``LEGATE_SPARSE_TPU_GRAPH_MAX_ITERS``,
      0 = n+1): sweep cap for BFS / connected-components semiring
      traversal loops.
    - ``graph_conv_iters`` (``LEGATE_SPARSE_TPU_GRAPH_CONV_ITERS``,
      5): PageRank device iterations per host convergence fetch
      (one-fetch-per-cycle cadence; see ``docs/GRAPH.md``).

Settings epoch
--------------
``settings.epoch`` is a monotone counter bumped by every post-import
VALUE CHANGE of a lowering-relevant setting.  Compiled-plan caches
(``engine.plan_cache``) key on it, so flipping a setting that could
change lowering (kernel budgets, variants) naturally invalidates
cached executables instead of serving stale programs.  ``obs`` and
``engine`` are exempt (they gate tracing/routing, never lowering), so
turning observability on to watch a warmed server does not void the
``warmup()`` guarantee.
"""

import os


def _parse_ladder(spec: str) -> tuple:
    """Parse a user bucket ladder ("1024,4096,65536") into an ascending
    int tuple; empty spec = () = power-of-two policy.  A malformed
    ladder must fail loudly at import, not silently bucket wrong."""
    spec = spec.strip()
    if not spec:
        return ()
    try:
        rungs = tuple(sorted({int(tok) for tok in spec.split(",")
                              if tok.strip()}))
    except ValueError:
        raise ValueError(
            f"LEGATE_SPARSE_TPU_ENGINE_BUCKETS={spec!r}: expected "
            f"comma-separated integers"
        ) from None
    if rungs and rungs[0] <= 0:
        raise ValueError(
            f"LEGATE_SPARSE_TPU_ENGINE_BUCKETS={spec!r}: rungs must "
            f"be positive"
        )
    return rungs


def _env_bool(name: str, default: bool) -> bool:
    val = os.environ.get(name)
    if val is None:
        return default
    return val.lower() not in ("0", "false", "no", "off", "")


def _resolve_x64() -> bool:
    """x64 on for the CPU platform (scipy-parity float64), off for an
    accelerator.  The platform is the one ``jax.config.jax_platforms``
    names first (``JAX_PLATFORMS``, or a ``pin_cpu``), else JAX's own
    default backend."""
    val = os.environ.get("LEGATE_SPARSE_TPU_X64")
    if val is not None and val.lower() != "auto":
        return val.lower() not in ("0", "false", "no", "off", "")
    import jax

    first = (jax.config.jax_platforms or "").split(",")[0].strip().lower()
    if first:
        return first == "cpu"
    return jax.default_backend() == "cpu"


class Settings:
    def __init__(self) -> None:
        self.precise_images: bool = _env_bool("LEGATE_SPARSE_PRECISE_IMAGES", False)
        self.fast_spgemm: bool = _env_bool("LEGATE_SPARSE_FAST_SPGEMM", False)
        # Default partition layout for shard_csr when no explicit
        # ``layout=`` argument is given: "1d-row" (historical default),
        # "1d-col", "2d-block", or "auto" (route by predicted bytes).
        # NOT epoch-exempt — the layout changes what dist plans lower
        # to.  See docs/DIST.md.
        self.dist_layout: str = os.environ.get(
            "LEGATE_SPARSE_TPU_DIST_LAYOUT", "1d-row"
        )
        self.x64: bool = _resolve_x64()
        self.check_bounds: bool = _env_bool(
            "LEGATE_SPARSE_TPU_CHECK_BOUNDS", False
        )
        # SpMV fast path: pack CSR into ELL (rows, max-row-nnz) when the
        # padded size stays within this multiple of the true nnz.  TPU
        # gathers over a rectangular layout run at HBM roofline; scatter-
        # based segment sums do not.  Set to 0 to disable ELL packing.
        self.ell_max_expand: float = float(
            os.environ.get("LEGATE_SPARSE_TPU_ELL_EXPAND", "4.0")
        )
        # Capacity multiplier for spgemm chunked mode (rows per chunk heuristic).
        self.spgemm_chunk_products: int = int(
            os.environ.get("LEGATE_SPARSE_SPGEMM_CHUNK", 1 << 24)
        )
        # SpMV fastest path: exactly-banded CSR matrices run gather-free
        # shifted-add (DIA) kernels when num_diags*cols stays within this
        # multiple of nnz.  Set to 0 to disable band detection.
        self.dia_max_expand: float = float(
            os.environ.get("LEGATE_SPARSE_TPU_DIA_EXPAND", "2.0")
        )
        self.dia_max_diags: int = int(
            os.environ.get("LEGATE_SPARSE_TPU_DIA_MAX_DIAGS", "128")
        )
        # Irregular SpMV path: densify present 128x128 blocks and stream
        # them through the MXU (ops/bsr.py), skipping absent blocks,
        # when the densified size stays within this multiple of nnz.
        # 128.0 ~= the break-even vs the XLA gather path on v5e (useful
        # bandwidth law in ops/bsr.py docstring).  0 disables BSR.
        self.bsr_max_expand: float = float(
            os.environ.get("LEGATE_SPARSE_TPU_BSR_EXPAND", "128.0")
        )
        # Build the BSR structure on any platform (kernel runs in
        # interpret mode off-TPU) — differential-testing hook.
        self.bsr_force: bool = _env_bool("LEGATE_SPARSE_TPU_BSR_FORCE",
                                         False)
        # XLA banded-SpMV lowering: "fused" (padded single-pass form,
        # the TPU-friendly layout), "nopad" (interior/edge split that
        # skips the x-pad materialization — measured ~20-25% faster on
        # the CPU lane, where every avoided copy is bandwidth), or
        # "auto" (nopad on cpu backends, fused elsewhere).  Only the
        # XLA path is affected; the Pallas kernel stays the TPU fast
        # path.  A typo must fail loudly, not silently benchmark the
        # wrong kernel.
        self.dia_xla_variant: str = os.environ.get(
            "LEGATE_SPARSE_TPU_DIA_XLA", "auto"
        )
        if self.dia_xla_variant not in ("fused", "nopad", "auto"):
            raise ValueError(
                f"LEGATE_SPARSE_TPU_DIA_XLA="
                f"{self.dia_xla_variant!r}: expected one of "
                f"'fused', 'nopad', 'auto'"
            )
        # ---- execution engine (legate_sparse_tpu.engine) ----
        self.engine: bool = _env_bool("LEGATE_SPARSE_TPU_ENGINE", False)
        self.engine_bucket_ladder: tuple = _parse_ladder(
            os.environ.get("LEGATE_SPARSE_TPU_ENGINE_BUCKETS", "")
        )
        self.engine_min_bucket: int = int(
            os.environ.get("LEGATE_SPARSE_TPU_ENGINE_MIN_BUCKET", "64")
        )
        self.engine_plan_cache_size: int = int(
            os.environ.get("LEGATE_SPARSE_TPU_ENGINE_PLANS", "128")
        )
        self.engine_max_batch: int = int(
            os.environ.get("LEGATE_SPARSE_TPU_ENGINE_BATCH", "8")
        )
        self.engine_queue_depth: int = int(
            os.environ.get("LEGATE_SPARSE_TPU_ENGINE_QUEUE", "64")
        )
        self.engine_batch_timeout_ms: float = float(
            os.environ.get("LEGATE_SPARSE_TPU_ENGINE_BATCH_TIMEOUT_MS",
                           "2.0")
        )
        self.engine_persist_dir: str = os.environ.get(
            "LEGATE_SPARSE_TPU_ENGINE_PERSIST", ""
        )
        # ---- resilience (legate_sparse_tpu.resilience) ----
        self.resil: bool = _env_bool("LEGATE_SPARSE_TPU_RESIL", False)
        self.resil_retries: int = int(
            os.environ.get("LEGATE_SPARSE_TPU_RESIL_RETRIES", "2")
        )
        self.resil_backoff_ms: float = float(
            os.environ.get("LEGATE_SPARSE_TPU_RESIL_BACKOFF_MS", "1.0")
        )
        self.resil_backoff_mult: float = float(
            os.environ.get("LEGATE_SPARSE_TPU_RESIL_BACKOFF_MULT",
                           "2.0")
        )
        self.resil_backoff_max_ms: float = float(
            os.environ.get("LEGATE_SPARSE_TPU_RESIL_BACKOFF_MAX_MS",
                           "50.0")
        )
        self.resil_retry_budget: int = int(
            os.environ.get("LEGATE_SPARSE_TPU_RESIL_RETRY_BUDGET",
                           "64")
        )
        self.resil_breaker_k: int = int(
            os.environ.get("LEGATE_SPARSE_TPU_RESIL_BREAKER_K", "3")
        )
        self.resil_breaker_cooldown_ms: float = float(
            os.environ.get(
                "LEGATE_SPARSE_TPU_RESIL_BREAKER_COOLDOWN_MS", "100.0")
        )
        self.resil_health: bool = _env_bool(
            "LEGATE_SPARSE_TPU_RESIL_HEALTH", False
        )
        self.resil_stagnation_cycles: int = int(
            os.environ.get("LEGATE_SPARSE_TPU_RESIL_STAGNATION_CYCLES",
                           "0")
        )
        self.resil_divergence_mult: float = float(
            os.environ.get("LEGATE_SPARSE_TPU_RESIL_DIVERGENCE_MULT",
                           "1e8")
        )
        self.resil_ckpt_iters: int = int(
            os.environ.get("LEGATE_SPARSE_TPU_RESIL_CKPT_ITERS", "0")
        )
        self.resil_abft: bool = _env_bool(
            "LEGATE_SPARSE_TPU_RESIL_ABFT", False
        )
        # ---- multi-tenant gateway (legate_sparse_tpu.engine.gateway) ----
        self.gateway: bool = _env_bool("LEGATE_SPARSE_TPU_GATEWAY",
                                       False)
        self.gateway_max_batch: int = int(
            os.environ.get("LEGATE_SPARSE_TPU_GATEWAY_BATCH", "8")
        )
        self.gateway_queue_depth: int = int(
            os.environ.get("LEGATE_SPARSE_TPU_GATEWAY_QUEUE", "128")
        )
        self.gateway_tenant_quota: int = int(
            os.environ.get("LEGATE_SPARSE_TPU_GATEWAY_TENANT_QUOTA",
                           "32")
        )
        self.gateway_rate: float = float(
            os.environ.get("LEGATE_SPARSE_TPU_GATEWAY_RATE", "0.0")
        )
        self.gateway_burst: float = float(
            os.environ.get("LEGATE_SPARSE_TPU_GATEWAY_BURST", "16.0")
        )
        self.gateway_slack_ms: float = float(
            os.environ.get("LEGATE_SPARSE_TPU_GATEWAY_SLACK_MS", "5.0")
        )
        self.gateway_timeout_ms: float = float(
            os.environ.get("LEGATE_SPARSE_TPU_GATEWAY_TIMEOUT_MS",
                           "2.0")
        )
        # ---- SLO burn-rate evaluation (legate_sparse_tpu.obs.slo) ----
        self.obs_slo: bool = _env_bool("LEGATE_SPARSE_TPU_OBS_SLO",
                                       False)
        self.obs_slo_watchdog_ms: float = float(
            os.environ.get("LEGATE_SPARSE_TPU_OBS_SLO_WATCHDOG_MS",
                           "0")
        )
        # ---- per-tenant attribution (legate_sparse_tpu.obs.attrib) ----
        self.obs_attrib: bool = _env_bool(
            "LEGATE_SPARSE_TPU_OBS_ATTRIB", False)
        # Distinct tenant labels before counters fold into __other__
        # (bounded OpenMetrics label cardinality).
        self.obs_tenant_cap: int = int(
            os.environ.get("LEGATE_SPARSE_TPU_OBS_TENANT_CAP", "64")
        )
        # ---- graph analytics (legate_sparse_tpu.graph) ----
        # Sweep cap for the semiring traversal loops (BFS/CC label
        # propagation); 0 = derive from the vertex count (n+1, the
        # structural bound).  SSSP keeps its own n-sweep cap — that
        # one is the negative-cycle detector, not a budget.
        self.graph_max_iters: int = int(
            os.environ.get("LEGATE_SPARSE_TPU_GRAPH_MAX_ITERS", "0")
        )
        # PageRank convergence-fetch cadence: device iterations per
        # host residual fetch (the solvers' one-fetch-per-cycle
        # pattern; also quantizes iteration counts for the bench
        # golden).
        self.graph_conv_iters: int = int(
            os.environ.get("LEGATE_SPARSE_TPU_GRAPH_CONV_ITERS", "5")
        )
        # ---- elastic placement (legate_sparse_tpu.placement) ----
        self.placement: bool = _env_bool("LEGATE_SPARSE_TPU_PLACEMENT",
                                         False)
        self.placement_cooldown_ms: float = float(
            os.environ.get("LEGATE_SPARSE_TPU_PLACEMENT_COOLDOWN_MS",
                           "1000.0")
        )
        self.placement_watchdog_ms: float = float(
            os.environ.get("LEGATE_SPARSE_TPU_PLACEMENT_WATCHDOG_MS",
                           "0")
        )
        self.placement_amortize: float = float(
            os.environ.get("LEGATE_SPARSE_TPU_PLACEMENT_AMORTIZE",
                           "1.0")
        )
        self.placement_bw_gbps: float = float(
            os.environ.get("LEGATE_SPARSE_TPU_PLACEMENT_BW_GBPS",
                           "10.0")
        )
        # ---- streaming mutation / delta layer (legate_sparse_tpu.delta) ----
        self.delta: bool = _env_bool("LEGATE_SPARSE_TPU_DELTA", False)
        # Side-buffer bound: distinct (row, col) update slots a
        # DeltaCSR may hold before update() raises DeltaCapacityError
        # (compact first).  Device buffers pad to pow2 buckets up to
        # this bound so streaming mutation never retraces.
        self.delta_capacity: int = int(
            os.environ.get("LEGATE_SPARSE_TPU_DELTA_CAPACITY", "1024")
        )
        # Compaction watermark as a fraction of capacity: crossing it
        # flags the matrix for background compaction (and bumps
        # delta.watermark.exceeded — the doctor's compaction-lagging
        # evidence).
        self.delta_watermark: float = float(
            os.environ.get("LEGATE_SPARSE_TPU_DELTA_WATERMARK", "0.75")
        )
        # Background compaction worker cadence (ms); 0 = no worker
        # thread — compaction runs only via compact() / the watermark
        # check at update time when a worker is armed.
        self.delta_worker_ms: float = float(
            os.environ.get("LEGATE_SPARSE_TPU_DELTA_WORKER_MS", "0")
        )
        # ---- autotuner (legate_sparse_tpu.autotune) ----
        self.autotune: bool = _env_bool("LEGATE_SPARSE_TPU_AUTOTUNE",
                                        False)
        self.autotune_store_path: str = os.environ.get(
            "LEGATE_SPARSE_TPU_AUTOTUNE_STORE", ""
        )
        self.autotune_store_size: int = int(
            os.environ.get("LEGATE_SPARSE_TPU_AUTOTUNE_VERDICTS",
                           "256")
        )
        self.autotune_trials: int = int(
            os.environ.get("LEGATE_SPARSE_TPU_AUTOTUNE_TRIALS", "5")
        )
        self.autotune_warmup: int = int(
            os.environ.get("LEGATE_SPARSE_TPU_AUTOTUNE_WARMUP", "1")
        )
        # Settings epoch: compiled-plan cache keys include it, so any
        # later settings mutation (see __setattr__) invalidates plans.
        self._epoch: int = 0
        self._init_done: bool = True

    # Settings that cannot change what a plan lowers to: mutating them
    # must NOT void warmup() guarantees (flipping ``obs`` on to watch
    # steady state would otherwise trigger the very compile storm one
    # is trying to measure; ``engine`` only gates routing; the
    # executor/cache knobs shape queueing and capacity, never the
    # compiled program — the bucket policy knobs are NOT exempt, they
    # legitimately change plan keys).
    _EPOCH_EXEMPT = frozenset({
        "obs", "engine", "engine_max_batch", "engine_queue_depth",
        "engine_batch_timeout_ms", "engine_plan_cache_size",
        "engine_persist_dir", "_epoch", "_init_done",
        # Resilience knobs shape retries/breakers/deadlines — the
        # request lifecycle around a dispatch, never what a plan
        # lowers to; flipping them (tests and the bench drill do, per
        # phase) must not void warmup() guarantees.
        "resil", "resil_retries", "resil_backoff_ms",
        "resil_backoff_mult", "resil_backoff_max_ms",
        "resil_retry_budget", "resil_breaker_k",
        "resil_breaker_cooldown_ms", "resil_health",
        "resil_stagnation_cycles", "resil_divergence_mult",
        "resil_ckpt_iters", "resil_abft",
        # Gateway knobs shape admission, fairness and queueing in
        # front of the engine — pure request-lifecycle policy, never
        # what a plan lowers to (the stacked multi-matrix plan is
        # keyed on its own bucketed batch size, not on these knobs).
        "gateway", "gateway_max_batch", "gateway_queue_depth",
        "gateway_tenant_quota", "gateway_rate", "gateway_burst",
        "gateway_slack_ms", "gateway_timeout_ms",
        # SLO evaluation only *reads* the always-on latency
        # histograms — pure telemetry, like ``obs``.
        "obs_slo", "obs_slo_watchdog_ms",
        # The attribution ledger only *tags* costs the obs stack
        # already measures — pure telemetry; the tenant-label cap
        # shapes counter naming, never any plan.
        "obs_attrib", "obs_tenant_cap",
        # Graph loop caps/cadence shape the HOST iteration loop around
        # semiring dist_spmv dispatches, never what any plan lowers to.
        "graph_max_iters", "graph_conv_iters",
        # Placement knobs shape which submesh serves a tenant and how
        # often the controller migrates — request-lifecycle policy in
        # front of the engine, never what any plan lowers to (the
        # per-submesh dist plans are keyed on their own
        # mesh_fingerprint; tests and the bench placement phase flip
        # these per phase).
        "placement", "placement_cooldown_ms", "placement_watchdog_ms",
        "placement_amortize", "placement_bw_gbps",
        # Delta knobs shape the mutation side-buffer's bound and
        # compaction cadence — request-lifecycle policy around the
        # serving path, never what any plan lowers to (a compaction
        # swaps in a FRESH base matrix whose packs/fingerprints are
        # new objects, so plan/autotune caches invalidate structurally
        # without an epoch bump; tests and the bench mutation phase
        # flip these per phase).
        "delta", "delta_capacity", "delta_watermark",
        "delta_worker_ms",
        # Autotune knobs pick *which already-compiled kernel* serves a
        # dispatch (routing) or shape the measurement budget — never
        # what any kernel lowers to.  Verdict keys carry the epoch
        # separately, so lowering-relevant mutations still invalidate
        # verdicts without these bumping the epoch themselves.
        "autotune", "autotune_store_path", "autotune_store_size",
        "autotune_trials", "autotune_warmup",
    })

    def __setattr__(self, name: str, value) -> None:
        # A post-init VALUE CHANGE of a lowering-relevant setting
        # bumps the epoch (a changed budget/variant can change what a
        # plan would lower to); no-op rewrites and exempt flags don't.
        d = self.__dict__
        if (d.get("_init_done") and name not in self._EPOCH_EXEMPT
                and (name not in d or d[name] != value)):
            d["_epoch"] = d.get("_epoch", 0) + 1
        super().__setattr__(name, value)

    @property
    def epoch(self) -> int:
        """Monotone settings-mutation counter (plan-cache key term)."""
        return self._epoch

    @property
    def obs(self) -> bool:
        """Span tracing on/off — delegates to ``obs.trace`` (single
        source of truth; the env var was read there at import)."""
        from .obs import trace

        return trace.enabled()

    @obs.setter
    def obs(self, value: bool) -> None:
        from .obs import trace

        if value:
            trace.enable()
        else:
            trace.disable()


settings = Settings()
