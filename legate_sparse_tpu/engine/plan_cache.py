# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Plan cache: bucket-keyed compiled executables.

A *plan* is one AOT-compiled XLA executable (``jax.jit`` lowered and
compiled against ``jax.ShapeDtypeStruct`` operands) for one bucketed
operand shape, keyed on::

    (op, dtype, rows bucket, cols bucket, nnz bucket, k bucket,
     mesh fingerprint, settings epoch)

Calling a plan runs the stored ``Compiled`` object directly — there is
no dispatch-time retrace to even *check* for: the zero-retrace hit
path is structural, and the ``trace.<kernel>`` compile counters prove
it (they increment only while a kernel body is being traced, which for
a plan happens exactly once, inside ``build``).

The settings epoch term means any post-import settings mutation
naturally invalidates plans (stale keys age out of the LRU); the mesh
fingerprint term keys distributed plans to the physical device set
(``parallel.dist_csr.mesh_fingerprint``).  With
``settings.engine_persist_dir`` set, JAX's persistent compilation
cache additionally backs every build, so a *fresh process* pays
deserialization instead of XLA compilation for known buckets.

Counters (always on, ``obs.counters`` contract):

    engine.plan.hits / engine.plan.misses    aggregate cache outcome
    engine.plan.evictions                    LRU pressure
    engine.plan.build_ms                     cumulative compile time
    engine.plan.<plan-id>.hits/.builds/.execs   per-plan rollup
                                             (``trace_summary --plans``)
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from .. import obs as _obs
from ..resilience import faults as _rfaults
from ..resilience import outcomes as _routcomes
from ..resilience import policy as _rpolicy


@dataclass(frozen=True)
class PlanKey:
    """Identity of one compiled plan (see module docstring)."""

    op: str                 # "spmv" | "spmm" | "dist_spmv" | ...
    dtype: str              # canonical numpy dtype name of the values
    rows_b: int             # bucketed output rows
    cols_b: int             # bucketed x/operand length
    nnz_b: int              # bucketed stored-entry count
    k_b: int = 1            # bucketed dense-operand width (SpMM/batch)
    mesh_fp: str = ""       # "" = single-device; folds layout+grid, so
                            # a resharded matrix (parallel.reshard:
                            # new mesh/layout) never aliases its
                            # source's cached plans
    epoch: int = 0          # settings epoch at build time

    @property
    def plan_id(self) -> str:
        """Compact human-readable id used in counter names and the
        ``--plans`` table.  The mesh/layout fingerprint is digested to
        8 hex chars — a prefix truncation would collide two layouts on
        one mesh (``dist_plan_fingerprint`` leads with the mesh
        hash)."""
        pid = (f"{self.op}/{self.dtype}/r{self.rows_b}/c{self.cols_b}"
               f"/z{self.nnz_b}/k{self.k_b}")
        if self.mesh_fp:
            import hashlib

            digest = hashlib.sha1(
                self.mesh_fp.encode()).hexdigest()[:8]
            pid += f"/m{digest}"
        return pid


@dataclass
class Plan:
    """One cached executable plus its ledger.

    ``compiled`` is the AOT executable for eager dispatch (None for
    metadata-only plans, e.g. distributed plans whose executables live
    in the shard_map structure caches); ``traced`` is the jitted
    kernel for use *inside* an ambient trace (solver loops), where an
    AOT executable cannot appear.
    """

    key: PlanKey
    compiled: Optional[Callable] = None
    traced: Optional[Callable] = None
    build_ms: float = 0.0
    hits: int = 0
    execs: int = 0
    meta: Dict[str, Any] = field(default_factory=dict)

    def __call__(self, *args):
        self.execs += 1
        _obs.inc(f"engine.plan.{self.key.plan_id}.execs")
        return self.compiled(*args)


class PlanBuildError(RuntimeError):
    """Raised on the cheap path for a key whose build already failed
    (the negative cache below)."""


class PlanCache:
    """Thread-safe LRU of ``PlanKey -> Plan``."""

    # Bound on the failed-build negative cache (same safety-valve
    # pattern as dist_spgemm's ``_WINDOW_DECLINED``).
    _FAILED_CAP = 256

    def __init__(self, capacity: int = 128):
        self.capacity = max(int(capacity), 1)
        self._lock = threading.Lock()
        self._plans: "OrderedDict[PlanKey, Plan]" = OrderedDict()
        # Keys whose build raised: a reproducible XLA failure must not
        # re-run a multi-second compile attempt on EVERY dispatch of a
        # solver loop — the first failure is cached and later lookups
        # fail fast (routing then falls back to the normal dispatch).
        self._failed: set = set()

    def __len__(self) -> int:
        with self._lock:
            return len(self._plans)

    def lookup(self, key: PlanKey) -> Optional[Plan]:
        """Hit path: returns the plan (LRU-refreshed) or None.  Hit
        counters are bumped here so every caller reports uniformly."""
        with self._lock:
            plan = self._plans.get(key)
            if plan is None:
                return None
            self._plans.move_to_end(key)
            plan.hits += 1
        _obs.inc("engine.plan.hits")
        _obs.inc(f"engine.plan.{key.plan_id}.hits")
        return plan

    def get_or_build(self, key: PlanKey,
                     builder: Callable[[PlanKey], Plan]) -> Tuple[Plan, bool]:
        """Returns ``(plan, hit)``.  The build runs OUTSIDE the cache
        lock — XLA compiles take seconds and must not serialize
        unrelated hits.  Two threads missing the same key concurrently
        may both compile (identical executables; the first insert
        wins) — a rare, benign race that keeps the lock discipline
        trivial; the executor serializes same-plan traffic anyway."""
        plan = self.lookup(key)
        if plan is not None:
            return plan, True
        with self._lock:
            if key in self._failed:
                _obs.inc("engine.plan.failed_fast")
                raise PlanBuildError(
                    f"plan {key.plan_id}: build already failed in "
                    f"this process (cached)")
        _obs.inc("engine.plan.misses")
        _obs.inc(f"engine.plan.{key.plan_id}.builds")
        t0 = time.perf_counter()

        def _build():
            # Resilience site: an injected (or real, transient) XLA
            # compile failure is retried per the engine.plan.build
            # policy before it reaches the negative cache below —
            # only a failure that survives its retry ladder poisons
            # the key.  Inert one flag read with RESIL off.
            _rfaults.fault_point("engine.plan.build")
            return builder(key)

        try:
            with _obs.span("engine.build", plan=key.plan_id):
                plan = _rpolicy.run("engine.plan.build", _build)
        except _routcomes.FinalOutcomeError:
            # A resilience verdict (the site's breaker is open) says
            # nothing about THIS key's buildability — it was never
            # attempted.  Do not poison the negative cache: the key
            # must stay buildable after the breaker heals.
            raise
        except Exception:
            with self._lock:
                if len(self._failed) >= self._FAILED_CAP:
                    self._failed.clear()
                self._failed.add(key)
            _obs.inc("engine.plan.build_failed")
            raise
        plan.build_ms = (time.perf_counter() - t0) * 1e3
        _obs.inc("engine.plan.build_ms", plan.build_ms)
        with self._lock:
            existing = self._plans.get(key)
            if existing is not None:
                # Lost the insert race: adopt the winner (identical
                # executable, and its ledger is the one hits go to).
                plan = existing
            else:
                self._plans[key] = plan
                while len(self._plans) > self.capacity:
                    old_key, _old = self._plans.popitem(last=False)
                    _obs.inc("engine.plan.evictions")
                    _obs.event("engine.plan.evict",
                               plan=old_key.plan_id)
        return plan, False

    def clear(self) -> None:
        with self._lock:
            self._plans.clear()
            self._failed.clear()

    def stats(self) -> Dict[str, Dict[str, Any]]:
        """Per-plan ledger snapshot (``Engine.stats`` / report)."""
        with self._lock:
            return {
                k.plan_id: {
                    "hits": p.hits,
                    "execs": p.execs,
                    "build_ms": round(p.build_ms, 3),
                    "meta": dict(p.meta),
                }
                for k, p in self._plans.items()
            }


_persist_enabled = False
_persist_lock = threading.Lock()


def maybe_enable_persistent_cache() -> bool:
    """Back plan builds with JAX's persistent compilation cache when
    ``settings.engine_persist_dir`` is set (idempotent; best-effort —
    an old jaxlib without the knobs just skips).  This is what turns
    the plan cache into cross-process warm starts: a fresh serving
    process deserializes known buckets instead of re-running XLA.

    The compilation cache is a PROCESS-GLOBAL jax facility: enabling
    it here persists every XLA compile in the process (non-engine
    kernels included), with the min-compile-time threshold dropped to
    0 so small engine plans qualify.  Deliberate — non-engine retraces
    become warm-startable too — but the operator owns the directory's
    growth (docs/ENGINE.md, scope caveat).  A set
    ``JAX_COMPILATION_CACHE_DIR`` names the process's cache and wins
    over ``engine_persist_dir``."""
    global _persist_enabled
    from ..settings import settings

    path = settings.engine_persist_dir
    if not path:
        return False
    with _persist_lock:
        if _persist_enabled:
            return True
        try:
            import jax

            if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
                jax.config.update("jax_compilation_cache_dir", path)
            # Persist everything the engine compiles, not only slow
            # builds (the default threshold skips small kernels).
            try:
                jax.config.update(
                    "jax_persistent_cache_min_compile_time_secs", 0.0)
            except Exception:
                pass
            _persist_enabled = True
            _obs.inc("engine.persist.enabled")
            return True
        except Exception as e:  # pragma: no cover - jaxlib-dependent
            _obs.event("engine.persist.failed", error=repr(e)[:200])
            return False


# ---------------------------------------------------------------- builders


def plan_program(key: PlanKey):
    """The (jitted fn, arg_specs, static kwargs, kernel name) quadruple
    a plan for ``key`` lowers — the single spec source shared by the
    compiling builders below and the lower-only verifier hook
    (``lower_plan``), so a contract is checked against EXACTLY the
    program production would compile."""
    import jax

    from ..ops import spmv as spmv_ops
    from ..types import coord_dtype_for

    dt = np.dtype(key.dtype)
    cdt = coord_dtype_for(max(key.cols_b, 1))
    sds = jax.ShapeDtypeStruct
    if key.op == "spmv":
        specs = (
            sds((key.nnz_b,), dt),            # data
            sds((key.nnz_b,), cdt),           # indices
            sds((key.nnz_b,), np.int32),      # row_ids
            sds((), np.int32),                # valid_nnz
            sds((key.cols_b,), dt),           # x
        )
        return (spmv_ops.csr_spmv_rowids_masked, specs,
                {"rows": key.rows_b}, "csr_spmv_rowids_masked")
    if key.op == "spmm":
        specs = (
            sds((key.nnz_b,), dt),
            sds((key.nnz_b,), cdt),
            sds((key.nnz_b,), np.int32),
            sds((), np.int32),
            sds((key.cols_b, key.k_b), dt),
        )
        return (spmv_ops.csr_spmm_rowids_masked, specs,
                {"rows": key.rows_b}, "csr_spmm_rowids_masked")
    if key.op == "spmv_multi":
        b = key.k_b
        specs = (
            sds((b, key.nnz_b), dt),          # stacked data
            sds((b, key.nnz_b), cdt),         # stacked indices
            sds((b, key.nnz_b), np.int32),    # stacked row_ids
            sds((b,), np.int32),              # per-matrix valid_nnz
            sds((b, key.cols_b), dt),         # per-matrix x
        )
        return (spmv_ops.csr_multi_spmv_rowids_masked, specs,
                {"rows": key.rows_b, "b": b},
                "csr_multi_spmv_rowids_masked")
    raise KeyError(f"no plan program for op {key.op!r}")


def lower_plan(key: PlanKey):
    """Lower — WITHOUT compiling — the kernel program
    ``BUILDERS[key.op]`` would AOT-compile for ``key``, against the
    same ``ShapeDtypeStruct`` operands.  Returns the ``jax.stages``
    ``Lowered`` (``.as_text()`` is its StableHLO; ``.jaxpr`` via
    ``jax.make_jaxpr`` on the traced form is the caller's affair).
    This is planverify's entry point: contract checks read the lowered
    IR and never pay (or trigger) an XLA compile."""
    fn, specs, static, _kernel = plan_program(key)
    return fn.lower(*specs, **static)


def _aot(fn, key: PlanKey, arg_specs, **static) -> Callable:
    """Lower + compile ``fn`` (a jitted function) against
    ``ShapeDtypeStruct`` operands — no example arrays materialized."""
    lowered = fn.lower(*arg_specs, **static)
    return lowered.compile()


def build_spmv_plan(key: PlanKey) -> Plan:
    """Bucketed CSR SpMV plan over the masked row-ids kernel.

    Operand layout (what ``matrix_pack`` produces): data/indices padded
    to ``nnz_b`` (zeros / clamped index 0), row ids padded with
    ``rows_b`` — OUT of ``[0, rows_b)``, so ``segment_sum`` drops the
    padded slots entirely (documented jax semantics) and the valid
    prefix reduces in exactly the unpadded order: bit-for-bit equality
    with ``csr_spmv_rowids``."""
    from ..ops import spmv as spmv_ops

    fn, specs, static, kernel = plan_program(key)
    compiled = _aot(fn, key, specs, **static)

    def traced(data, indices, row_ids, valid, x):
        return spmv_ops.csr_spmv_rowids_masked(
            data, indices, row_ids, valid, x, rows=key.rows_b)

    return Plan(key, compiled=compiled, traced=traced,
                meta={"kernel": kernel})


def build_spmm_plan(key: PlanKey) -> Plan:
    """Bucketed CSR SpMM plan (also the executor's stacked-batch
    kernel; same padding contract as the SpMV plan, ``k_b`` wide)."""
    from ..ops import spmv as spmv_ops

    fn, specs, static, kernel = plan_program(key)
    compiled = _aot(fn, key, specs, **static)

    def traced(data, indices, row_ids, valid, X):
        return spmv_ops.csr_spmm_rowids_masked(
            data, indices, row_ids, valid, X, rows=key.rows_b)

    return Plan(key, compiled=compiled, traced=traced,
                meta={"kernel": kernel})


def build_spmv_multi_plan(key: PlanKey) -> Plan:
    """Stacked multi-matrix SpMV plan: ``k_b`` independent matrices
    from the SAME shape bucket (different tenants/matrices, one
    gateway batch) dispatched as one executable.

    Operand slot ``i`` carries matrix ``i``'s pack (the same
    per-matrix pack the SpMV/SpMM plans consume — pack cache terms
    exclude the op, so no re-padding) and its own x vector; segment
    ids are offset per slot by ``rows_b + 1`` so every pack's
    out-of-range padding row id stays in its own discarded segment
    (bit-for-bit contract, see ``csr_multi_spmv_rowids_masked``)."""
    from ..ops import spmv as spmv_ops

    fn, specs, static, kernel = plan_program(key)
    compiled = _aot(fn, key, specs, **static)
    b = key.k_b

    def traced(data, indices, row_ids, valid, X):
        return spmv_ops.csr_multi_spmv_rowids_masked(
            data, indices, row_ids, valid, X, rows=key.rows_b, b=b)

    return Plan(key, compiled=compiled, traced=traced,
                meta={"kernel": kernel})


BUILDERS: Dict[str, Callable[[PlanKey], Plan]] = {
    "spmv": build_spmv_plan,
    "spmm": build_spmm_plan,
    "spmv_multi": build_spmv_multi_plan,
}
