# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""legate_sparse_tpu.obs: observability — op-level tracing, counters,
and structured perf evidence.

Twelve pieces (see each module's docstring for the design contract):

- ``trace``    — near-zero-overhead spans (``with obs.span("spmv",
                 nnz=...)``) recording wall time + first-call vs
                 steady-state, exporting newline-JSON and
                 Chrome-trace/Perfetto; structured instant events.
- ``counters`` — always-on process-wide counters (op invocations, nnz
                 processed, bytes moved, transfers, scipy-fallback
                 hits, jit cache misses) with a per-thread buffered
                 lock-free fast path (``counters.handle``) for
                 hot-loop sites.
- ``report``   — aggregation into a per-op table (calls, first-call
                 vs steady-state time, nnz/bytes, GB/s over host span
                 time).
- ``latency``  — always-on streaming latency histograms (``lat.*``):
                 mergeable fixed-log2-bucket distributions with a
                 documented quantile error bound, written through the
                 same lock-free per-thread-handle pattern as counters.
- ``comm``     — the communication ledger: per-collective interconnect
                 byte predictions from static shard shapes, recorded
                 as ``comm.*`` counters and solver-span attrs.
- ``export``   — OpenMetrics/Prometheus text rendering of all counters
                 and histograms (``snapshot_openmetrics()`` /
                 ``write_openmetrics``; ``LEGATE_SPARSE_TPU_OBS_PROM``
                 arms an atexit snapshot-to-file).
- ``memory``   — phase memory watermarks (``mem.*`` events: RSS,
                 device stats, optional tracemalloc peaks).
- ``regress``  — the bench-trajectory regression gate behind
                 ``tools/bench_compare.py``.
- ``context``  — causal trace ids minted at ``Gateway.submit`` /
                 ``Executor.submit``, carried across worker threads on
                 the request record, auto-tagged onto spans/events and
                 exported as Chrome-trace flow arcs (obs v4).
- ``slo``      — declarative per-(op, QoS) latency objectives with
                 error budgets, evaluated as multi-window burn rates
                 over the ``lat.*`` histograms; inert without
                 ``LEGATE_SPARSE_TPU_OBS_SLO`` (obs v4).
- ``attrib``   — per-tenant cost attribution ledger: wall time, comm
                 bytes, wait, dispatch/compile counts and watermark
                 growth charged to the ``(tenant, qos)`` identity the
                 TraceContext carries, with an exact-conservation
                 split rule for packed multi-tenant batches; inert
                 without ``LEGATE_SPARSE_TPU_OBS_ATTRIB`` (obs v5).
- ``capacity`` — rolling mesh-slice utilization window over the
                 attributed dispatch spans + the pure-function
                 advisory capacity report (``capacity.recommendation``
                 events) joining demand, QoS weight and SLO burn
                 (obs v5).

Enable tracing with ``LEGATE_SPARSE_TPU_OBS=1`` (read once at import,
like the other settings) or programmatically::

    from legate_sparse_tpu import obs
    obs.enable()
    ...             # run the workload
    obs.write_chrome_trace("run.trace.json")
    print(obs.report.summarize(obs.records()))

Disabled (the default) the span API is a no-op returning a shared
null context manager; counters stay live either way.  While a
``jax.profiler`` trace runs, spans record and also annotate the
profiler's trace (``trace`` module docstring).
"""

from . import (  # noqa: F401
    attrib, capacity, comm, context, counters, export, latency, memory,
    regress, report, slo, trace,
)
from .counters import inc, snapshot  # noqa: F401
from .export import snapshot_openmetrics, write_openmetrics  # noqa: F401
from .latency import observe  # noqa: F401
from .trace import (  # noqa: F401
    disable, enable, enabled, event, records, reset, span,
    to_chrome_trace, write_chrome_trace, write_jsonl,
)

__all__ = [
    "attrib", "capacity", "comm", "context", "counters", "export",
    "latency", "memory", "regress", "report", "slo", "trace",
    "inc", "snapshot", "observe",
    "snapshot_openmetrics", "write_openmetrics",
    "enable", "disable", "enabled", "event", "records", "reset", "span",
    "to_chrome_trace", "write_chrome_trace", "write_jsonl",
]


def reset_all() -> None:
    """Convenience: drop buffered trace records AND zero counters and
    histograms (test isolation / between bench phases); SLO window
    baselines reset with them (they are snapshots of the zeroed
    histograms)."""
    trace.reset()
    counters.reset()
    latency.reset()
    slo.reset()
    attrib.reset()
    capacity.reset()
