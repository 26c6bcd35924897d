# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Op-level tracing: near-zero-overhead spans and structured events.

The performance story of this package was "asserted, not demonstrated"
for five review rounds: ``bench.py`` emitted one JSON blob, and when a
number regressed nobody could tell whether compile, host<->device
transfer, or kernel execution moved.  This
module is the fix: every hot path wraps its python-level dispatch in

    with obs.span("spmv", nnz=nnz, bytes=nbytes) as sp:
        y = kernel(...)
        if sp is not None:
            sp.set(path="ell")     # attrs discovered during the op

and the recorded spans export as newline-JSON or Chrome-trace/Perfetto
format for machine-readable per-op evidence (``report.py`` aggregates
them into the per-op table).

Overhead contract
-----------------
Disabled (the default), ``span()`` touches one module global, asks
``jax.profiler.TraceAnnotation.is_enabled()`` (a static flag, tens of
nanoseconds) and returns a shared no-op context manager — no
allocation, no clock read; the hot-path cost is building the kwargs
dict at the call site (nanoseconds).  Tracing activates via
``settings``/env (``LEGATE_SPARSE_TPU_OBS=1``), an explicit
``enable()`` call, or a running ``jax.profiler`` trace.  This is what
lets the spans live permanently in ``csr_array.dot`` and the solver
loops without moving ``bench_wall_s``.

Spans follow the profiler
-------------------------
While a ``jax.profiler`` trace is being collected
(``jax.profiler.start_trace`` ... ``stop_trace``, or ``trace()``),
spans, complete spans and events record into the buffer as if
``enable()`` had been called — ``disable()`` does not stop them — and
each context-manager span also opens a ``TraceAnnotation`` of its name
for its lifetime, and each event a zero-length one, so the package's
spans sit on the profiler's host plane, on the same clock as the
device's ``XLA Ops``.  Buffered timestamps stay on
``time.perf_counter_ns``.

Compile-vs-execute split
------------------------
Spans carry a per-name sequence number: occurrence 0 of a name is the
first call (jit compile + execute through this dispatch), later
occurrences are steady-state.  ``report.py`` splits first-call from
steady-state time with it — the per-op answer to "did compile or
execution move?".

Spans observed *inside* a jax trace (e.g. an ``A @ x`` under
``jax.jit``) measure trace time, not device time — exactly like
``jax.named_scope``.  Python-level dispatch, which is where this
package's per-op decisions (DIA vs ELL vs CSR, window vs all_gather)
happen, is the intended instrumentation point.
"""

from __future__ import annotations

import io
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation as _TraceAnnotation

from . import attrib as _attrib
from . import context as _context
from . import counters as _counters

# Span attribute keys that auto-accumulate into the process-wide
# counters when a span closes (tentpole contract: nnz processed and
# bytes moved are counters, not just per-span attrs).
_ACCUMULATED_ATTRS = {"nnz": "obs.nnz_processed", "bytes": "obs.bytes_moved",
                      "flops": "obs.flops"}

_lock = threading.Lock()
# Buffered records as flat tuples ``(type, name, ts_ns, dur_ns, depth,
# seq, tid, attr items)`` (events: dur_ns, depth and seq None).  Tuples
# of plain values leave the cyclic collector nothing to traverse, so a
# buffer of a million spans does not slow every later collection;
# ``records()`` builds the dicts.
_records: List[tuple] = []
_seq_by_name: Dict[str, int] = {}
_tls = threading.local()

# Hard cap on buffered records: an unbounded-session safety valve (a
# long-lived service with tracing left on must not leak memory without
# bound).  Overflow drops new spans and counts them.
MAX_RECORDS = int(os.environ.get("LEGATE_SPARSE_TPU_OBS_MAX_RECORDS",
                                 1_000_000))


def _env_enabled() -> bool:
    val = os.environ.get("LEGATE_SPARSE_TPU_OBS")
    if val is None:
        return False
    return val.lower() not in ("0", "false", "no", "off", "")


_enabled: bool = _env_enabled()


#: Is a ``jax.profiler`` trace being collected?  A static flag read.
_profiling = _TraceAnnotation.is_enabled


def enabled() -> bool:
    """Fast hot-path check: is tracing on (``enable()`` or the env
    switch; a running profiler trace is asked separately)?"""
    return _enabled


def enable() -> None:
    """Turn span/event recording on for this process."""
    global _enabled
    _enabled = True


def disable() -> None:
    """Turn span/event recording off (buffered records are kept).  A
    running ``jax.profiler`` trace still records until it stops."""
    global _enabled
    _enabled = False


def reset() -> None:
    """Drop all buffered records and per-name sequence state."""
    with _lock:
        _records.clear()
        _seq_by_name.clear()


def _depth_stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _accumulators() -> tuple:
    """This thread's ``(attr key, counter handle)`` pairs for
    :data:`_ACCUMULATED_ATTRS` (lock-free per-thread handles: spans
    close on every dispatch)."""
    acc = getattr(_tls, "acc", None)
    if acc is None:
        acc = _tls.acc = tuple((key, _counters.handle(name))
                               for key, name in _ACCUMULATED_ATTRS.items())
    return acc


class Span:
    """One recorded operation.  Use via ``span()``; ``set()`` attaches
    attributes discovered while the op runs (kernel choice, output
    nnz)."""

    __slots__ = ("name", "attrs", "_t0", "_stack", "_depth", "_ann")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self._t0 = 0
        self._stack = None
        self._depth = 0
        self._ann = None

    def set(self, **attrs: Any) -> "Span":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        st = self._stack = _depth_stack()
        self._depth = len(st)
        st.append(self.name)
        if _profiling():
            self._ann = _TraceAnnotation(self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        dur = time.perf_counter_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        name, attrs = self.name, self.attrs
        st = self._stack
        if st and st[-1] == name:
            st.pop()
        if exc_type is not None:
            attrs["error"] = exc_type.__name__
        # Causal auto-tag (obs/context.py): a span closed while a
        # trace context is active belongs to that request's flow arc.
        # Explicit trace ids (batch spans tagging their members) win.
        if "trace_id" not in attrs and "trace_ids" not in attrs:
            tid_ctx = _context.current_trace_id()
            if tid_ctx is not None:
                attrs["trace_id"] = tid_ctx
        items = tuple(attrs.items())
        tid = threading.get_ident()
        with _lock:
            seq = _seq_by_name.get(name, 0)
            _seq_by_name[name] = seq + 1
            kept = len(_records) < MAX_RECORDS
            if kept:
                _records.append(("span", name, self._t0, dur, self._depth,
                                 seq, tid, items))
        if not kept:
            _counters.inc("obs.dropped_records")
        # Counter accumulation is independent of the span buffer: it
        # must keep counting even when overflow drops the records
        # (counters advertise process-lifetime totals).
        if attrs:
            for key, acc in _accumulators():
                val = attrs.get(key)
                if isinstance(val, (int, float)) and not isinstance(
                        val, bool):
                    acc.inc(val)
        # Per-tenant attribution (obs/attrib.py): dispatch busy spans
        # charge their wall time to the active tenant members.
        _attrib.on_span_close(name, dur, seq == 0)


class _NullSpan:
    """Shared disabled-mode context manager: no allocation per call."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, exc_type, exc, tb):
        return None

    def set(self, **attrs: Any) -> "_NullSpan":  # tolerate stray .set()
        return self


_NULL_SPAN = _NullSpan()


def span(name: str, **attrs: Any):
    """Context manager recording one operation.

    Yields the live ``Span`` when tracing is enabled or a
    ``jax.profiler`` trace is being collected (so the body can
    ``sp.set(...)`` late attributes) and ``None`` otherwise — guard
    late-attribute work with ``if sp is not None``.
    """
    if not _enabled and not _profiling():
        return _NULL_SPAN
    return Span(name, attrs)


def complete_span(name: str, start_ns: int, dur_ns: int,
                  **attrs: Any) -> None:
    """Record an externally-timed span — a lifecycle that starts in
    one thread and ends in another (the executor's ``engine.request``
    spans), where a context manager can't bracket it.  Takes the same
    per-name sequence slot and buffer-cap treatment as ``Span``;
    ``depth`` is 0 (cross-thread lifecycles have no nesting stack).
    Its interval is past when it is recorded, so it opens no profiler
    annotation."""
    if not _enabled and not _profiling():
        return
    if "trace_id" not in attrs and "trace_ids" not in attrs:
        tid_ctx = _context.current_trace_id()
        if tid_ctx is not None:
            attrs["trace_id"] = tid_ctx
    with _lock:
        seq = _seq_by_name.get(name, 0)
        _seq_by_name[name] = seq + 1
        if len(_records) >= MAX_RECORDS:
            _counters.inc("obs.dropped_records")
            return
        _records.append(("span", name, int(start_ns), int(dur_ns), 0, seq,
                         threading.get_ident(), tuple(attrs.items())))


def event(name: str, **attrs: Any) -> None:
    """Record an instant (zero-duration) structured event — e.g. an
    accelerator-probe failure, a collective-realization decline.  Under
    a running profiler trace it is also a zero-length annotation."""
    if not _enabled and not _profiling():
        return
    if _profiling():
        with _TraceAnnotation(name):
            pass
    if "trace_id" not in attrs:
        tid_ctx = _context.current_trace_id()
        if tid_ctx is not None:
            attrs = dict(attrs, trace_id=tid_ctx)
    with _lock:
        if len(_records) >= MAX_RECORDS:
            _counters.inc("obs.dropped_records")
            return
        _records.append(("event", name, time.perf_counter_ns(), None, None,
                         None, threading.get_ident(), tuple(attrs.items())))


def _as_dict(r: tuple) -> Dict[str, Any]:
    kind, name, ts, dur, depth, seq, tid, items = r
    if kind == "span":
        rec = {"type": kind, "name": name, "ts_ns": ts, "dur_ns": dur,
               "depth": depth, "seq": seq, "first": seq == 0, "tid": tid}
    else:
        rec = {"type": kind, "name": name, "ts_ns": ts, "tid": tid}
    if items:
        rec["attrs"] = dict(items)
    return rec


def records() -> List[Dict[str, Any]]:
    """Snapshot of the buffered records (copy; safe to mutate)."""
    with _lock:
        raw = list(_records)
    return [_as_dict(r) for r in raw]


def _json_default(obj: Any) -> Any:
    # Span attrs may carry numpy scalars / dtypes; stringify anything
    # the stdlib encoder rejects rather than losing the whole trace.
    try:
        import numpy as np

        if isinstance(obj, np.integer):
            return int(obj)
        if isinstance(obj, np.floating):
            return float(obj)
    except Exception:
        pass
    return str(obj)


def write_jsonl(path: str) -> int:
    """Export the buffer as newline-JSON (one record per line).
    Returns the number of records written."""
    recs = records()
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r, default=_json_default) + "\n")
    return len(recs)


def to_chrome_trace(extra_metadata: Optional[Dict[str, Any]] = None
                    ) -> Dict[str, Any]:
    """Render the buffer in Chrome-trace ("Trace Event") format — loads
    directly in Perfetto / chrome://tracing.  Spans become complete
    ("X") events, events become instants ("i"); counters ride along as
    process metadata."""
    pid = os.getpid()
    trace_events: List[Dict[str, Any]] = []
    # Flow anchors: spans tagged with a trace id (obs/context.py) —
    # singly via ``trace_id`` or as a batch member list ``trace_ids``.
    flow_anchors: Dict[str, List[Dict[str, Any]]] = {}
    for r in records():
        ev: Dict[str, Any] = {
            "name": r["name"],
            "pid": pid,
            "tid": r.get("tid", 0),
            "ts": r["ts_ns"] / 1e3,       # Chrome trace wants us
        }
        args = dict(r.get("attrs") or {})
        if r["type"] == "span":
            ev["ph"] = "X"
            ev["dur"] = r["dur_ns"] / 1e3
            args["seq"] = r["seq"]
            args["first_call"] = r["first"]
            ids = []
            tid_one = args.get("trace_id")
            if isinstance(tid_one, str):
                ids.append(tid_one)
            for t in (args.get("trace_ids") or ()):
                if isinstance(t, str):
                    ids.append(t)
            for t in ids:
                flow_anchors.setdefault(t, []).append(ev)
        else:
            ev["ph"] = "i"
            ev["s"] = "p"
        if args:
            ev["args"] = args
        trace_events.append(ev)
    # One flow arc per trace id: Chrome flow events ("s" start / "t"
    # step / "f" finish) bound to the tagged slices render the request
    # as a connected arc (gateway.admit → engine.batch → dist
    # collectives) in Perfetto.  The binding point is the slice
    # enclosing (pid, tid, ts), so each flow record reuses its anchor
    # span's coordinates.
    for trace_id, anchors in sorted(flow_anchors.items()):
        if len(anchors) < 2:
            continue
        anchors.sort(key=lambda ev: ev["ts"])
        last = len(anchors) - 1
        for i, anchor in enumerate(anchors):
            flow: Dict[str, Any] = {
                "name": "request",
                "cat": "flow",
                "ph": "s" if i == 0 else ("f" if i == last else "t"),
                "id": trace_id,
                "pid": pid,
                "tid": anchor["tid"],
                "ts": anchor["ts"],
            }
            if i == last:
                flow["bp"] = "e"
            trace_events.append(flow)
    from . import latency as _latency

    meta: Dict[str, Any] = {
        "counters": _counters.snapshot(),
        # Sparse serialized histograms (obs/latency.py): the artifact
        # carries the full distributions, so tools/trace_summary.py
        # --latency renders p50/p95/p99 from the file alone.
        "histograms": {name: h.to_dict()
                       for name, h in _latency.snapshot().items()},
        "format": "legate_sparse_tpu.obs/1",
    }
    if extra_metadata:
        meta.update(extra_metadata)
    return {"traceEvents": trace_events, "otherData": meta}


def write_chrome_trace(path: str,
                       extra_metadata: Optional[Dict[str, Any]] = None
                       ) -> int:
    """Export the buffer as a Chrome-trace JSON file.  Returns the
    number of trace events written."""
    doc = to_chrome_trace(extra_metadata)
    buf = io.StringIO()
    json.dump(doc, buf, default=_json_default)
    with open(path, "w") as f:
        f.write(buf.getvalue())
    return len(doc["traceEvents"])
