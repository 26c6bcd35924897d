"""The library's own spans in a traced window, on the trace's clock.

While the profiler runs, ``legate_sparse_tpu.obs`` records each of its
spans into its buffer (``obs.records()``, stamped with the host's
``perf_counter_ns``) and annotates the profiler's host plane with it.
The reduced trace keeps only the harness's annotations, so the spans
are read from the buffer and moved onto the trace's clock by one
offset: the median, over the window's operations, of the op module's
annotation start (``solve``, ``search`` or ``batch``) minus the
record's ``t0``; where the counts differ, the ``window`` annotation's
start minus the window's.

A program whose spans do not follow the profiler records none in the
window; every reader built on this module then returns None, as it
does without a trace.
"""

from __future__ import annotations

import bisect
import statistics
from typing import Optional

from benchmark import trace

#: The op modules' annotations around each timed operation.
OPS = ("solve", "search", "batch")

#: ``idle_by_span``'s name for idle time while the host was in no span.
OUTSIDE = "outside"


def window_spans(run) -> list:
    """``[(name, start_ns, end_ns)]`` of the program spans recorded in
    the traced window, on the host clock, in start order; empty without
    a trace."""
    if run.reduced is None:
        return []
    if "spans" not in run.notes:
        from legate_sparse_tpu import obs

        lo, hi = (t * 1e9 for t in run.window)
        run.notes["spans"] = sorted(
            ((r["name"], r["ts_ns"], r["ts_ns"] + r["dur_ns"])
             for r in obs.records()
             if r.get("type") == "span" and lo <= r["ts_ns"] <= hi),
            key=lambda s: s[1])
    return run.notes["spans"]


def offset_ns(run) -> float:
    """Trace time minus host-clock time, in ns."""
    host = run.reduced.host
    starts = sorted(s for n, s, _ in host if n in OPS)
    t0s = sorted(r["t0"] for r in run.records)
    if starts and len(starts) == len(t0s):
        return statistics.median(a - t * 1e9 for a, t in zip(starts, t0s))
    return run.reduced.window[0] - run.window[0] * 1e9


def on_trace(run) -> list:
    """The window's program spans on the trace's clock."""
    spans = window_spans(run)
    if not spans:
        return []
    off = offset_ns(run)
    return [(n, s + off, e + off) for n, s, e in spans]


def innermost(spans: list) -> list:
    """Disjoint ``[(start, end, name)]`` covering the spans' union, each
    piece named by the innermost span over it (spans nest)."""
    segs: list = []
    stack: list = []             # (end, name), outermost first
    t = None

    def close(t):
        end, name = stack.pop()
        if end > t:
            segs.append((t, end, name))
            return end
        return t

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            t = close(t)
        if stack and s > t:
            segs.append((t, s, stack[-1][1]))
        t = s if t is None or s > t else t
        stack.append((e, name))
    while stack:
        t = close(t)
    return segs


def idle_by_span(run) -> Optional[dict]:
    """{innermost program span: idle ms} of the first device's idle
    gaps in the window, with :data:`OUTSIDE` for idle time during which
    the host was in no program span; None without spans."""
    spans = on_trace(run)
    if not spans:
        return None
    red = run.reduced
    gaps = red.idle_gaps(min(red.ops))
    segs = innermost(spans)
    out: dict = {}
    covered = 0.0
    j = 0
    for s, e in gaps:
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < e:
            lo, hi = max(s, segs[k][0]), min(e, segs[k][1])
            if hi > lo:
                name = segs[k][2]
                out[name] = out.get(name, 0.0) + (hi - lo) * 1e-6
                covered += hi - lo
            k += 1
    out[OUTSIDE] = (trace.length(gaps) - covered) * 1e-6
    return out


def idle_in_spans(run, name: str) -> Optional[float]:
    """Percent of the traced window in which the device ran nothing
    while the host was inside a ``name`` span (its children included),
    a mean over the cell's devices; never above ``idle_share``."""
    inside = [(s, e) for n, s, e in on_trace(run) if n == name]
    if not inside:
        return None
    red = run.reduced
    inside = trace.union(trace.clip(inside, *red.window))
    idle = sum(trace.length(trace.subtract(inside, red.busy(d)))
               for d in red.ops) / len(red.ops)
    return 100.0 * idle * 1e-9 / red.window_s


def total_ms(run, name: str) -> Optional[float]:
    """Summed host-clock milliseconds of the window's ``name`` spans;
    None where there are none."""
    durs = [e - s for n, s, e in window_spans(run) if n == name]
    return sum(durs) * 1e-6 if durs else None


def self_times_ns(run, name: str, child: str) -> list:
    """Each ``name`` span's duration less the time its ``child`` spans
    (those that start inside it) cover."""
    spans = window_spans(run)
    kids = sorted((s, e) for n, s, e in spans if n == child)
    starts = [s for s, _ in kids]
    out = []
    for n, s, e in spans:
        if n != name:
            continue
        i = bisect.bisect_left(starts, s)
        inner = 0.0
        while i < len(kids) and kids[i][0] < e:
            inner += min(kids[i][1], e) - kids[i][0]
            i += 1
        out.append(e - s - inner)
    return out


def host_syncs(run, key: str) -> Optional[float]:
    """The window's change of the library's ``transfer.host_sync.*``
    counters over its operations that carry ``key``; None without
    program spans or without such counters."""
    ops = sum(1 for r in run.records if key in r)
    syncs = sum(v for k, v in run.counters.items()
                if k.startswith("transfer.host_sync."))
    if not window_spans(run) or not ops or not syncs:
        return None
    return syncs / ops
