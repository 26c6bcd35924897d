# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Tier-1 tests for the observability subsystem (legate_sparse_tpu.obs):
span recording/nesting, counters, disabled-mode no-op contract, export
formats, per-op aggregation, and the wiring into the hot paths."""

import json
import re

import numpy as np
import pytest

import legate_sparse_tpu as sparse
from legate_sparse_tpu import obs
from legate_sparse_tpu.obs import counters, latency, report, trace


@pytest.fixture(autouse=True)
def _obs_isolation():
    """Each test starts disabled with empty buffers and leaves no
    residue for the rest of the suite."""
    was_enabled = trace.enabled()
    obs.reset_all()
    trace.disable()
    yield
    obs.reset_all()
    if was_enabled:
        trace.enable()
    else:
        trace.disable()


def _banded(n=32, dtype=np.float32):
    return sparse.diags(
        [np.ones(n - 1), np.full(n, 4.0), np.ones(n - 1)], [-1, 0, 1],
        shape=(n, n), format="csr", dtype=dtype,
    )


# ---------------------------------------------------------------- trace --
def test_disabled_mode_records_nothing():
    assert not trace.enabled()
    with obs.span("never", nnz=1) as sp:
        assert sp is None          # null context: no live span handle
    obs.event("never.event", detail=1)
    assert obs.records() == []


def test_disabled_span_is_shared_singleton():
    # Near-zero-overhead contract: disabled span() allocates nothing.
    a = trace.span("x", k=1)
    b = trace.span("y")
    assert a is b is trace._NULL_SPAN


def test_spans_nest_and_record_depth():
    trace.enable()
    with obs.span("outer"):
        with obs.span("inner"):
            with obs.span("innermost"):
                pass
        with obs.span("inner"):
            pass
    recs = obs.records()
    by_name = {}
    for r in recs:
        by_name.setdefault(r["name"], []).append(r)
    assert [r["depth"] for r in by_name["inner"]] == [1, 1]
    assert by_name["innermost"][0]["depth"] == 2
    assert by_name["outer"][0]["depth"] == 0
    # Inner spans close before outer: buffer order is completion order.
    assert [r["name"] for r in recs] == [
        "innermost", "inner", "inner", "outer"]
    # Nested wall times are consistent.
    assert by_name["outer"][0]["dur_ns"] >= by_name["inner"][0]["dur_ns"]


def test_first_call_vs_steady_state_sequencing():
    trace.enable()
    for _ in range(3):
        with obs.span("op"):
            pass
    recs = obs.records()
    assert [r["seq"] for r in recs] == [0, 1, 2]
    assert [r["first"] for r in recs] == [True, False, False]


def test_span_set_attaches_late_attrs_and_errors_are_recorded():
    trace.enable()
    with obs.span("op", early=1) as sp:
        sp.set(late="kernel-choice")
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("x")
    recs = obs.records()
    assert recs[0]["attrs"] == {"early": 1, "late": "kernel-choice"}
    assert recs[1]["attrs"]["error"] == "ValueError"


def test_events_are_instant_records():
    trace.enable()
    obs.event("platform.probe_fail", attempt=1, rc=2)
    (r,) = obs.records()
    assert r["type"] == "event"
    assert "dur_ns" not in r
    assert r["attrs"] == {"attempt": 1, "rc": 2}


def test_span_attrs_accumulate_into_counters():
    trace.enable()
    with obs.span("op", nnz=10, bytes=100):
        pass
    with obs.span("op", nnz=5, bytes=50, flops=7):
        pass
    assert counters.get("obs.nnz_processed") == 15
    assert counters.get("obs.bytes_moved") == 150
    assert counters.get("obs.flops") == 7


# ------------------------------------------------------------- counters --
def test_counters_accumulate_and_reset():
    counters.inc("a.x")
    counters.inc("a.x", 2)
    counters.inc("a.y", 1.5)
    counters.inc("b.z")
    assert counters.get("a.x") == 3
    snap = counters.snapshot("a.")
    assert snap == {"a.x": 3, "a.y": 1.5}
    counters.reset("a.")
    assert counters.get("a.x") == 0
    assert counters.get("b.z") == 1
    counters.reset()
    assert counters.snapshot() == {}


def test_counters_live_even_when_tracing_disabled():
    assert not trace.enabled()
    A = _banded()
    _ = A @ np.ones(A.shape[0], np.float32)
    assert counters.get("op.spmv") == 1
    assert obs.records() == []      # but no trace entries


# -------------------------------------------------------------- exports --
def test_chrome_trace_export_is_valid_json(tmp_path):
    trace.enable()
    with obs.span("spmv", nnz=11, bytes=88):
        pass
    obs.event("probe.fail", rc=1)
    path = tmp_path / "out.trace.json"
    n = obs.write_chrome_trace(str(path), extra_metadata={"tag": "t"})
    assert n == 2
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert len(evs) == 2
    x = [e for e in evs if e["ph"] == "X"][0]
    assert x["name"] == "spmv" and x["dur"] >= 0
    assert x["args"]["nnz"] == 11 and x["args"]["first_call"] is True
    i = [e for e in evs if e["ph"] == "i"][0]
    assert i["name"] == "probe.fail"
    assert doc["otherData"]["tag"] == "t"
    assert "counters" in doc["otherData"]


def test_jsonl_export_and_load_roundtrip(tmp_path):
    trace.enable()
    with obs.span("op", nnz=3):
        pass
    path = tmp_path / "out.jsonl"
    assert obs.write_jsonl(str(path)) == 1
    loaded = report.load_records(str(path))
    assert loaded[0]["name"] == "op"
    assert loaded[0]["attrs"]["nnz"] == 3


def test_load_records_reads_chrome_format_back(tmp_path):
    trace.enable()
    with obs.span("op", nnz=3, bytes=24):
        pass
    with obs.span("op"):
        pass
    path = tmp_path / "out.trace.json"
    obs.write_chrome_trace(str(path))
    loaded = report.load_records(str(path))
    spans = [r for r in loaded if r["type"] == "span"]
    assert len(spans) == 2
    assert spans[0]["first"] is True and spans[1]["first"] is False


# --------------------------------------------------------------- report --
def test_report_aggregates_first_vs_steady_and_bandwidth():
    recs = [
        {"type": "span", "name": "spmv", "ts_ns": 0, "dur_ns": int(5e6),
         "seq": 0, "first": True, "attrs": {"nnz": 10, "bytes": 1000}},
        {"type": "span", "name": "spmv", "ts_ns": 0, "dur_ns": int(1e6),
         "seq": 1, "first": False, "attrs": {"nnz": 10, "bytes": 1000}},
        {"type": "span", "name": "spmv", "ts_ns": 0, "dur_ns": int(1e6),
         "seq": 2, "first": False, "attrs": {"nnz": 10, "bytes": 1000}},
        {"type": "event", "name": "probe", "ts_ns": 0},
    ]
    agg = report.aggregate(recs)
    row = agg["spmv"]
    assert row["calls"] == 3
    assert row["first_ms"] == pytest.approx(5.0)
    assert row["steady_ms"] == pytest.approx(1.0)
    assert row["nnz"] == 30
    # steady bytes (2 calls x 1000 B) over 2 ms -> 1e-3 GB/s
    assert row["gbs"] == pytest.approx(1e-3)
    assert agg["probe"]["events"] == 1
    table = report.render_table(agg)
    header = table.splitlines()[0].split()
    assert header == ["op", "calls", "total_ms", "first_ms", "steady_ms",
                      "nnz", "bytes", "GB/s"]
    (spmv_row,) = [ln.split() for ln in table.splitlines()
                   if ln.startswith("spmv")]
    assert spmv_row[-1] == "0.00"   # 1e-3 GB/s, the last column


# --------------------------------------------------------------- wiring --
def test_spmv_span_records_path_nnz_bytes():
    trace.enable()
    A = _banded()
    x = np.ones(A.shape[0], np.float32)
    _ = A @ x
    _ = A @ x
    spans = [r for r in obs.records() if r["name"] == "spmv"]
    assert len(spans) == 2
    at = spans[0]["attrs"]
    assert at["path"] in ("dia-xla", "dia-xla-nopad", "dia-pallas",
                          "ell", "csr-rowids", "csr", "bsr")
    assert at["nnz"] == A.nnz and at["bytes"] > 0
    assert spans[0]["first"] and not spans[1]["first"]


def test_spgemm_span_records_output_nnz():
    trace.enable()
    A = _banded()
    C = A @ A
    (sp,) = [r for r in obs.records() if r["name"] == "spgemm"]
    assert sp["attrs"]["nnz"] == C.nnz
    assert sp["attrs"]["path"] in ("dia-xla", "dia-pallas", "esc")


def test_cg_span_records_iteration_count():
    import legate_sparse_tpu.linalg as linalg

    trace.enable()
    A = _banded(64)
    b = np.ones(64, np.float32)
    x, iters = linalg.cg(A, b, rtol=1e-6, maxiter=100)
    (sp,) = [r for r in obs.records() if r["name"] == "cg"]
    assert sp["attrs"]["iters"] == int(iters) > 0
    assert sp["attrs"]["n"] == 64


def test_scipy_fallback_counter_increments():
    base = counters.get("scipy_fallback.linalg.spsolve")
    import legate_sparse_tpu.linalg as linalg

    A = _banded(16, dtype=np.float64)
    b = np.ones(16, np.float64)
    _ = linalg.spsolve(A, b)
    assert counters.get("scipy_fallback.linalg.spsolve") == base + 1


def test_jit_retrace_counter_counts_compiles_not_calls():
    from legate_sparse_tpu.ops import spmv as spmv_ops

    import jax.numpy as jnp

    data = jnp.asarray(np.ones(4, np.float32))
    idx = jnp.asarray(np.array([0, 1, 2, 3], np.int32))
    ptr = jnp.asarray(np.array([0, 1, 2, 3, 4], np.int32))
    x = jnp.ones(4, jnp.float32)
    base = counters.get("trace.csr_spmv")
    for _ in range(3):
        _ = spmv_ops.csr_spmv(data, idx, ptr, x, 4)
    got = counters.get("trace.csr_spmv") - base
    # The jit cache may already be warm from earlier tests; what can
    # never happen is one trace per call.
    assert got <= 1


def test_trace_summary_tool_renders_table(tmp_path, capsys):
    trace.enable()
    A = _banded()
    _ = A @ np.ones(A.shape[0], np.float32)
    _ = A @ np.ones(A.shape[0], np.float32)
    path = tmp_path / "t.trace.json"
    obs.write_chrome_trace(str(path))

    mod = _tool("trace_summary")
    rc = mod.main([str(path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "spmv" in out and "steady_ms" in out

    # Empty trace -> nonzero exit (the silent-no-op guard).
    empty = tmp_path / "empty.trace.json"
    empty.write_text('{"traceEvents": []}')
    assert mod.main([str(empty)]) == 2


def test_settings_obs_property_delegates():
    from legate_sparse_tpu.settings import settings

    assert settings.obs is False
    settings.obs = True
    try:
        assert trace.enabled()
    finally:
        settings.obs = False
    assert not trace.enabled()


def test_buffer_cap_drops_and_counts(monkeypatch):
    trace.enable()
    monkeypatch.setattr(trace, "MAX_RECORDS", 2)
    for _ in range(4):
        with obs.span("op"):
            pass
    assert len(obs.records()) == 2
    assert counters.get("obs.dropped_records") == 2


# ----------------------------------------------------- obs v3: latency --
from utils_test.tools import load_tool as _tool


def _heavy_row_csr(n=300, seed=0):
    """Engine-eligible matrix (random columns + one heavy row defeat
    the DIA/ELL/BSR structure fast paths on every platform)."""
    import scipy.sparse as sp

    rng = np.random.default_rng(seed)
    S = sp.random(n, n, density=0.03, format="csr", random_state=rng,
                  dtype=np.float32)
    S = (S + sp.eye(n, format="csr", dtype=np.float32)).tocsr()
    heavy = sp.csr_matrix(
        (np.ones(n, np.float32), (np.zeros(n, np.int64),
                                  np.arange(n))), shape=(n, n))
    S = (S + heavy).tocsr()
    return sparse.csr_array(
        (S.data.astype(np.float32), S.indices, S.indptr), shape=S.shape)


def test_dot_records_latency_histogram_per_shape_bucket():
    A = _banded(48)           # bucket n64
    x = np.ones(48, np.float32)
    latency.reset("lat.")
    for _ in range(5):
        _ = np.asarray(A @ x)
    hist = latency.get("lat.spmv.n64")
    assert hist is not None and hist.count == 5
    assert hist.quantile(0.5) > 0
    # spmm keyed by the same bucket
    _ = np.asarray(A @ np.ones((48, 3), np.float32))
    assert latency.get("lat.spmm.n64").count == 1


def test_latency_histograms_add_zero_sync_to_hot_path():
    """Acceptance pin (mirrors the resilience inertness test):
    steady-state dots with obs ON move latency histograms but leave
    every trace.* (compile) and transfer.* (host-sync) counter
    untouched — the recording is pure host-side arithmetic."""
    trace.enable()
    A = _banded(64)
    x = np.ones(64, np.float32)
    _ = np.asarray(A @ x)                  # warm compile
    latency.reset("lat.")
    before = {k: v for k, v in counters.snapshot().items()
              if k.startswith("trace.") or k.startswith("transfer.")}
    for _ in range(10):
        _ = np.asarray(A @ x)
    after = {k: v for k, v in counters.snapshot().items()
             if k.startswith("trace.") or k.startswith("transfer.")}
    assert after == before, "histogram traffic moved a sync counter"
    assert latency.get("lat.spmv.n64").count == 10


def test_solver_latency_histograms_recorded():
    A = _banded(96)
    b = np.ones(96, np.float32)
    latency.reset("lat.")
    _x, _it = sparse.linalg.cg(A, b, maxiter=10)
    assert latency.get("lat.cg.solve.n128").count == 1
    _x, _it = sparse.linalg.gmres(A, b, restart=5, maxiter=10)
    h = latency.get("lat.gmres.cycle.n128")
    assert h is not None and h.count >= 1


def test_chrome_trace_embeds_histograms_and_summary_renders(
        tmp_path, capsys):
    trace.enable()
    A = _banded()
    _ = A @ np.ones(A.shape[0], np.float32)
    path = tmp_path / "lat.trace.json"
    obs.write_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    hists = doc["otherData"]["histograms"]
    ser = hists.get("lat.spmv.n32")       # _banded() is n=32
    assert ser is not None, sorted(hists)
    assert ser["count"] >= 1 and ser["buckets"]

    mod = _tool("trace_summary")
    rc = mod.main([str(path), "--latency"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "latency histograms:" in out
    assert "lat.spmv." in out and "p99" in out


# ------------------------------------------- obs v3: request lifecycle --
def test_engine_request_lifecycle_spans_and_wait_histograms():
    from legate_sparse_tpu.engine import Engine, RequestExecutor

    trace.enable()
    latency.reset("lat.engine.")
    counters.reset("engine.exec.outcome.")
    A = _heavy_row_csr()
    x = np.ones(A.shape[1], np.float32)
    ex = RequestExecutor(Engine(), max_batch=4, queue_depth=64,
                         timeout_ms=0)
    try:
        futs = [ex.submit(A, x) for _ in range(4)]   # max-batch
        extra = ex.submit(A, x)
        ex.flush()                                   # k=1 dispatch
        for f in futs + [extra]:
            _ = np.asarray(f.result(timeout=60))
    finally:
        ex.shutdown()
    recs = [r for r in obs.records() if r["name"] == "engine.request"]
    assert len(recs) == 5
    rids = [r["attrs"]["rid"] for r in recs]
    assert len(set(rids)) == 5, "request ids must be unique"
    for r in recs:
        at = r["attrs"]
        assert at["outcome"] == "resolved"
        assert at["queue_ms"] >= 0 and at["batch_ms"] >= 0
        assert at["dispatch_ms"] > 0
        assert r["dur_ns"] > 0
    ks = sorted(r["attrs"]["batch_k"] for r in recs)
    assert ks == [1, 4, 4, 4, 4]
    assert counters.get("engine.exec.outcome.resolved") == 5
    assert latency.get("lat.engine.wait.resolved").count == 5
    occ = latency.get("lat.engine.batch_occupancy")
    assert occ.count == 2 and occ.sum == pytest.approx(5.0)
    req_hists = latency.snapshot("lat.engine.request.")
    assert sum(h.count for h in req_hists.values()) == 5


def test_engine_request_inline_and_shed_record_waits():
    """Satellite pin: EVERY outcome records its wait — the inline
    (ineligible-matrix) path and the shed path, not just shed."""
    from legate_sparse_tpu.engine import Engine, RequestExecutor
    from legate_sparse_tpu.resilience import deadline as rdeadline
    from legate_sparse_tpu.settings import settings

    trace.enable()
    latency.reset("lat.engine.")
    counters.reset("engine.exec.outcome.")
    A_banded = _banded(64)            # DIA fast path -> inline service
    x = np.ones(64, np.float32)
    ex = RequestExecutor(Engine(), max_batch=4, queue_depth=64,
                         timeout_ms=0)
    try:
        f = ex.submit(A_banded, x)
        _ = np.asarray(f.result(timeout=60))
        assert counters.get("engine.exec.outcome.inline") == 1
        assert latency.get("lat.engine.wait.inline").count == 1

        A_el = _heavy_row_csr(seed=2)
        x_el = np.ones(A_el.shape[1], np.float32)
        saved = settings.resil
        try:
            settings.resil = True
            with rdeadline.scope(0.0):
                fut = ex.submit(A_el, x_el)
            out = fut.result(timeout=10)
            assert type(out).__name__ == "Rejected"
            assert out.waited_ms >= 0
        finally:
            settings.resil = saved
        assert counters.get("engine.exec.outcome.shed") == 1
        assert latency.get("lat.engine.wait.shed").count == 1
    finally:
        ex.shutdown()
    outs = {r["attrs"]["outcome"]
            for r in obs.records() if r["name"] == "engine.request"}
    assert outs == {"inline", "shed"}


# ------------------------------------------------ obs v3: OpenMetrics --
def test_openmetrics_snapshot_parses_minimal_format():
    """The exposition text must satisfy a minimal OpenMetrics parse:
    valid sample syntax, counter samples ending in _total, histogram
    bucket series cumulative with ascending le ending at +Inf ==
    _count, terminated by # EOF."""
    counters.reset("omt.")
    latency.reset("lat.omt.")
    counters.inc("omt.calls", 3)
    for v in (0.5, 1.5, 1.5, 200.0, 0.0):
        latency.observe("lat.omt.demo", v)
    text = obs.snapshot_openmetrics()
    assert text.endswith("# EOF\n")
    sample_re = re.compile(
        r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
        r'(?:\{([a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
        r'(?:,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*)\})? '
        r'(\S+)$')
    buckets = {}
    sums = {}
    cnts = {}
    seen_counter = False
    for line in text.splitlines():
        if line.startswith("#"):
            assert re.match(r"^# (TYPE|HELP|EOF)", line), line
            continue
        m = sample_re.match(line)
        assert m, f"unparseable sample line: {line!r}"
        metric, labels, value = m.groups()
        labels = dict(re.findall(r'([a-zA-Z_][a-zA-Z0-9_]*)="([^"]*)"',
                                 labels or ""))
        if metric == "legate_sparse_tpu_counter_total":
            seen_counter = True
            if labels.get("name") == "omt.calls":
                assert float(value) == 3
        elif metric == "legate_sparse_tpu_latency_bucket":
            buckets.setdefault(labels["name"], []).append(
                (labels["le"], float(value)))
        elif metric == "legate_sparse_tpu_latency_sum":
            sums[labels["name"]] = float(value)
        elif metric == "legate_sparse_tpu_latency_count":
            cnts[labels["name"]] = float(value)
    assert seen_counter
    assert "lat.omt.demo" in buckets
    series = buckets["lat.omt.demo"]
    assert series[-1][0] == "+Inf"
    les = [float(le) for le, _c in series[:-1]]
    assert les == sorted(les), "le boundaries must ascend"
    vals = [c for _le, c in series]
    assert vals == sorted(vals), "bucket counts must be cumulative"
    assert series[-1][1] == cnts["lat.omt.demo"] == 5
    assert sums["lat.omt.demo"] == pytest.approx(203.5)
    counters.reset("omt.")
    latency.reset("lat.omt.")


def test_write_openmetrics_to_file_and_env(tmp_path, monkeypatch):
    from legate_sparse_tpu.obs import export

    counters.inc("omt.file", 1)
    p = tmp_path / "metrics.prom"
    out = export.write_openmetrics(str(p))
    assert out == str(p)
    text = p.read_text()
    assert text.endswith("# EOF\n")
    assert 'name="omt.file"' in text
    # env-default path
    monkeypatch.setenv(export.ENV_PROM_FILE, str(tmp_path / "e.prom"))
    export.write_openmetrics()
    assert (tmp_path / "e.prom").read_text().endswith("# EOF\n")
    with monkeypatch.context() as mc:
        mc.delenv(export.ENV_PROM_FILE)
        with pytest.raises(ValueError):
            export.write_openmetrics()
    counters.reset("omt.")


# ------------------------------------------ obs v3: docs coverage gate --
def test_check_obs_docs_passes(capsys):
    rc = _tool("check_obs_docs").main([])
    out = capsys.readouterr()
    assert rc == 0, out.out + out.err


def test_check_obs_docs_catches_rot(tmp_path, capsys, monkeypatch):
    """An undocumented emission literal must fail the pass — that is
    the rot the tool exists to catch."""
    mod = _tool("check_obs_docs")
    rogue = tmp_path / "rogue.py"
    rogue.write_text('_obs.inc("zz.totally_undocumented")\n')
    monkeypatch.setattr(mod, "PKG_DIR", str(tmp_path))
    rc = mod.main([])
    out = capsys.readouterr()
    assert rc == 1
    assert "zz.totally_undocumented" in out.err
