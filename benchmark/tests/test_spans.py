"""The program spans' readers, on the recorded v5e excerpt with span
records written through the library's own obs API: the clock offset,
the idle split by innermost span, and the new per-layer readers."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark import harness, spans, trace
from benchmark.tests.conftest import ROOT

HERE = os.path.dirname(os.path.abspath(__file__))

#: Host clock, in seconds, at the excerpt's ``solve`` annotation.
T0 = 1000.0

READERS = ["launch_ms.cg", "host_syncs.cg", "idle_in_spans.cg",
           "build_ms.bfs", "host_syncs.bfs", "idle_in_spans.bfs",
           "route_us.spmv", "idle_in_spans.spmv"]


@pytest.fixture
def obs():
    from legate_sparse_tpu import obs

    was = obs.enabled()
    obs.reset()
    obs.enable()
    yield obs
    obs.reset()
    if not was:
        obs.disable()


def _reduced():
    with open(os.path.join(HERE, "data", "trace_pde2d_cg_v5e.json")) as f:
        return trace.Reduced.from_planes(json.load(f)["planes"])


def _annotation(red, name):
    (s, e), = [(s, e) for n, s, e in red.host if n == name]
    return s, e


def _run(red, records, counters=None):
    """A run of the excerpt: its one operation's record starts at host
    time ``T0`` where the trace's op annotation starts."""
    off = _annotation(red, "solve")[0] - T0 * 1e9
    return SimpleNamespace(
        reduced=red, records=records, counters=counters or {}, notes={},
        window=((red.window[0] - off) * 1e-9, (red.window[1] - off) * 1e-9))


def _record_span(obs, name, start, end, red):
    """Record a span over trace times [start, end) in host-clock ns."""
    off = _annotation(red, "solve")[0] - T0 * 1e9
    obs.trace.complete_span(name, round(start - off), round(end - start))


def _largest_gap_in_solve(red):
    s0, s1 = _annotation(red, "solve")
    inside = [(s, e) for s, e in red.idle_gaps(0) if s0 <= s and e <= s1]
    return max(inside, key=lambda g: g[1] - g[0])


def test_offset_from_op_annotations():
    red = _reduced()
    run = _run(red, [{"t0": T0, "t1": T0 + 0.07, "iters": 10}])
    assert spans.offset_ns(run) == pytest.approx(
        _annotation(red, "solve")[0] - T0 * 1e9, abs=1)
    # Without a record per annotation the window annotation places it.
    run.records = []
    assert spans.offset_ns(run) == pytest.approx(
        _annotation(red, "solve")[0] - T0 * 1e9, abs=1)


def test_innermost_by_hand():
    got = spans.innermost([("a", 0, 10), ("b", 2, 5), ("c", 6, 8),
                           ("d", 12, 14), ("e", 12, 13)])
    assert got == [(0, 2, "a"), (2, 5, "b"), (5, 6, "a"), (6, 8, "c"),
                   (8, 10, "a"), (12, 13, "e"), (13, 14, "d")]


def test_span_over_a_gap_takes_that_gap(obs):
    red = _reduced()
    s0, s1 = _annotation(red, "solve")
    gs, ge = _largest_gap_in_solve(red)
    _record_span(obs, "cg", s0, s1, red)
    _record_span(obs, "cg.launch", gs, ge, red)
    run = _run(red, [{"t0": T0, "t1": T0 + (s1 - s0) * 1e-9, "iters": 9}])
    split = spans.idle_by_span(run)
    assert split["cg.launch"] == pytest.approx((ge - gs) * 1e-6, abs=1e-5)
    gaps_ms = trace.length(red.idle_gaps(0)) * 1e-6
    assert sum(split.values()) == pytest.approx(gaps_ms)
    # The rhs draw and the tail of the window are in no program span.
    assert split[spans.OUTSIDE] > 0


def test_idle_in_spans_within_idle_share(obs):
    red = _reduced()
    s0, s1 = _annotation(red, "solve")
    _record_span(obs, "cg", s0, s1, red)
    gs, ge = _largest_gap_in_solve(red)
    _record_span(obs, "cg.launch", gs, ge, red)
    run = _run(red, [{"t0": T0, "t1": T0 + 0.07, "iters": 9}])
    share = 100.0 * red.idle_share()
    inside = spans.idle_in_spans(run, "cg")
    assert 0 < inside <= share
    launch = spans.idle_in_spans(run, "cg.launch")
    assert launch == pytest.approx(100.0 * (ge - gs) / (
        red.window[1] - red.window[0]), rel=1e-6)
    assert launch < inside
    # A span over the whole window takes all of the idle share.
    _record_span(obs, "all", red.window[0] + 1, red.window[1], red)
    run.notes.clear()
    assert spans.idle_in_spans(run, "all") == pytest.approx(share,
                                                            abs=1e-4)


def test_readers_on_the_excerpt(obs):
    red = _reduced()
    s0, s1 = _annotation(red, "solve")
    gs, ge = _largest_gap_in_solve(red)
    for name in ("cg", "graph.bfs"):
        _record_span(obs, name, s0, s1, red)
    for name in ("cg.launch", "graph.build"):
        _record_span(obs, name, s0, ge, red)
    # Two calls, each 10 us of which the kernel launch takes 4.
    for k in range(2):
        a = gs + k * 20_000
        _record_span(obs, "spmv", a, a + 10_000, red)
        _record_span(obs, "pallas.spmv", a + 3_000, a + 7_000, red)
    rec = {"t0": T0, "t1": T0 + 0.07, "iters": 9, "root": 3, "calls": 2}
    counters = {"transfer.host_sync.cg_norm": 1, "graph.bfs.iters": 5}
    run = _run(red, [rec], counters)
    got = {n: harness._reader(n, ROOT).read(run) for n in READERS}
    assert got["launch_ms.cg"] == pytest.approx((ge - s0) * 1e-6, abs=1e-5)
    assert got["build_ms.bfs"] == got["launch_ms.cg"]
    assert got["host_syncs.cg"] == got["host_syncs.bfs"] == 1.0
    assert got["route_us.spmv"] == pytest.approx(6.0, abs=2e-3)
    share = 100.0 * red.idle_share()
    for n in ("idle_in_spans.cg", "idle_in_spans.bfs",
              "idle_in_spans.spmv"):
        assert 0 < got[n] <= share, n


@pytest.mark.parametrize("name", READERS)
def test_readers_none_without_trace_or_spans(obs, name):
    reader = harness._reader(name, ROOT)
    rec = {"t0": T0, "t1": T0 + 0.07, "iters": 9, "root": 3, "calls": 2}
    counters = {"transfer.host_sync.cg_norm": 1}
    run = SimpleNamespace(reduced=None, records=[rec], counters=counters,
                          notes={}, window=(T0 - 1, T0 + 1))
    assert reader.read(run) is None
    # A trace, but a program that recorded no span in the window.
    red = _reduced()
    assert reader.read(_run(red, [rec], counters)) is None
