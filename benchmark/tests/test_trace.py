"""The trace reduction, on counts worked by hand and on an excerpt of a
real TPU v5 lite trace."""

import json
import os

import pytest

from benchmark import trace

HERE = os.path.dirname(os.path.abspath(__file__))


def _planes():
    # Device 0, in ns: a while loop spanning 0-100; a fusion 10-30 and a
    # Pallas kernel 20-50 (overlapping); an all-reduce 60-80 with a
    # fusion 70-75 under it; a collective-permute-done 90-110, cut by
    # the window's end at 100.  Device 1: one fusion 0-50.
    dev0 = [
        ["%while.1 = (f32[4]{0}, s32[]) while((f32[4]{0}, s32[]) %t), "
         "condition=%c, body=%b", 0, 100],
        ["%fusion.1 = f32[4]{0} fusion(f32[4]{0} %a), kind=kLoop", 10, 20],
        ['%k.1 = f32[4]{0} custom-call(f32[4]{0} %a), '
         'custom_call_target="tpu_custom_call"', 20, 30],
        ["%ar.1 = f32[] all-reduce(f32[] %d), to_apply=%sum", 60, 20],
        ["%fusion.2 = f32[4]{0} fusion(f32[4]{0} %ar.1), kind=kLoop",
         70, 5],
        ["%cp.1 = f32[4]{0} collective-permute-done(f32[4]{0} %s)", 90,
         20],
        ['%cc = f32[8]{0} custom-call(f32[4]{0} %a, f32[4]{0} %b), '
         'custom_call_target="ConcatBitcast"', 200, 10],
    ]
    dev1 = [["%fusion.9 = f32[4]{0} fusion(f32[4]{0} %x), kind=kLoop",
             0, 50]]
    host = [["window", 0, 100], ["solve", 0, 60], ["rhs", 60, 40],
            ["PjitFunction(f)", 5, 1]]
    return [{"name": "/device:TPU:0",
             "lines": [{"name": "XLA Ops", "events": dev0}]},
            {"name": "/device:TPU:1",
             "lines": [{"name": "XLA Ops", "events": dev1}]},
            {"name": "/host:CPU",
             "lines": [{"name": "python3", "events": host}]}]


def test_classify_by_opcode():
    dev0 = _planes()[0]["lines"][0]["events"]
    assert [trace.classify(e[0]) for e in dev0] == [
        "container", "op", "kernel", "collective", "op", "collective",
        "op"]
    assert trace.short_name(dev0[2][0]) == "k.1"
    # An operand named after a collective does not make a collective.
    assert trace.classify("%f = f32[] fusion(f32[] %all-reduce.3)") == "op"


def test_busy_idle_by_hand():
    red = trace.Reduced.from_planes(_planes())
    assert red.window == (0, 100)
    # Device 0: [10,50] + [60,80] + [90,100] = 70 ns (the while left
    # out, the collective-permute cut at 100); device 1: [0,50] = 50.
    assert red.busy(0) == [(10, 50), (60, 80), (90, 100)]
    assert red.busy(1) == [(0, 50)]
    assert red.busy_s() == pytest.approx(60e-9)
    assert red.idle_share() == pytest.approx(0.4)


def test_kernel_selection_by_hand():
    red = trace.Reduced.from_planes(_planes())
    is_kernel = lambda n, k: k == "kernel"  # noqa: E731
    # One kernel event of 30 ns on device 0, none on device 1.
    assert red.event_s(is_kernel) == pytest.approx(15e-9)
    assert red.kind_counts() == {"collective": 1.0, "container": 0.5,
                                 "kernel": 0.5, "op": 1.5}


def test_collective_exposed_by_hand():
    red = trace.Reduced.from_planes(_planes())
    # Device 0: all-reduce [60,80] less fusion [70,75] = 15 ns, plus
    # [90,100] with nothing under it = 10 ns; device 1 has none.
    assert red.collective_exposed_s() == pytest.approx(12.5e-9)


def test_breakdown_by_hand():
    red = trace.Reduced.from_planes(_planes())
    b = red.breakdown()
    assert b["device_ops"][0] == ["fusion.9", pytest.approx(25e-9)]
    assert dict(b["device_ops"]) == pytest.approx({
        "fusion.9": 25e-9, "k.1": 15e-9, "fusion.1": 10e-9,
        "ar.1": 10e-9, "cp.1": 5e-9, "fusion.2": 2.5e-9})
    # Device 0's gaps: [0,10] and [50,60] while solving, [80,90] in rhs.
    assert dict(b["idle_gaps"]) == pytest.approx(
        {"solve": 20e-9, "rhs": 10e-9})


def test_interval_helpers():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3),
                                                              (5, 8)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2),
                                                           (3, 5)]
    assert trace.clip([(0, 5), (8, 9)], 2, 8) == [(2, 5)]


def _sweep_cover(intervals):
    """Covered length by a +1/-1 sweep (independent of trace.union)."""
    pts = sorted([(s, 1) for s, e in intervals]
                 + [(e, -1) for s, e in intervals])
    depth, last, cover = 0, None, 0.0
    for t, d in pts:
        if depth > 0:
            cover += t - last
        depth += d
        last = t
    return cover


def test_recorded_v5e_excerpt():
    with open(os.path.join(HERE, "data", "trace_pde2d_cg_v5e.json")) as f:
        fx = json.load(f)
    red = trace.Reduced.from_planes(fx["planes"])
    evs = fx["planes"][0]["lines"][0]["events"]
    kinds = [trace.classify(e[0]) for e in evs]
    # Every Pallas call of the CG loop is a kernel; the ConcatBitcast
    # custom calls and the while loop are not.
    tagged = sum('custom_call_target="tpu_custom_call"' in e[0]
                 for e in evs)
    assert kinds.count("kernel") == tagged == 42
    assert kinds.count("container") == 1
    assert sum(trace.opcode(e[0]) == "custom-call" for e in evs) == 44
    lo, hi = red.window
    leaves = [(max(s, lo), min(s + d, hi)) for (n, s, d), k in
              zip(evs, kinds) if k != "container" and min(s + d, hi)
              > max(s, lo)]
    assert red.busy_s() == pytest.approx(_sweep_cover(leaves) * 1e-9)
    assert 0 < red.busy_s() < red.window_s
    # The kernel leads the breakdown; the solve's set-up is the gap.
    b = red.breakdown()
    assert b["device_ops"][0][0].startswith("pallas_dia_spmv")
    assert b["idle_gaps"][0][0] == "solve"
