"""Reduce a profiler trace to what the per-layer metrics read.

The profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  :func:`plain_planes` keeps, as plain data (the form the test
fixture is stored in), the events of each chip's ``XLA Ops`` line (on
``/device:TPU:<id>``, named by their HLO text) and the harness's own
host annotations (:data:`LABELS`).

:class:`Reduced` sorts each device event by its HLO opcode into
``kernel`` (a Pallas ``tpu_custom_call``), ``collective``,
``container`` (``while`` and the like, whose event spans the body's
operations) or ``op``, and then works on intervals: the union of a
device's busy intervals inside the ``window`` annotation, the time of
chosen events, the collective time during which nothing else runs on
that device, and the idle gaps labelled by what the host was doing.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Iterable, Optional

#: Host annotations the harness and the op modules make.
LABELS = ("window", "solve", "rhs", "batch", "search")

#: HLO opcodes of operations that move data between chips.
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "send", "recv")
#: HLO opcodes whose event spans the operations they run.
CONTAINERS = ("while", "conditional", "call")

# The opcode follows the result shape: "%x = f32[8]{0} fusion(...".
_OPCODE = re.compile(r"[\]\})] ([a-z][\w-]*)\(")


def opcode(text: str) -> str:
    rhs = text.split(" = ", 1)[1] if " = " in text else text
    m = _OPCODE.search(rhs)
    return m.group(1) if m else ""


def short_name(text: str) -> str:
    """The HLO instruction's name: ``pallas_dia_spmv.6``."""
    return text.split(" = ", 1)[0].lstrip("%") if " = " in text else text


def classify(text: str) -> str:
    """``kernel`` (a Pallas ``tpu_custom_call``), ``collective``,
    ``container`` (a ``while`` and the like, whose event spans its
    body's operations) or ``op``, from the event's HLO text."""
    op = opcode(text)
    base = op[:-len("-start")] if op.endswith("-start") else (
        op[:-len("-done")] if op.endswith("-done") else op)
    if base in COLLECTIVES:
        return "collective"
    if base == "async" and any(f"calls=%{c}" in text for c in COLLECTIVES):
        return "collective"
    if op in CONTAINERS:
        return "container"
    if op == "custom-call" and "tpu_custom_call" in text:
        return "kernel"
    return "op"


def device_id(plane_name: str) -> Optional[int]:
    m = re.fullmatch(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def plain_planes(profile, device_ids: Iterable[int]) -> list:
    """What the reduction reads from a ``ProfileData``, as plain data:
    the ``XLA Ops`` lines of the given devices and the host events
    named in :data:`LABELS`, each event ``[name, start_ns, dur_ns]``."""
    want = set(device_ids)
    out = []
    for plane in profile.planes:
        dev = device_id(plane.name)
        if dev is not None and dev in want:
            keep = lambda line, ev: line.name == "XLA Ops"  # noqa: E731
        elif plane.name.startswith("/host:"):
            keep = lambda line, ev: ev.name in LABELS  # noqa: E731
        else:
            continue
        lines = []
        for line in plane.lines:
            evs = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                   for ev in line.events if keep(line, ev)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        out.append({"name": plane.name, "lines": lines})
    return out


def union(intervals: Iterable[tuple]) -> list:
    """Merged, sorted ``[(start, end)]`` of the given intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals: Iterable[tuple]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[tuple], lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: list, b: list) -> list:
    """The parts of the merged intervals ``a`` that the merged
    intervals ``b`` do not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclass
class Reduced:
    """A traced window: device op events and host annotations, in ns."""

    ops: dict                     # device id -> [(name, start, dur, kind)]
    host: list                    # [(label, start, end)]
    window: tuple                 # (start, end) of the window annotation
    _busy: dict = field(default_factory=dict, repr=False)

    @classmethod
    def from_planes(cls, planes: list) -> "Reduced":
        ops, host = {}, []
        for plane in planes:
            dev = device_id(plane["name"])
            for line in plane["lines"]:
                for name, start, dur in line["events"]:
                    if dev is not None:
                        ops.setdefault(dev, []).append(
                            (short_name(name), start, dur, classify(name)))
                    elif name in LABELS:
                        host.append((name, start, start + dur))
        wins = [(s, e) for n, s, e in host if n == "window"]
        if not wins:
            raise ValueError("the trace holds no 'window' annotation")
        if not ops:
            raise ValueError("the trace holds no TPU device plane")
        window = (min(s for s, _ in wins), max(e for _, e in wins))
        return cls(ops=ops, host=host, window=window)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def intervals(self, dev: int, pick: Optional[Callable] = None) -> list:
        """Merged intervals of device ``dev``'s events (those ``pick``
        accepts), clipped to the window."""
        return union(clip(((s, s + d) for n, s, d, k in self.ops[dev]
                           if pick is None or pick(n, k)), *self.window))

    def busy(self, dev: int) -> list:
        """The union of the device's operations other than containers:
        a gap inside a loop's body is idle time too."""
        if dev not in self._busy:
            self._busy[dev] = self.intervals(
                dev, lambda n, k: k != "container")
        return self._busy[dev]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        return sum(length(self.busy(d)) for d in self.ops) * 1e-9 / len(
            self.ops)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def event_s(self, pick: Callable) -> float:
        """Summed duration of the chosen events inside the window,
        averaged over devices."""
        lo, hi = self.window
        tot = 0.0
        for evs in self.ops.values():
            tot += sum(min(s + d, hi) - max(s, lo) for n, s, d, k in evs
                       if pick(n, k) and min(s + d, hi) > max(s, lo))
        return tot * 1e-9 / len(self.ops)

    def kind_counts(self) -> dict:
        """Events of each kind that start inside the window, averaged
        over devices."""
        lo, hi = self.window
        out: dict = {}
        for evs in self.ops.values():
            for n, s, d, k in evs:
                if lo <= s < hi:
                    out[k] = out.get(k, 0) + 1
        return {k: v / len(self.ops) for k, v in sorted(out.items())}

    def collective_exposed_s(self) -> float:
        """Collective time during which no other operation runs on that
        device, averaged over devices."""
        tot = 0.0
        for dev in self.ops:
            coll = self.intervals(dev, lambda n, k: k == "collective")
            comp = self.intervals(
                dev, lambda n, k: k not in ("collective", "container"))
            tot += length(subtract(coll, comp))
        return tot * 1e-9 / len(self.ops)

    def label_at(self, t: float) -> str:
        """The innermost harness annotation (other than ``window``)
        that covers host time ``t``."""
        best = None
        for n, s, e in self.host:
            if n != "window" and s <= t < e and (
                    best is None or e - s < best[1]):
                best = (n, e - s)
        return best[0] if best else "between"

    def idle_gaps(self, dev: int) -> list:
        return subtract([self.window], self.busy(dev))

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (seconds summed
        over the window, averaged over devices; containers left out)
        and the idle time by what the host was doing, from the first
        device's gaps."""
        by_op: dict = {}
        for evs in self.ops.values():
            for n, s, d, k in evs:
                lo, hi = max(s, self.window[0]), min(s + d, self.window[1])
                if hi > lo and k != "container":
                    by_op[n] = by_op.get(n, 0.0) + (hi - lo)
        ndev = len(self.ops)
        ops = sorted(((n, t * 1e-9 / ndev) for n, t in by_op.items()),
                     key=lambda x: -x[1])[:top]
        by_label: dict = {}
        for s, e in self.idle_gaps(min(self.ops)):
            lab = self.label_at((s + e) / 2)
            by_label[lab] = by_label.get(lab, 0.0) + (e - s) * 1e-9
        gaps = sorted(by_label.items(), key=lambda x: -x[1])[:top]
        return {"device_ops": [list(x) for x in ops],
                "idle_gaps": [list(x) for x in gaps]}


def profile_options():
    """Host annotations and device events; no Python call tracing."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def load_reduced(log_dir: str, device_ids: Iterable[int]) -> Reduced:
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    prof = ProfileData.from_file(max(files, key=os.path.getmtime))
    return Reduced.from_planes(plain_planes(prof, device_ids))
