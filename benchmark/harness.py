"""The generic part of the benchmark.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.
The configuration is a JSON file of sizes (``configs/<name>.json``);
the mix is a JSON file of parameters (``traffic/<name>.json``) whose
``op`` names its module in ``ops/<op>.py``.  An op module has five
functions, each given the :class:`Run`:

- ``setup(run)``: build the system under test from the configuration
  and the seed, and warm every shape the window will use;
- ``step(run, i)``: the ``i``-th timed operation; returns its record, a
  dict with at least its host-clock start ``t0`` and end ``t1``;
- ``finish(run)``: after the window, add to the records what the
  metrics need (such as edges counted from returned levels);
- ``release(run)``: drop the program's objects before the reference;
- ``check(run)``: compare what the window produced with the plain
  reference; returns ``[(name, value, limit), ...]``, each passing when
  ``value <= limit``.

Each metric is read by ``metrics/<name>.py``, whose ``read(run)``
returns a number, or None where the run has nothing to read.  A new
cell, configuration, traffic mix or metric is new files and entries.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def jax_seed(seed: int) -> int:
    """A 31-bit key for ``jax.random`` from a seed of any size (the
    seeds may exceed 32 signed bits)."""
    import numpy as np

    return int(np.random.default_rng(int(seed)).integers(1 << 31))


@dataclass
class Cell:
    """One entry of ``workloads`` with its configuration, traffic mix
    and the metrics it reports."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    root: str = ROOT

    @property
    def op(self) -> str:
        return self.traffic["op"]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: Optional[dict] = None,
              root: str = ROOT) -> Cell:
    """The cell ``name`` as ``BENCHMARK.json`` (or ``bench``) has it."""
    bench = bench if bench is not None else load_json(
        os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(has {sorted(cells)})")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(os.path.join(root, cfg["file"])),
        traffic=load_json(os.path.join(root, "benchmark", "traffic",
                                       w["traffic"] + ".json")),
        end_to_end=e2e, per_layer=per_layer, root=root)


@dataclass
class Run:
    """What one run of a cell knows: its inputs, the records of its
    timed operations, and what was read around them."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    control: bool = False
    state: Any = None
    records: list = field(default_factory=list)
    window: tuple = (0.0, 0.0)
    setup_s: Optional[float] = None
    reduced: Any = None            # trace.Reduced of the traced window
    counters: dict = field(default_factory=dict)   # obs deltas
    events: dict = field(default_factory=dict)     # jax.monitoring
    notes: dict = field(default_factory=dict)      # earlier lines

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def rng(self, stream: int):
        """A numpy generator for one named use of the seed."""
        import numpy as np

        return np.random.default_rng([int(self.seed), int(stream)])


class EventCounter:
    """Counts ``jax.monitoring`` duration events (compiles, cache reads)
    while armed.  Registered once per process."""

    _instance = None

    def __init__(self):
        self.armed = False
        self.counts: dict = {}

    @classmethod
    def get(cls) -> "EventCounter":
        if cls._instance is None:
            import jax

            cls._instance = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._instance._on)
        return cls._instance

    def _on(self, event: str, secs: float, **_) -> None:
        if self.armed:
            c = self.counts.setdefault(event.rsplit("/", 1)[-1], [0, 0.0])
            c[0] += 1
            c[1] += secs

    def start(self) -> None:
        self.counts = {}
        self.armed = True

    def stop(self) -> dict:
        self.armed = False
        return {k: {"count": v[0], "seconds": v[1]}
                for k, v in sorted(self.counts.items())}


def fresh_compiles(events: dict) -> int:
    """Backend compiles that the persistent cache did not serve (a
    program read from the cache also reports a compile event)."""
    n = events.get("backend_compile_duration", {}).get("count", 0)
    return n - events.get("cache_retrieval_time_sec", {}).get("count", 0)


def op_module(op: str):
    return importlib.import_module(f"benchmark.ops.{op}")


def _reader(name: str, root: str):
    path = os.path.join(root, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(run: Run, metrics: list) -> dict:
    out = {}
    for m in metrics:
        v = _reader(m["name"], run.cell.root).read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def _counters() -> dict:
    from legate_sparse_tpu import obs

    return dict(obs.counters.snapshot())


def window(run: Run, drv) -> None:
    """Run timed operations back to back until ``run.seconds`` have
    passed; the window closes when the operation that crosses that
    mark completes."""
    import jax

    ev = EventCounter.get()
    before = _counters()
    ev.start()
    i = 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("window"):
        while True:
            rec = drv.step(run, i)
            run.records.append(rec)
            i += 1
            if rec["t1"] - t0 >= run.seconds:
                break
    run.window = (t0, run.records[-1]["t1"])
    run.events = ev.stop()
    after = _counters()
    run.counters = {k: after[k] - before.get(k, 0) for k in after
                    if after[k] != before.get(k, 0)}


def traced_window(run: Run, drv) -> None:
    """The window under the profiler; its trace is reduced, then the
    trace directory is deleted."""
    import jax

    from benchmark import trace

    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp,
                                 profiler_options=trace.profile_options())
        try:
            window(run, drv)
        finally:
            jax.profiler.stop_trace()
        run.reduced = trace.load_reduced(tmp, [d.id for d in run.devices])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def emit(line: dict, stream=None) -> None:
    print(json.dumps(line), file=stream or sys.stdout, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices: list, t_start: Optional[float] = None,
             control: bool = False) -> dict:
    """Set up, measure, read and check one run of ``cell``; returns the
    result line.  Prints the earlier lines to stdout and the compared
    numbers to stderr."""
    import jax

    from legate_sparse_tpu import obs

    t_start = time.perf_counter() if t_start is None else t_start
    run = Run(cell=cell, seed=int(seed), seconds=float(seconds),
              trace=bool(trace), devices=list(devices), control=control)
    drv = op_module(cell.op)
    ev = EventCounter.get()
    ev.start()
    # The dispatch path each op's span records, read in set-up only.
    obs.enable()
    drv.setup(run)
    obs.disable()
    run.notes["paths"] = _span_paths(obs.records())
    obs.reset()
    run.setup_s = time.perf_counter() - t_start
    run.notes["setup_events"] = ev.stop()
    emit({"setup": {"setup_s": run.setup_s,
                    "compiles": fresh_compiles(run.notes["setup_events"]),
                    "paths": run.notes["paths"],
                    "events": run.notes["setup_events"]}})

    error = None
    try:
        (traced_window if run.trace else window)(run, drv)
    except Exception as e:  # the run reports it: correct is false
        error = f"{type(e).__name__}: {e}"[:2000]
    attempted = len(run.records) + (error is not None)
    mem = memory_peak(run.devices)
    emit({"window": {"window_s": run.window_s, "ops": len(run.records),
                     "op_s": _spread([r["t1"] - r["t0"] for r in run.records]),
                     "totals": _totals(run.records),
                     "compiles": fresh_compiles(run.events),
                     "events": run.events, "counters": run.counters},
          "memory": {"peak_bytes_in_use": mem}})

    metrics, checks = {}, []
    if error is None:
        drv.finish(run)
        metrics = read_metrics(
            run, cell.per_layer if run.trace else cell.end_to_end)
    drv.release(run)
    if error is None:
        t_ref = time.perf_counter()
        checks = drv.check(run)
        emit({"reference": {"host_s": time.perf_counter() - t_ref}})
    correct = (error is None and bool(checks)
               and all(v <= lim for _, v, lim in checks))
    d0 = run.devices[0]
    result = {
        "correct": correct,
        "attempted": attempted,
        # A run that raised failed its last operation; a wrong answer
        # in the sample puts every operation in doubt.
        "failed": 0 if correct else (1 if error else attempted),
        "metrics": metrics,
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": mem},
    }
    if run.trace and run.reduced is not None:
        emit({"trace": {"events": run.reduced.kind_counts(),
                        "busy_s": run.reduced.busy_s(),
                        "window_s": run.reduced.window_s}})
        result["device"]["busy_s"] = run.reduced.busy_s()
        result["device"]["window_s"] = run.reduced.window_s
        result["breakdown"] = run.reduced.breakdown()
    if error is not None:
        result["error"] = error
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in checks}
    for n, v, lim in checks:
        print(f"check {n}: {v!r} (limit {lim!r})", file=sys.stderr)
    if error is not None:
        print(f"check error: {error}", file=sys.stderr)
    return result


def _spread(values: list) -> dict:
    """Least, median and largest of the operations' seconds."""
    if not values:
        return {}
    v = sorted(values)
    return {"min": v[0], "median": v[len(v) // 2], "max": v[-1]}


def _totals(records: list) -> dict:
    """Sums of the records' whole-number fields (iterations, calls,
    edges) over the window."""
    keys = {k for r in records for k, v in r.items()
            if isinstance(v, int) and not isinstance(v, bool)
            and k not in ("i", "root")}
    return {k: sum(r.get(k, 0) for r in records) for k in sorted(keys)}


def _span_paths(records) -> dict:
    """{span name: sorted set of ``path`` attributes} of the recorded
    spans."""
    out: dict = {}
    for r in records:
        path = r.get("attrs", {}).get("path")
        if r.get("type") == "span" and path is not None:
            out.setdefault(r["name"], set()).add(str(path))
    return {k: sorted(v) for k, v in sorted(out.items())}
