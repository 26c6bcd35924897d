"""One reader per metric: ``<name>.py`` defines ``read(run)``, which
returns the metric's value, or None where the run holds nothing to
read.  What several readers share is here."""

from __future__ import annotations

from benchmark import costs


def solves(run) -> list:
    return [r for r in run.records if "iters" in r]


def idle_share(run):
    """Percent of the traced window in which no operation ran on the
    device (mean over the cell's devices)."""
    if run.reduced is None:
        return None
    return 100.0 * run.reduced.idle_share()


def is_kernel(name: str, kind: str) -> bool:
    """A Pallas kernel's event (``tpu_custom_call``)."""
    return kind == "kernel"


def band_roofline(run, calls: int, call_bytes: int):
    """Least time of ``calls`` SpMVs of ``call_bytes`` each over the
    summed device time of the band kernel's events, per chip, in
    percent."""
    if run.reduced is None or not calls:
        return None
    kernel_s = run.reduced.event_s(is_kernel)
    if kernel_s <= 0:
        return None
    least = costs.least_seconds(
        calls * call_bytes, calls * costs.spmv_flops(run.state.nnz),
        run.cell.chips, run.devices[0].device_kind)
    return costs.share(least, kernel_s)
