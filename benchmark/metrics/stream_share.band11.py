"""The bytes the band kernel's blocks really stream, over its device
time, as a share of the chip's HBM peak, in percent.

The bytes are the sum of the ``bytes`` attribute of the window's
``pallas.spmv`` spans (``pallas_dia.stream_bytes``: the band values,
three x tiles and y a grid step); the time is the summed device time
of the kernel's events.  Near 100% the kernel is bound by bytes; well
under it, by its work in registers (the rolls and selects of the
shifted x).  It cannot pass 100% unless the pipeline skips a fetch.
None without a trace or without such spans."""

from benchmark import costs
from benchmark.metrics import is_kernel


def read(run):
    if run.reduced is None:
        return None
    from legate_sparse_tpu import obs

    lo, hi = (t * 1e9 for t in run.window)
    nbytes = sum(r.get("attrs", {}).get("bytes", 0) for r in obs.records()
                 if r.get("type") == "span" and r["name"] == "pallas.spmv"
                 and lo <= r["ts_ns"] <= hi)
    kernel_s = run.reduced.event_s(is_kernel)
    if not nbytes or kernel_s <= 0:
        return None
    least = costs.least_seconds(nbytes, 0, run.cell.chips,
                                run.devices[0].device_kind)
    return costs.share(least, kernel_s)
