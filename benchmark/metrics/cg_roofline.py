"""The solves' least time over their measured time, in percent.  Least
time: iterations x nnz x itemsize bytes (the operator's stored values,
streamed once an iteration) over the chips' HBM bandwidth
(``benchmark/costs.py``)."""

from benchmark import costs
from benchmark.metrics import solves


def read(run):
    s = solves(run)
    if not s:
        return None
    st = run.state
    iters = sum(r["iters"] for r in s)
    least = costs.least_seconds(
        iters * costs.cg_iteration_bytes(st.nnz, st.itemsize),
        iters * costs.spmv_flops(st.nnz), run.cell.chips,
        run.devices[0].device_kind)
    return costs.share(least, sum(r["t1"] - r["t0"] for r in s))
