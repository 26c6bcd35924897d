"""The band kernel of the eager calls: least time of the window's
calls, each reading the stored values and x and writing y in HBM, over
the summed device time of its events, in percent."""

from benchmark import costs
from benchmark.metrics import band_roofline


def read(run):
    st = run.state
    return band_roofline(run, sum(r.get("calls", 0) for r in run.records),
                         costs.spmv_bytes(st.n, st.nnz, st.itemsize))
