"""The band kernel inside the solves: least time of its calls (one per
iteration and one for the initial residual), each streaming the
operator's stored values, over the summed device time of its events,
per chip, in percent."""

from benchmark import costs
from benchmark.metrics import band_roofline, solves


def read(run):
    st = run.state
    return band_roofline(run, sum(r["iters"] + 1 for r in solves(run)),
                         costs.resident_spmv_bytes(st.nnz, st.itemsize))
