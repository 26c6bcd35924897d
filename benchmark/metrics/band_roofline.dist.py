"""The per-shard band kernel inside the distributed solves: least time
of its calls (one per iteration and one for the initial residual), each
streaming the operator's stored values spread over the cell's chips,
over the kernel events' device time averaged over the devices, in
percent."""

from benchmark import costs
from benchmark.metrics import band_roofline, solves


def read(run):
    st = run.state
    return band_roofline(run, sum(r["iters"] + 1 for r in solves(run)),
                         costs.resident_spmv_bytes(st.nnz, st.itemsize))
