"""The band kernel of the eager calls on the 11-diagonal band: least
time of the window's calls, each reading the stored values and x and
writing y in HBM, over the summed device time of its events, in
percent.

The kernel streams more than this yardstick counts: per row, 11 value
slots, three x tiles and y, 60 bytes against the yardstick's 52
(4 * (nnz + 2n) over n, the 30 out-of-range corner slots aside).  So this share cannot pass 52/60 = 86.7%;
``stream_share.band11`` reads the kernel's own stream."""

from benchmark import costs
from benchmark.metrics import band_roofline


def read(run):
    st = run.state
    return band_roofline(run, sum(r.get("calls", 0) for r in run.records),
                         costs.spmv_bytes(st.n, st.nnz, st.itemsize))
