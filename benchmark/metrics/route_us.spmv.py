"""Host microseconds per ``A @ x`` spent routing in ``csr.py``: the
library's ``spmv`` span less its ``pallas.spmv`` child (the kernel's
own launch), a mean over the window's calls."""

from benchmark import spans


def read(run):
    t = spans.self_times_ns(run, "spmv", "pallas.spmv")
    if not t:
        return None
    return 1e-3 * sum(t) / len(t)
