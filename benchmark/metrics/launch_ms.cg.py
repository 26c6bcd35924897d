"""Host milliseconds per solve before the device can start its loop:
the library's ``cg.launch`` span (the ‖b‖ fetch, the operator wrap,
the loop's trace, lowering, compile-cache read and enqueue), summed
over the window and divided by its solves."""

from benchmark import spans
from benchmark.metrics import solves


def read(run):
    total = spans.total_ms(run, "cg.launch")
    s = solves(run)
    if total is None or not s:
        return None
    return total / len(s)
