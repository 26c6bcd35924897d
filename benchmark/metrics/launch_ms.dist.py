"""Host milliseconds per solve before the devices can start their
loop: the library's ``dist_cg.launch`` span (sharding the right-hand
side, the ‖b‖ fetch, the operator wrap, the loop's trace, lowering,
compile-cache read and enqueue), summed over the window and divided by
its solves."""

from benchmark import spans
from benchmark.metrics import solves


def read(run):
    total = spans.total_ms(run, "dist_cg.launch")
    s = solves(run)
    if total is None or not s:
        return None
    return total / len(s)
