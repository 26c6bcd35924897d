"""Host milliseconds per search spent building the push operator: the
library's ``graph.build`` span (edge fetch, symmetrize, lexsort, CSR,
shard upload), summed over the window and divided by its searches."""

from benchmark import spans


def read(run):
    total = spans.total_ms(run, "graph.build")
    n = sum(1 for r in run.records if "root" in r)
    if total is None or not n:
        return None
    return total / n
