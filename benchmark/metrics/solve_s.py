"""Time to tolerance: the summed host-clock time of the window's
solves, each from the call to its solution on the host, over their
count."""

from benchmark.metrics import solves


def read(run):
    s = solves(run)
    if not s:
        return None
    return sum(r["t1"] - r["t0"] for r in s) / len(s)
