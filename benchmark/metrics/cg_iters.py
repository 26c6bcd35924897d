"""Iterations per solve, as the solver returns them (a mean over the
window's solves)."""

from benchmark.metrics import solves


def read(run):
    s = solves(run)
    if not s:
        return None
    return sum(r["iters"] for r in s) / len(s)
