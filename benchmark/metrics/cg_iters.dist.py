"""Iterations per distributed solve, as ``dist_cg`` returns them (a
mean over the window's solves): the one-chip ``cg_iters`` reader."""

from benchmark.metrics.cg_iters import read  # noqa: F401
