"""Percent of the traced window in which the device ran nothing while
the host was inside the library's ``graph.bfs`` span."""

from benchmark import spans


def read(run):
    return spans.idle_in_spans(run, "graph.bfs")
