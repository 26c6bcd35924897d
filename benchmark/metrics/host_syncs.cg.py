"""Blocking device-to-host fetches per solve: the window's change of
the library's ``transfer.host_sync.*`` counters over its solves."""

from benchmark import spans


def read(run):
    return spans.host_syncs(run, "iters")
