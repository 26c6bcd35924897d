"""Blocking device-to-host fetches per search: the window's change of
the library's ``transfer.host_sync.*`` counters over its searches."""

from benchmark import spans


def read(run):
    return spans.host_syncs(run, "root")
