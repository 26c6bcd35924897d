"""Blocking device-to-host fetches per distributed solve: the window's
change of the library's ``transfer.host_sync.*`` counters (``dist_cg``
counts its ‖b‖ fetch as ``dist_cg_norm``) over its solves."""

from benchmark import spans


def read(run):
    return spans.host_syncs(run, "iters")
