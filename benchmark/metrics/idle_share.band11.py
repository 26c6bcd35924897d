"""Percent of the traced window in which the device ran nothing."""

from benchmark.metrics import idle_share


def read(run):
    return idle_share(run)
