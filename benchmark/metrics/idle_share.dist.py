"""Percent of the traced window in which the devices ran nothing (a
mean over the four)."""

from benchmark.metrics import idle_share


def read(run):
    return idle_share(run)
