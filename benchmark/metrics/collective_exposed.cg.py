"""Percent of the traced window spent in collectives during which no
other operation runs on that device (a mean over the devices)."""


def read(run):
    if run.reduced is None or run.cell.chips < 2:
        return None
    return 100.0 * run.reduced.collective_exposed_s() / run.reduced.window_s
