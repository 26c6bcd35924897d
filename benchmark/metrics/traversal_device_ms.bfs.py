"""Device-busy milliseconds per search: the union of the device's
operation intervals in the traced window over the searches in it."""


def read(run):
    n = sum(1 for r in run.records if "root" in r)
    if run.reduced is None or not n:
        return None
    return 1e3 * run.reduced.busy_s() / n
