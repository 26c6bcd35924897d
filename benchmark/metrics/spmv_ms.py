"""Milliseconds per ``A @ x``: the window's time over the calls it
completed."""


def read(run):
    calls = sum(r.get("calls", 0) for r in run.records)
    if not calls:
        return None
    return 1e3 * run.window_s / calls
