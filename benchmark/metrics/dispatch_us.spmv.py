"""Host microseconds from entering ``A @ x`` to its return, with no
wait for the device (a mean over the window's calls)."""


def read(run):
    calls = sum(r.get("calls", 0) for r in run.records)
    if not calls:
        return None
    return 1e6 * sum(r["dispatch_s"] for r in run.records) / calls
