"""Graph500 TEPS: the input edge tuples in each searched component,
summed over the window's searches, over their summed host-clock
time."""


def read(run):
    s = [r for r in run.records if "edges" in r]
    if not s:
        return None
    return sum(r["edges"] for r in s) / sum(r["t1"] - r["t0"] for r in s)
