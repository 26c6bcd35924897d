"""Sweeps per search: the window's change of the library's
``graph.bfs.iters`` counter over the searches."""


def read(run):
    n = sum(1 for r in run.records if "root" in r)
    sweeps = run.counters.get("graph.bfs.iters")
    if not n or not sweeps:
        return None
    return sweeps / n
