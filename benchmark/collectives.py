"""Device time of the four-chip solve's collectives, per iteration.

``dist_cg``'s loop exchanges halos with ``jax.lax.ppermute`` (HLO
``collective-permute``, one two-sided phase per product) and reduces
its three scalars an iteration with ``psum`` (HLO ``all-reduce``).  The
trace names each collective's event after its HLO instruction
(``collective-permute-start.2``, ``all-reduce.7``), so an event is
matched by its kind and the start of its name."""

from __future__ import annotations

from typing import Optional

from benchmark.metrics import solves


def us_per_iteration(run, prefix: str) -> Optional[float]:
    """Device µs a CG iteration in the ``prefix`` collectives' events
    (summed in the window, a mean over the devices, over the window's
    iterations); None without a trace, on one chip, or where no such
    event ran."""
    if run.reduced is None or run.cell.chips < 2:
        return None
    iters = sum(r["iters"] for r in solves(run))
    secs = run.reduced.event_s(
        lambda n, k: k == "collective" and n.startswith(prefix))
    if not iters or secs <= 0:
        return None
    return 1e6 * secs / iters
