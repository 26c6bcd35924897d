"""Device µs per CG iteration in the scalar reductions: the
``all-reduce`` events of ``dist_cg``'s three ``psum``s an iteration, a
mean over the devices."""

from benchmark.collectives import us_per_iteration


def read(run):
    return us_per_iteration(run, "all-reduce")
