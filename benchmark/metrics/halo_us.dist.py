"""Device µs per CG iteration in the halo exchange: the
``collective-permute`` events of ``dist_cg``'s ``ppermute``s, a mean
over the devices."""

from benchmark.collectives import us_per_iteration


def read(run):
    return us_per_iteration(run, "collective-permute")
