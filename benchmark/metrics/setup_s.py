"""Seconds from the start of the process to the first timed operation:
JAX and chip start, data made from the seed, the build through the
library's constructors, and the warm-up (with compiles, on a first
run)."""


def read(run):
    return run.setup_s
