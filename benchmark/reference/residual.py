"""Both norms of a solve's true residual, from one product with the
float64 scipy operator.

The four-chip solve's check needs the max-norm: a halo exchange that
answers zeros leaves each chip's edge rows without their neighbours'
coupling, a few rows in millions, which the 2-norm averages away and
the max-norm does not.  A 2^24-row residual in float64 is 128 MiB, so
the rows are not blocked."""

from __future__ import annotations

import numpy as np


def residual_norms(A_ref, b, x) -> tuple:
    """(||b - A x||_2 / ||b||_2, ||b - A x||_inf / ||b||_inf) in
    float64; both infinite for a solution of the wrong shape or with a
    value that is not finite."""
    b = np.asarray(b, dtype=np.float64)
    x = np.asarray(x, dtype=np.float64)
    if x.shape != b.shape or not np.isfinite(x).all():
        return float("inf"), float("inf")
    r = b - A_ref @ x
    return (float(np.linalg.norm(r) / np.linalg.norm(b)),
            float(np.abs(r).max() / np.abs(b).max()))
