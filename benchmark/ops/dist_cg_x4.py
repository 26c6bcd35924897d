"""``"op": "dist_cg_x4"``: the ``dist_cg`` op (repeated
``legate_sparse_tpu.parallel.dist_cg(dA, b_i)`` over a row mesh of the
cell's chips) with a check that also holds the max-norm residual.

Set-up, the timed solve and its record are ``dist_cg``'s.  The check
samples the solves as ``cg.check`` does and compares each sampled
solution's float64 residual in the 2-norm (``cg.check``'s
``true_residual_max``) and in the max-norm (``residual_inf_max``),
both from one product with the benchmark's own scipy operator."""

from __future__ import annotations

import numpy as np

from benchmark.ops import common
from benchmark.ops.dist_cg import finish, release, setup, step  # noqa: F401
from benchmark.reference import poisson, residual


def check(run) -> list:
    cfg, tr = run.cell.config, run.cell.traffic
    st = run.state
    A_ref = poisson.scipy_operator(cfg)
    picked = common.sample(run.records, run.rng(7), tr["checked"])
    norms = [residual.residual_norms(
        A_ref, np.asarray(st.rhs(st.key, r["i"])), r["x"]) for r in picked]
    lim = cfg["limits"]
    return [("true_residual_max", max(t for t, _ in norms),
             lim["true_residual_max"]),
            ("residual_inf_max", max(i for _, i in norms),
             lim["residual_inf_max"])]
