# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Distributed graph analytics: semiring SpMV engine + algorithms.

Graph traversal IS SpMV over a different semiring (the GraphBLAS
observation; the scalable-distributed-SpMV decomposition of
arXiv:1112.5588 applies verbatim once the add/multiply pair is
configurable).  This package holds:

- :mod:`~legate_sparse_tpu.graph.semiring` — the closed semiring
  catalog (``plus-times``, ``min-plus``, ``max-times``, ``or-and``);
- :mod:`~legate_sparse_tpu.graph.algorithms` — distributed BFS, SSSP
  (Bellman-Ford), connected components and PageRank built as iterated
  semiring ``dist_spmv`` (docs/GRAPH.md cookbook);
- :func:`matvec` — the single-device semiring SpMV dispatcher over the
  autotune kernel catalog labels.

The generalized kernels themselves live in ``ops/spmv.py``
(``*_semiring_*``: same masking/IEEE contract as the plus-times
kernels with the padding value generalized to the semiring's additive
identity) and the distributed realizations in ``parallel/dist_csr.py``
(``dist_spmv(..., semiring=)``).
"""

from __future__ import annotations

from .semiring import (  # noqa: F401
    MAX_TIMES,
    MIN_PLUS,
    OR_AND,
    PLUS_TIMES,
    SEMIRINGS,
    Semiring,
    resolve,
)

from .algorithms import (  # noqa: F401
    bfs,
    connected_components,
    pagerank,
    sssp,
)


def matvec(A, x, semiring="plus-times", kernel=None):
    """Single-device semiring SpMV ``y = A (x)`` over the catalog
    kernels, dispatched by autotune registry label.

    ``kernel`` picks the packed structure explicitly: "semiring-csr"
    (default — masked gather/segment-reduce over the row-ids pack; an
    or-and product reduces rows by a prefix count read at ``indptr``),
    "semiring-ell" or "semiring-sliced-ell" (require the matrix's ELL
    / sliced-ELL cache to exist, exactly like the plus-times
    candidates they generalize).  All three produce identical results
    for a given semiring; they are one kernel family with three
    memory layouts, which is why the autotuner may race them.
    """
    import jax.numpy as jnp

    from .. import obs as _obs
    from ..ops import spmv as _sp
    from .semiring import resolve as _resolve

    sr = resolve(semiring) if not isinstance(semiring, Semiring) \
        else semiring
    _obs.inc("graph.matvec." + sr.name)
    label = kernel or "semiring-csr"
    if label == "semiring-ell":
        ell = A._get_ell()
        if ell is None:
            raise ValueError(
                "graph.matvec: kernel='semiring-ell' but the matrix "
                "has no ELL pack (padding budget exceeded?)")
        return _sp.ell_semiring_spmv(ell[0], ell[1], ell[2], x,
                                     sr.add, sr.mul)
    if label == "semiring-sliced-ell":
        bins = A._get_sliced_ell()
        if bins is None:
            raise ValueError(
                "graph.matvec: kernel='semiring-sliced-ell' but the "
                "matrix has no sliced-ELL pack (empty matrix?)")
        return _sp.sliced_ell_semiring_spmv(bins, x, A.shape[0],
                                            sr.add, sr.mul)
    if label != "semiring-csr":
        raise ValueError(f"graph.matvec: unknown kernel {label!r}")
    nnz = jnp.asarray(A.data.shape[0], dtype=jnp.int32)
    return _sp.csr_semiring_spmv_rowids_masked(
        A.data, A.indices, A._get_row_ids(), nnz, x, A.shape[0],
        sr.add, sr.mul, row_ptr=A.indptr)
