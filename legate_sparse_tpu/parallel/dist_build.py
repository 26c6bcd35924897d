# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Sharded matrix construction: build each row block on its own shard.

Kills the host-assembly bottleneck the reference acknowledges for its
dense→CSR path (reference ``legate_sparse/csr.py:134-145`` runs a
single-process manual task) and that round 1's ``shard_csr`` reproduced
(host numpy build of the full CSR before sharding).  Here a banded
matrix never exists as a host CSR: each shard computes its (rps, W) ELL
blocks directly on device from the diagonal *descriptions* — scalars,
per-diagonal value arrays (sliced per shard, never concatenated into a
global CSR), or jit-traceable callables (zero host data at any size).

At 1e8 rows (BASELINE.md north star) this is the difference between a
multi-minute single-host build and an O(nnz/R)-per-device one.
"""

from __future__ import annotations

import math
from typing import Callable, Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp

from ..types import index_dtype
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .dist_csr import DistCSR
from .mesh import ROW_AXIS, make_row_mesh

DiagSpec = Union[float, int, np.ndarray, Callable]


def band_ell_local(vals_by_diag, offs_dev, n: int, rps: int, halo: int,
                   start, r, r_l):
    """Per-shard full-band -> ELL assembly (shared by ``dist_diags`` and
    the banded distributed SpGEMM): given row-indexed diagonal values
    ``vals_by_diag`` (W, rps) and sorted ``offs_dev``, produce
    (ell_data, ell_cols, cnt) with the standard padded-slot conventions
    (padding replicates the clamped column with value 0; cols rebased to
    the halo window when ``halo >= 0``)."""
    dtype = vals_by_diag.dtype
    W = vals_by_diag.shape[0]
    # Valid diagonal range per row: o in [-r, n-1-r].
    lo = jnp.searchsorted(offs_dev, -r, side="left")
    hi = jnp.searchsorted(offs_dev, n - r, side="left")
    cnt = jnp.where(r < n, hi - lo, 0).astype(jnp.int32)
    slot = jnp.arange(W, dtype=jnp.int32)
    valid = slot[None, :] < cnt[:, None]
    d_idx = jnp.clip(
        lo[:, None] + jnp.minimum(slot[None, :],
                                  jnp.maximum(cnt[:, None] - 1, 0)),
        0, W - 1,
    )
    col = jnp.clip(r[:, None] + offs_dev[d_idx], 0, n - 1)
    ell_data = jnp.where(
        valid, vals_by_diag[d_idx, r_l[:, None]], jnp.zeros((), dtype)
    )
    if halo >= 0:
        ell_cols = jnp.clip(
            col - (start - halo), 0, rps + 2 * halo - 1
        ).astype(jnp.int32)
    else:
        from ..types import coord_dtype_for

        ell_cols = col.astype(coord_dtype_for(n))
    return ell_data, ell_cols, cnt


def dist_diags(
    diagonals: Sequence[DiagSpec],
    offsets: Sequence[int],
    shape,
    mesh: Optional[Mesh] = None,
    dtype=np.float64,
    materialize_ell: bool = True,
) -> DistCSR:
    """Banded ``DistCSR`` built shard-locally (scipy ``diags`` semantics).

    Each diagonal may be:

    - a **scalar** — constant band (no host data at all);
    - a **callable** ``f(i)`` mapping the diagonal's element indices
      (a traced jnp int array, scipy ``diags`` indexing: element ``i``
      sits at ``(i, i+k)`` for ``k>=0``, ``(i-k, i)`` for ``k<0``) to
      values — evaluated on device per shard;
    - an **array** of length ``n - |k|`` — sliced per shard on host
      (views + one (rps,) copy per shard; the global CSR is never
      materialized).

    The result is the ELL layout ``shard_csr`` would pick for a banded
    matrix, with the same halo/rebase invariants, plus DIA fast-path
    blocks in halo mode.  ``materialize_ell=False`` (halo mode only)
    skips the ELL blocks entirely — the memory-lean scale path: the
    matrix then supports ``dist_spmv``/``dist_diagonal``/``to_csr``
    (solvers) but not block consumers like ``dist_spgemm``.
    """
    if mesh is None:
        mesh = make_row_mesh()
    rows, cols = int(shape[0]), int(shape[1])
    if rows != cols:
        raise NotImplementedError("dist_diags requires a square shape")
    n = rows
    order = np.argsort(np.asarray(offsets, dtype=np.int64), kind="stable")
    offs = np.asarray(offsets, dtype=np.int64)[order]
    diags_sorted = [diagonals[i] for i in order]
    if len(set(offs.tolist())) != len(offs):
        raise ValueError("duplicate offsets")
    W = len(offs)
    # Row-shard count: the size of the "rows" axis only (a 2-D
    # grid mesh replicates the matrix along "cols").
    R = int(mesh.shape[ROW_AXIS])
    rps = math.ceil(n / R) if n else 1
    rows_p = R * rps
    starts = np.minimum(np.arange(R) * rps, n)

    # Halo decision mirrors shard_csr: every window reach must fit one
    # neighbor block on each side.
    reach = int(max(offs.max(initial=0), -offs.min(initial=0)))
    halo = reach if reach <= rps else -1
    if not materialize_ell and halo < 0:
        raise ValueError(
            "materialize_ell=False requires halo mode "
            f"(band reach {reach} > rows-per-shard {rps})"
        )

    dtype = np.dtype(dtype)

    # Host-array diagonals -> per-shard (rps,) windows, stacked (R, rps).
    # block[s, r_l] = value of this diagonal at global row start+r_l
    # (row-indexed for k>=0, column-indexed source i = r+k for k<0).
    array_blocks = {}
    for d, (k, spec) in enumerate(zip(offs.tolist(), diags_sorted)):
        if np.isscalar(spec) or callable(spec):
            continue
        arr = np.asarray(spec, dtype=dtype)
        L = n - abs(k)
        if arr.ndim == 0:
            continue
        if arr.shape[0] != L:
            raise ValueError(
                f"diagonal {k} has length {arr.shape[0]}, expected {L}"
            )
        block = np.zeros((R, rps), dtype=dtype)
        for s in range(R):
            # Source index for local row r_l: i = r (k>=0) or r+k (k<0).
            i_lo = starts[s] + (0 if k >= 0 else k)
            i_hi = i_lo + rps
            o_lo, o_hi = max(i_lo, 0), min(i_hi, L)
            if o_hi > o_lo:
                block[s, o_lo - i_lo : o_hi - i_lo] = arr[o_lo:o_hi]
        array_blocks[d] = jax.device_put(
            jnp.asarray(block), NamedSharding(mesh, P(ROW_AXIS))
        )

    offs_dev = jnp.asarray(offs)

    def kernel(*blocks):
        shard = jax.lax.axis_index(ROW_AXIS)
        start = shard.astype(index_dtype()) * rps
        r_l = jnp.arange(rps, dtype=index_dtype())
        r = start + r_l

        # vals_by_diag[d, r_l] = value of diagonal d at global row r.
        vals = []
        b_iter = iter(blocks)
        for d, (k, spec) in enumerate(zip(offs.tolist(), diags_sorted)):  # lint: disable=trace-purity — offs is a host np array; static per-diag unroll at trace time is deliberate
            if d in array_blocks:
                vals.append(next(b_iter)[0])
            elif callable(spec):
                i = r + min(k, 0)
                i = jnp.clip(i, 0, max(n - abs(k) - 1, 0))
                vals.append(jnp.asarray(spec(i), dtype=dtype))
            else:
                vals.append(
                    jnp.full((rps,), float(spec), dtype=dtype)
                )
        vals_by_diag = jnp.stack(vals)                      # (W, rps)

        outs = ()
        if materialize_ell:
            ell_data, ell_cols, cnt = band_ell_local(
                vals_by_diag, offs_dev, n, rps, halo, start, r, r_l
            )
            outs += (ell_data[None], ell_cols[None], cnt[None])
        if halo >= 0:
            # DIA fast-path blocks (gather-free dist_spmv): value of
            # diagonal d at local row r, zeroed outside the matrix.
            tgt = r[:, None] + offs_dev[None, :]
            in_range = jnp.logical_and(
                jnp.logical_and(tgt >= 0, tgt < n), r[:, None] < n
            )                                            # (rps, W)
            dia_block = jnp.where(
                in_range.T, vals_by_diag, jnp.zeros((), dtype)
            )
            outs += (dia_block[None],)
        return outs

    blocks = tuple(array_blocks[d] for d in sorted(array_blocks))
    in_specs = tuple(P(ROW_AXIS, None) for _ in blocks)
    ell_specs = (P(ROW_AXIS, None, None), P(ROW_AXIS, None, None),
                 P(ROW_AXIS, None))
    out_specs = (ell_specs if materialize_ell else ()) + (
        (P(ROW_AXIS, None, None),) if halo >= 0 else ()
    )
    results = shard_map(
        kernel, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(*blocks)

    data = cols_b = counts = dia_data = None
    if materialize_ell:
        data, cols_b, counts = results[:3]
        results = results[3:]
    if halo >= 0:
        (dia_data,) = results

    from .dist_csr import attach_dia_prepack

    return attach_dia_prepack(DistCSR(
        data=data, cols=cols_b, counts=counts, row_ids=None,
        shape=(n, n), rows_per_shard=rps, halo=halo, ell=True, mesh=mesh,
        dia_data=dia_data,
        dia_offsets=(tuple(int(o) for o in offs.tolist())
                     if halo >= 0 else None),
        # Stored entries = every in-range band slot (explicit zeros
        # from callable diagonals included — they occupy ELL slots).
        nnz_hint=sum(n - abs(int(k)) for k in offs.tolist()),
    ))


def dist_poisson2d(N: int, mesh: Optional[Mesh] = None,
                   dtype=np.float64,
                   materialize_ell: bool = True) -> DistCSR:
    """5-point 2-D Poisson operator, built entirely on device (no host
    data of any size — the boundary pattern is a traced callable)."""
    n = N * N

    def off1(i):
        # Coupling (i, i+1) is zero across grid-row boundaries.
        return jnp.where((i + 1) % N == 0, 0.0, -1.0)

    return dist_diags(
        [4.0, off1, off1, -1.0, -1.0],
        [0, 1, -1, N, -N],
        shape=(n, n), mesh=mesh, dtype=dtype,
        materialize_ell=materialize_ell,
    )
