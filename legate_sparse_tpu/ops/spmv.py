# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Sparse matrix-vector / matrix-matrix products.

TPU-native replacement for the reference's CSR SpMV row-split task family
(reference: ``src/sparse/array/csr/spmv.cc:36-44`` CPU loop,
``spmv_omp.cc:36-45``, ``spmv.cu:62-152`` cuSPARSE with the
shifted-pointer trick).  The row-block distribution strategy
(``csr.py:562-593`` align + image constraints) lives in
``parallel/dist_csr.py``; this module is the single-shard kernel.

Kernel choice on TPU:
- General CSR: gather x by column index, multiply, ``segment_sum`` by row.
  XLA lowers the gather + segmented reduction onto the VPU; no scalar
  loops, no dynamic shapes.
- Structured (banded/DIA) matrices keep the gather-free shifted-add
  kernels in ``ops/dia_ops.py`` (use ``dia_array.dot``).

Observability: each jitted kernel body bumps a ``trace.<kernel>``
counter — the body only executes on a jit cache miss, so the counter
IS the retrace/compile count for that kernel (``obs/counters.py``
naming contract).
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs as _obs
from .convert import row_ids_from_indptr


@partial(jax.jit, static_argnames=("rows",))
def csr_spmv(data, indices, indptr, x, rows: int):
    """y[i] = sum_j data[j] * x[indices[j]] over row i's extent.

    Matches the reference leaf computation (``spmv.cc:36-44``) as one
    gather-multiply-segment_sum.  Prefer ``csr_spmv_rowids`` /
    ``ell_spmv`` (cached-structure paths) in iterative callers: they skip
    the per-call ``searchsorted`` the same way Legion caches partitions
    across solver iterations (reference §3.2 partition-caching note).
    """
    _obs.inc("trace.csr_spmv")
    nnz = data.shape[0]
    row_ids = row_ids_from_indptr(indptr, nnz)
    prod = data * x[indices]
    return jax.ops.segment_sum(
        prod, row_ids, num_segments=rows, indices_are_sorted=True
    )


@partial(jax.jit, static_argnames=("rows",))
def csr_spmv_rowids(data, indices, row_ids, x, rows: int):
    """SpMV with precomputed per-nnz row ids (static matrix structure)."""
    _obs.inc("trace.csr_spmv_rowids")
    prod = data * x[indices]
    return jax.ops.segment_sum(
        prod, row_ids, num_segments=rows, indices_are_sorted=True
    )


@partial(jax.jit, static_argnames=("rows",))
def csr_spmv_rowids_masked(data, indices, row_ids, valid_nnz, x, rows: int):
    """SpMV over a zero-padded nonzero suffix: slots >= ``valid_nnz``
    contribute an exact 0 (masked product, not 0*x — preserves IEEE
    semantics against non-finite x, same invariant as ``ell_spmv``)."""
    _obs.inc("trace.csr_spmv_rowids_masked")
    nnz = data.shape[0]
    slot = jnp.arange(nnz, dtype=jnp.int32)
    prod = jnp.where(
        slot < valid_nnz, data * x[indices],
        jnp.zeros((1,), dtype=data.dtype),
    )
    return jax.ops.segment_sum(
        prod, row_ids, num_segments=rows, indices_are_sorted=True
    )


@partial(jax.jit, static_argnames=("rows",))
def csr_spmm_rowids_masked(data, indices, row_ids, valid_nnz, X, rows: int):
    """SpMM over a zero-padded nonzero suffix (the engine's bucketed
    batch kernel): slots >= ``valid_nnz`` contribute an exact 0 via a
    masked product — identical IEEE semantics to
    ``csr_spmv_rowids_masked`` column by column, so a stacked dispatch
    of k requests is bit-for-bit the k individual dispatches."""
    _obs.inc("trace.csr_spmm_rowids_masked")
    nnz = data.shape[0]
    slot = jnp.arange(nnz, dtype=jnp.int32)
    prod = jnp.where(
        (slot < valid_nnz)[:, None], data[:, None] * X[indices, :],
        jnp.zeros((1, 1), dtype=data.dtype),
    )
    return jax.ops.segment_sum(
        prod, row_ids, num_segments=rows, indices_are_sorted=True
    )


@partial(jax.jit, static_argnames=("rows", "b"))
def csr_multi_spmv_rowids_masked(data, indices, row_ids, valid_nnz, X,
                                 rows: int, b: int):
    """``b`` independent masked SpMVs in ONE stacked dispatch (the
    gateway's cross-tenant batch kernel): operand slot ``i`` holds
    matrix ``i``'s padded pack and its own x vector.

    Each matrix's segment ids are offset by ``i * (rows + 1)`` — the
    ``+ 1`` keeps the pack's out-of-bounds padding row id (``rows``)
    inside matrix ``i``'s own discarded segment instead of aliasing
    matrix ``i+1``'s row 0.  Per matrix this performs exactly the
    masked product and in-order segment reduction of
    :func:`csr_spmv_rowids_masked`, so packing requests from
    different tenants/matrices is bit-for-bit invisible to each of
    them.  A slot with ``valid_nnz == 0`` (batch padding up to the
    bucketed width) contributes only exact zeros."""
    _obs.inc("trace.csr_multi_spmv_rowids_masked")
    nnz = data.shape[1]
    slot = jnp.arange(nnz, dtype=jnp.int32)
    gathered = jnp.take_along_axis(X, indices, axis=1)
    prod = jnp.where(
        slot[None, :] < valid_nnz[:, None], data * gathered,
        jnp.zeros((1, 1), dtype=data.dtype),
    )
    offs = jnp.arange(b, dtype=jnp.int32)[:, None] * (rows + 1)
    seg = (row_ids + offs).reshape(-1)
    out = jax.ops.segment_sum(
        prod.reshape(-1), seg, num_segments=b * (rows + 1),
        indices_are_sorted=True,
    )
    return out.reshape(b, rows + 1)[:, :rows]


@partial(jax.jit, static_argnames=("rows",))
def coo_spmv_segment(data, row_ids, col_ids, valid_nnz, x, rows: int):
    """Masked COO SpMV over a pow2-padded update buffer (the delta
    layer's serving kernel, docs/MUTATION.md): slots >= ``valid_nnz``
    contribute an exact 0 via the masked product (never ``0*x`` — the
    same IEEE discipline as ``csr_spmv_rowids_masked``), and padded
    ``row_ids`` carry the out-of-range sentinel ``rows`` so
    ``segment_sum`` drops them (the engine-pack padding contract).
    The buffer is padded to a pow2 capacity bucket by the caller, so
    streaming mutation never retraces — one compile per bucket."""
    _obs.inc("trace.coo_spmv_segment")
    nnz = data.shape[0]
    slot = jnp.arange(nnz, dtype=jnp.int32)
    prod = jnp.where(
        slot < valid_nnz, data * x[col_ids],
        jnp.zeros((1,), dtype=data.dtype),
    )
    # The delta layer ingests entries sorted by (row, col); the padded
    # sentinel tail (row id == rows) sorts after every valid id.
    return jax.ops.segment_sum(
        prod, row_ids, num_segments=rows, indices_are_sorted=True
    )


@jax.jit
def ell_spmv(ell_data, ell_cols, ell_counts, x):
    """SpMV over ELL-packed structure: the TPU fast path.

    ``ell_data``/``ell_cols`` are (rows, W); ``ell_counts`` is the
    per-row nnz (rows,), masking padded slots' *products* so non-finite
    x entries behave exactly as in the segment-sum path (0*inf must not
    inject NaN).  One 2-D gather + a W-width masked row reduction — no
    scatter, no searchsorted.  A flat scatter costs about as much as the
    gather again: on one v5e, over the 31.4M slots of the Graph500
    scale-20 push operator, the ``segment_max`` or-and SpMV took 515.5
    ms, its gather alone 240.4 ms.
    """
    _obs.inc("trace.ell_spmv")
    W = ell_data.shape[1]
    slot = jnp.arange(W, dtype=ell_counts.dtype)
    valid = slot[None, :] < ell_counts[:, None]
    prod = jnp.where(valid, ell_data * x[ell_cols],
                     jnp.zeros((1, 1), dtype=ell_data.dtype))
    return jnp.sum(prod, axis=1)


def sliced_ell_pack(data, indices, indptr, rows: int):
    """Row-binned ("sliced") ELL pack: rows grouped by next-pow2 row
    length, one (rows_bin, W_bin) ELL block per bin.

    Flat ELL pads every row to the matrix max W, so one heavy row in a
    power-law matrix blows the ``ell_max_expand`` budget and the whole
    matrix falls back to the gather/segment-sum path.  Binning rows by
    ``next_pow2(len)`` bounds padding at < 2x the true nnz regardless
    of skew (each row pads to at most twice its own length), at the
    cost of one masked-row-reduction dispatch per occupied bin
    (<= log2(max row length) bins).

    Returns a tuple of ``(ell_data, ell_cols, ell_counts, row_idx)``
    bins — ``row_idx`` maps each bin row back to its original row —
    or None for an empty matrix.  Padded slots replicate the row's
    last valid column with value 0, exactly like :func:`ell_pack`;
    the kernel masks padded *products* so non-finite x entries cannot
    inject NaN through padding.  Bin membership is computed on host
    from the (rows+1,) indptr; the block gathers run on device.
    """
    nnz = int(indices.shape[0])
    if nnz == 0 or rows == 0:
        return None
    indptr_h = np.asarray(indptr)
    counts = (indptr_h[1:] - indptr_h[:-1]).astype(np.int64)
    nzr = counts > 0
    # next_pow2 per row; float64 log2 is exact for the int32-bounded
    # row lengths a single shard can hold.
    widths = np.ones_like(counts)
    widths[nzr] = (
        2 ** np.ceil(np.log2(counts[nzr])).astype(np.int64))
    indptr_d = jnp.asarray(indptr)
    bins = []
    for W in np.unique(widths[nzr]):
        sel = np.nonzero(nzr & (widths == W))[0]
        W = int(W)
        row_idx = jnp.asarray(sel.astype(np.int32))
        cnt = jnp.asarray(counts[sel].astype(np.int32))
        row_start = indptr_d[row_idx].astype(jnp.int32)
        row_last = jnp.clip(
            indptr_d[row_idx + 1].astype(jnp.int32) - 1, 0, nnz - 1)
        slot = jnp.arange(W, dtype=jnp.int32)
        src = jnp.minimum(row_start[:, None] + slot[None, :],
                          row_last[:, None])
        valid = slot[None, :] < cnt[:, None]
        ell_cols = indices[src]
        ell_data = jnp.where(valid, data[src],
                             jnp.zeros((1, 1), dtype=data.dtype))
        bins.append((ell_data, ell_cols, cnt, row_idx))
    return tuple(bins)


@partial(jax.jit, static_argnames=("rows",))
def sliced_ell_spmv(bins, x, rows: int):
    """SpMV over a :func:`sliced_ell_pack` structure.

    One masked ELL row-reduction per bin (same IEEE masking contract
    as :func:`ell_spmv`), scattered back to original row order with a
    unique-sorted ``.at[].set`` — rows with zero stored entries keep
    the exact-0 initial value.  The bin tuple is a pytree argument, so
    one compiled program covers a matrix's pack; a different bin
    structure retraces (counted below)."""
    _obs.inc("trace.sliced_ell_spmv")
    out_dtype = jnp.result_type(bins[0][0].dtype, x.dtype)
    y = jnp.zeros((rows,), dtype=out_dtype)
    for ell_data, ell_cols, cnt, row_idx in bins:
        W = ell_data.shape[1]
        slot = jnp.arange(W, dtype=cnt.dtype)
        valid = slot[None, :] < cnt[:, None]
        prod = jnp.where(valid, ell_data * x[ell_cols],
                         jnp.zeros((1, 1), dtype=ell_data.dtype))
        y = y.at[row_idx].set(
            jnp.sum(prod, axis=1).astype(out_dtype),
            indices_are_sorted=True, unique_indices=True)
    return y


# --- Low-precision-storage variants (f32 accumulation) ------------------
#
# SpMV is bandwidth-bound on every lane this repo targets, so bf16/f16
# value storage halves the dominant byte stream.  Each variant widens
# the gathered *product* to f32 BEFORE the reduction (the paper's
# "narrow storage, wide accumulate" contract), then narrows the result
# to ``result_type(data, x)`` — bf16 in/bf16 out, while an f32 x
# promotes the output to f32 with no intermediate copy of the matrix.
# The IEEE masking contract is unchanged: padded slots mask the
# product, never the operand.


@partial(jax.jit, static_argnames=("rows",))
def csr_spmv_rowids_f32acc(data, indices, row_ids, x, rows: int):
    """Low-byte-storage SpMV (precomputed row ids): bf16/f16 values,
    f32 ``segment_sum`` accumulation, ``result_type(data, x)`` out."""
    _obs.inc("trace.csr_spmv_rowids_f32acc")
    out_dtype = jnp.result_type(data.dtype, x.dtype)
    prod = data.astype(jnp.float32) * x[indices].astype(jnp.float32)
    y = jax.ops.segment_sum(
        prod, row_ids, num_segments=rows, indices_are_sorted=True
    )
    return y.astype(out_dtype)


@partial(jax.jit, static_argnames=("rows",))
def csr_spmv_rowids_masked_f32acc(data, indices, row_ids, valid_nnz, x,
                                  rows: int):
    """Masked low-byte SpMV (zero-padded nonzero suffix): the 2-D
    block-sharded panel kernel for bf16 panels.  Same masked-product
    IEEE contract as :func:`csr_spmv_rowids_masked`, accumulated in
    f32."""
    _obs.inc("trace.csr_spmv_rowids_masked_f32acc")
    out_dtype = jnp.result_type(data.dtype, x.dtype)
    nnz = data.shape[0]
    slot = jnp.arange(nnz, dtype=jnp.int32)
    prod = jnp.where(
        slot < valid_nnz,
        data.astype(jnp.float32) * x[indices].astype(jnp.float32),
        jnp.zeros((1,), dtype=jnp.float32),
    )
    y = jax.ops.segment_sum(
        prod, row_ids, num_segments=rows, indices_are_sorted=True
    )
    return y.astype(out_dtype)


@partial(jax.jit, static_argnames=("rows",))
def csr_spmm_rowids_f32acc(data, indices, row_ids, X, rows: int):
    """Low-byte-storage SpMM: bf16/f16 values, f32 accumulation."""
    _obs.inc("trace.csr_spmm_rowids_f32acc")
    out_dtype = jnp.result_type(data.dtype, X.dtype)
    prod = data.astype(jnp.float32)[:, None] \
        * X[indices, :].astype(jnp.float32)
    Y = jax.ops.segment_sum(
        prod, row_ids, num_segments=rows, indices_are_sorted=True
    )
    return Y.astype(out_dtype)


@jax.jit
def ell_spmv_f32acc(ell_data, ell_cols, ell_counts, x):
    """Low-byte-storage ELL SpMV: masked f32 products, f32 row
    reduction, ``result_type(ell_data, x)`` out."""
    _obs.inc("trace.ell_spmv_f32acc")
    out_dtype = jnp.result_type(ell_data.dtype, x.dtype)
    W = ell_data.shape[1]
    slot = jnp.arange(W, dtype=ell_counts.dtype)
    valid = slot[None, :] < ell_counts[:, None]
    prod = jnp.where(
        valid,
        ell_data.astype(jnp.float32) * x[ell_cols].astype(jnp.float32),
        jnp.zeros((1, 1), dtype=jnp.float32),
    )
    return jnp.sum(prod, axis=1).astype(out_dtype)


@partial(jax.jit, static_argnames=("rows",))
def sliced_ell_spmv_f32acc(bins, x, rows: int):
    """Low-byte-storage sliced-ELL SpMV: per-bin masked f32 products
    and f32 row reductions, scattered back in original row order
    (same unique-sorted ``.at[].set`` as :func:`sliced_ell_spmv`)."""
    _obs.inc("trace.sliced_ell_spmv_f32acc")
    out_dtype = jnp.result_type(bins[0][0].dtype, x.dtype)
    y = jnp.zeros((rows,), dtype=out_dtype)
    for ell_data, ell_cols, cnt, row_idx in bins:
        W = ell_data.shape[1]
        slot = jnp.arange(W, dtype=cnt.dtype)
        valid = slot[None, :] < cnt[:, None]
        prod = jnp.where(
            valid,
            ell_data.astype(jnp.float32)
            * x[ell_cols].astype(jnp.float32),
            jnp.zeros((1, 1), dtype=jnp.float32),
        )
        y = y.at[row_idx].set(
            jnp.sum(prod, axis=1).astype(out_dtype),
            indices_are_sorted=True, unique_indices=True)
    return y


# --- Semiring-generalized kernels (graph/semiring.py catalog) -----------
#
# Graph traversal is SpMV with the (add, multiply) pair swapped
# (min-plus relaxation, or-and frontier push, max-times best path —
# docs/GRAPH.md).  These kernels are the plus-times masked kernels
# with two static strings threaded through: ``add`` picks the segment
# reduction, ``mul`` the product.  The masking contract generalizes
# verbatim: a padded slot's *product* is replaced by the semiring's
# additive identity (== its multiplicative annihilator: 0 / +-inf /
# False), so the reduction absorbs it exactly as the plus-times
# kernels absorb an exact 0 — and the empty-segment fill of
# ``segment_min``/``segment_max`` (+inf / -inf) is that same identity,
# so rows with no stored entries come out right for free.

_SEG_REDUCE = {
    "sum": jax.ops.segment_sum,
    "min": jax.ops.segment_min,
    "max": jax.ops.segment_max,
}

_ROW_REDUCE = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}


def semiring_identity(add: str, dtype):
    """Additive identity of a catalog add-op as a rank-0 ``dtype``
    array — the padded-slot masking value (sum: 0; min: +inf; max:
    -inf; booleans: or IS max, identity False)."""
    dtype = jnp.dtype(dtype)
    if add == "sum":
        return jnp.zeros((), dtype=dtype)
    if dtype == jnp.bool_:
        return jnp.asarray(add == "min", dtype=dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf if add == "min" else -jnp.inf,
                           dtype=dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if add == "min" else info.min,
                       dtype=dtype)


def _semiring_product(mul: str, vals, gathered):
    """The per-slot product.  ``and`` is structural (a stored entry IS
    an edge — csgraph's explicit-zero convention), so the product is
    the gathered frontier bit, independent of the stored value."""
    if mul == "times":
        return vals * gathered
    if mul == "plus":
        return vals + gathered
    if mul == "and":
        return gathered.astype(jnp.bool_)
    raise ValueError(f"unknown semiring multiply {mul!r}")


def is_boolean_or(add: str, mul: str, vals_dtype, x_dtype) -> bool:
    """Whether the pair's add is a boolean OR: ``max`` over a bool
    product (``or-and``, or any pair over bool operands).  Given row
    bounds, the padded-CSR semiring kernels reduce such rows by a
    prefix count; every other add keeps its ``segment_*`` reduction."""
    prod = (jnp.bool_ if mul == "and"
            else jnp.result_type(vals_dtype, x_dtype))
    return add == "max" and prod == jnp.bool_


def _prefix_or(prod, row_ptr):
    """Row-wise OR of a (slots,) bool product without a scatter: row r
    holds a live product exactly when the count of live slots before
    its end exceeds the count before its start.  One int32 prefix count
    over the slots, then one gather of the ``rows + 1`` counts at the
    bounds.  Slots past ``row_ptr[-1]`` (the padding suffix) lie past
    every row's end and are never read."""
    c = jnp.cumsum(prod.astype(jnp.int32))
    at = jnp.concatenate([jnp.zeros((1,), jnp.int32), c])[row_ptr]
    return at[1:] > at[:-1]


@partial(jax.jit, static_argnames=("rows", "add", "mul"))
def csr_semiring_spmv_rowids_masked(data, indices, row_ids, valid_nnz,
                                    x, rows: int, add: str, mul: str,
                                    row_ptr=None):
    """Semiring SpMV over a padded nonzero suffix: the
    ``csr_spmv_rowids_masked`` program with the reduction and product
    generalized to the (add, mul) pair.  ``add="sum", mul="times"``
    is bit-identical to the plus-times kernel (same gather, same
    in-order segment reduction).

    ``row_ptr`` ((rows + 1,) offsets of each row's slots, the
    ``indptr`` of the valid prefix) lets a boolean OR (``add="max"``
    over a bool product, :func:`is_boolean_or`) reduce rows by a
    prefix count in place of the ``segment_max`` scatter.  That is
    exact: OR over a row is "some slot in [start, end) is live", and
    the count of live slots before ``end`` minus the count before
    ``start`` is that number of slots.  ``sum``, and ``min``/``max``
    over numbers, keep the segment reduction: a count answers only
    OR."""
    _obs.inc("trace.csr_semiring_spmv_rowids_masked")
    prod = _semiring_product(mul, data, x[indices])
    if row_ptr is not None and is_boolean_or(add, mul, data.dtype,
                                             x.dtype):
        return _prefix_or(prod, row_ptr)
    slot = jnp.arange(data.shape[0], dtype=jnp.int32)
    prod = jnp.where(slot < valid_nnz, prod,
                     semiring_identity(add, prod.dtype))
    return _SEG_REDUCE[add](
        prod, row_ids, num_segments=rows, indices_are_sorted=True
    )


@partial(jax.jit, static_argnames=("rows", "add", "mul"))
def csr_semiring_spmm_rowids_masked(data, indices, row_ids, valid_nnz,
                                    X, rows: int, add: str, mul: str,
                                    row_ptr=None):
    """Batched semiring SpMV (k stacked operand columns in one
    dispatch — the multi-source frontier kernel, the semiring arm of
    the PR-8 stacked ``multi_matvec`` packing): column by column this
    is exactly :func:`csr_semiring_spmv_rowids_masked`, so a batch of
    k sources is bit-for-bit the k individual sweeps.  With
    ``row_ptr``, a boolean OR takes the same prefix count, run down
    the slots for each column in turn."""
    _obs.inc("trace.csr_semiring_spmm_rowids_masked")
    if row_ptr is not None and is_boolean_or(add, mul, data.dtype,
                                             X.dtype):
        # One column at a time: a count of all k at once would hold 4k
        # bytes a slot (and a narrow k pads to a lane's 128 on TPU).
        return jax.lax.map(lambda x: _prefix_or(
            _semiring_product(mul, data, x[indices]), row_ptr), X.T).T
    prod = _semiring_product(mul, data[:, None], X[indices, :])
    slot = jnp.arange(data.shape[0], dtype=jnp.int32)
    prod = jnp.where((slot < valid_nnz)[:, None], prod,
                     semiring_identity(add, prod.dtype))
    return _SEG_REDUCE[add](
        prod, row_ids, num_segments=rows, indices_are_sorted=True
    )


@partial(jax.jit, static_argnames=("add", "mul"))
def ell_semiring_spmv(ell_data, ell_cols, ell_counts, x, add: str,
                      mul: str):
    """Semiring SpMV over ELL-packed structure (the :func:`ell_spmv`
    program generalized): padded slots' products masked to the
    semiring identity, W-width row reduction by the add-op."""
    _obs.inc("trace.ell_semiring_spmv")
    W = ell_data.shape[1]
    slot = jnp.arange(W, dtype=ell_counts.dtype)
    valid = slot[None, :] < ell_counts[:, None]
    prod = _semiring_product(mul, ell_data, x[ell_cols])
    prod = jnp.where(valid, prod, semiring_identity(add, prod.dtype))
    return _ROW_REDUCE[add](prod, axis=1)


@partial(jax.jit, static_argnames=("add", "mul"))
def ell_semiring_spmm(ell_data, ell_cols, ell_counts, X, add: str,
                      mul: str):
    """Batched semiring SpMV over ELL structure (dense (cols, k)
    operand — the distributed multi-source frontier's per-shard
    kernel).  Frontier batches are narrow, so the (rows, W, k)
    product is materialized in one fused pass (no
    ``_ELL_SPMM_MATERIALIZE_CAP`` loop arm)."""
    _obs.inc("trace.ell_semiring_spmm")
    W = ell_data.shape[1]
    slot = jnp.arange(W, dtype=ell_counts.dtype)
    valid = slot[None, :] < ell_counts[:, None]
    prod = _semiring_product(mul, ell_data[:, :, None], X[ell_cols, :])
    prod = jnp.where(valid[:, :, None], prod,
                     semiring_identity(add, prod.dtype))
    return _ROW_REDUCE[add](prod, axis=1)


@partial(jax.jit, static_argnames=("rows", "add", "mul"))
def sliced_ell_semiring_spmv(bins, x, rows: int, add: str, mul: str):
    """Semiring SpMV over a :func:`sliced_ell_pack` structure: one
    masked ELL reduction per bin scattered back in original row order
    (same unique-sorted ``.at[].set`` as :func:`sliced_ell_spmv`).
    Rows outside every bin (zero stored entries) keep the semiring
    identity — the empty-segment value of the rowids kernels."""
    _obs.inc("trace.sliced_ell_semiring_spmv")
    probe = _semiring_product(mul, bins[0][0][:1, :1],
                              x[bins[0][1][:1, :1]])
    out_dtype = probe.dtype
    y = jnp.full((rows,), semiring_identity(add, out_dtype),
                 dtype=out_dtype)
    for ell_data, ell_cols, cnt, row_idx in bins:
        W = ell_data.shape[1]
        slot = jnp.arange(W, dtype=cnt.dtype)
        valid = slot[None, :] < cnt[:, None]
        prod = _semiring_product(mul, ell_data, x[ell_cols])
        prod = jnp.where(valid, prod,
                         semiring_identity(add, prod.dtype))
        y = y.at[row_idx].set(
            _ROW_REDUCE[add](prod, axis=1).astype(out_dtype),
            indices_are_sorted=True, unique_indices=True)
    return y


# Above this many intermediate elements (rows*W*k), ell_spmm switches to
# a W-slice accumulation loop instead of materializing the full
# (rows, W, k) product tensor (~512 MB of f32 at the default cap).
_ELL_SPMM_MATERIALIZE_CAP = 1 << 27


@jax.jit
def ell_spmm(ell_data, ell_cols, ell_counts, X):
    """Y = A @ X (dense X, shape (cols, k)) over ELL-packed structure.

    Shapes are static under jit, so the memory strategy is picked at
    trace time: one fused (rows, W, k) pass when it fits, else a
    fori_loop accumulating one W-slice at a time (transient memory
    O(rows*k) instead of O(rows*W*k))."""
    _obs.inc("trace.ell_spmm")
    rows, W = ell_data.shape
    k = X.shape[1]
    slot = jnp.arange(W, dtype=ell_counts.dtype)
    valid = slot[None, :] < ell_counts[:, None]
    if rows * W * k <= _ELL_SPMM_MATERIALIZE_CAP:
        prod = jnp.where(valid[:, :, None],
                         ell_data[:, :, None] * X[ell_cols, :],
                         jnp.zeros((1, 1, 1), dtype=ell_data.dtype))
        return jnp.sum(prod, axis=1)

    def body(w, Y):
        v = jax.lax.dynamic_slice_in_dim(valid, w, 1, axis=1)       # (rows,1)
        d = jax.lax.dynamic_slice_in_dim(ell_data, w, 1, axis=1)
        c = jax.lax.dynamic_slice_in_dim(ell_cols, w, 1, axis=1)[:, 0]
        contrib = jnp.where(v, d * X[c, :],
                            jnp.zeros((1, 1), dtype=ell_data.dtype))
        return Y + contrib

    Y0 = jnp.zeros((rows, k), dtype=ell_data.dtype)
    return jax.lax.fori_loop(0, W, body, Y0)


def ell_within_budget(rows: int, W: int, nnz: int,
                      max_expand: float) -> bool:
    """Shared ELL padding-budget predicate (single-chip + distributed)."""
    return max_expand > 0 and rows * W <= max_expand * max(nnz, 1)


def ell_pack(data, indices, indptr, rows: int, W: int, xp=jnp):
    """Pack CSR into ELL blocks; works on jnp *or* numpy (xp).

    Returns ``(ell_data, ell_cols, ell_counts)``: (rows, W) value and
    column blocks plus the (rows,) per-row nnz.  W is the matrix's max
    nonzeros-per-row.  Padded slots replicate the row's last valid
    column (keeping the gather local) with value 0; the SpMV kernels
    mask padded *products* with ``ell_counts`` so padded slots
    contribute an exact 0 even against non-finite x.

    The structure analog of the reference's cached image partitions:
    computed once per matrix, reused every SpMV.
    """
    nnz = indices.shape[0]
    counts = (indptr[1:] - indptr[:-1]).astype(xp.int32)
    if nnz == 0:
        return (
            xp.zeros((rows, W), dtype=data.dtype),
            xp.zeros((rows, W), dtype=indices.dtype),
            counts,
        )
    slot = xp.arange(W, dtype=indptr.dtype)
    row_start = indptr[:-1, None]
    row_last = xp.clip(indptr[1:, None] - 1, 0, nnz - 1)
    src = xp.minimum(row_start + slot[None, :], row_last)
    valid = slot[None, :] < counts[:, None]
    ell_cols = indices[src]
    ell_data = xp.where(valid, data[src], xp.zeros((1, 1), dtype=data.dtype))
    return ell_data, ell_cols, counts


@partial(jax.jit, static_argnames=("rows", "W"))
def ell_pack_device(data, indices, indptr, rows: int, W: int):
    """Device-side ELL pack (one fused gather; no host round trip)."""
    return ell_pack(data, indices, indptr, rows, W, xp=jnp)


@partial(jax.jit, static_argnames=("rows",))
def csr_spmm_rowids(data, indices, row_ids, X, rows: int):
    """SpMM with precomputed per-nnz row ids (static matrix structure)."""
    _obs.inc("trace.csr_spmm_rowids")
    prod = data[:, None] * X[indices, :]
    return jax.ops.segment_sum(
        prod, row_ids, num_segments=rows, indices_are_sorted=True
    )


@partial(jax.jit, static_argnames=("rows",))
def csr_spmm(data, indices, indptr, X, rows: int):
    """Y = A @ X for dense X of shape (cols, k) — column-batched SpMV.

    The reference reaches this through repeated SpMV dispatch; on TPU the
    whole k-wide gather feeds the VPU in one pass.
    """
    nnz = data.shape[0]
    row_ids = row_ids_from_indptr(indptr, nnz)
    prod = data[:, None] * X[indices, :]
    return jax.ops.segment_sum(
        prod, row_ids, num_segments=rows, indices_are_sorted=True
    )


@partial(jax.jit, static_argnames=("cols",))
def csr_rmatvec(data, indices, indptr, x, cols: int):
    """y = A.T @ x without materializing the transpose: scatter-add
    x[row]*val into column bins (used by ``sum(axis=0)`` and rmatvec
    fallbacks; the reference instead materializes ``A.T.conj()`` —
    ``linalg.py:375-390``)."""
    nnz = data.shape[0]
    row_ids = row_ids_from_indptr(indptr, nnz)
    contrib = data * x[row_ids]
    return jnp.zeros((cols,), dtype=contrib.dtype).at[indices].add(contrib)
