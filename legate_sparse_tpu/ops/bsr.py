# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Block-sparse (BSR) SpMV — the TPU irregular-path kernel.

Role parity with the reference's general CSR SpMV leaf
(``src/sparse/array/csr/spmv.cc:36-44``, ``spmv.cu:62-152``): the path
for matrices that are *not* banded (those take ``ops/pallas_dia.py``).

Why block-sparse instead of an element-gather kernel: Mosaic's gather
lowering (jax ``pallas/mosaic/lowering.py::_gather_lowering_rule``)
only supports same-shape ``take_along_axis`` along one axis of a 2-D
operand — a per-lane sublane-gather or per-sublane lane-gather.  An
element-gather SpMV needs ``x[c]`` routed from lane ``c % 128`` to an
arbitrary destination lane, which that primitive cannot express in
fewer than three chained permutation stages, all VPU-serialized.  The
TPU-native formulation is the one the hardware is built for: densify
the *present* 128x128 blocks of the sparse matrix and stream them
through the MXU at HBM bandwidth, skipping absent blocks entirely
(the block-sparse "megablocks" pattern).  See IRREGULAR.md for the
measured ceilings of every alternative.

Design:

- Pack time (host numpy, structure-static): the CSR matrix is tiled
  into 128x128 blocks; blocks containing any nonzero are densified and
  stored **transposed** as ``blkT[b, c, r] = A[R0 + r, C0 + c]`` so the
  kernel's matvec ``x_chunk(1,128) @ blkT(128,128)`` lands the result
  lane-major (no in-kernel transpose).  Block ids sorted by
  (block-row, block-col); empty block-rows get one explicit zero block
  so every output row is written.
- Kernel: 1-D grid over blocks.  ``brow``/``bcol`` ride as prefetched
  scalars; the index maps stream the right x chunk and data block per
  step, and the output block spec revisits the same (1,128) y row for
  consecutive blocks of one block-row, accumulating in VMEM (zeroed on
  first visit) — the canonical Pallas reduction pattern.
- Everything is 32-bit on the TPU path (f32 values / int32 ids).

Useful-bandwidth law (random uniform density d): traffic is 64 KiB per
present block regardless of its population, so effective CSR-equivalent
bandwidth is ~ ``819 GB/s * 2 * d`` on v5e — the path wins over the XLA
gather (~4 GB/s measured) above d ≈ 0.25%, and real (clustered) sparse
matrices sit far above their uniform-density equivalent because their
nonzeros concentrate in few blocks.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

B = 128  # block edge: one lane tile; MXU-native matvec per block

# Present-block scalar ids live in SMEM; cap their footprint (2 int32
# arrays) and the densified data (64 KiB/block in HBM).
MAX_BLOCKS = 1 << 16
# int32 zero for index maps: a bare Python 0 binds as i64 in an
# x64-enabled process, which Mosaic cannot return from an index map.
_I0 = np.int32(0)


def bsr_pack(data, indices, indptr, shape, max_expand: float):
    """Host-side CSR -> transposed-BSR pack, or None over budget.

    Returns ``(blkT, brow, bcol, nbr, nbc)``: ``blkT`` (nb, B, B) with
    ``blkT[b, c, r]``, ``brow``/``bcol`` (nb,) int32 sorted by
    (brow, bcol), ``nbr``/``nbc`` the padded block-grid shape.  The
    budget check (``nb * B*B <= max_expand * nnz``) runs before any
    densification so an over-budget matrix costs one bincount, not GBs.
    """
    rows, cols = shape
    data = np.asarray(data)
    indices = np.asarray(indices)
    indptr = np.asarray(indptr)
    nnz = data.shape[0]
    if nnz == 0 or rows == 0 or cols == 0 or max_expand <= 0:
        return None

    # Native single-pass pack when the C++ helper is built (no global
    # sort — exploits CSR row order); numpy fallback below.
    from ..utils_native import native_bsr_pack

    native = native_bsr_pack(
        indptr, indices, data, rows, cols, float(max_expand), MAX_BLOCKS
    )
    if native == "over_budget":
        return None
    if native is not None:
        return native

    nbr = -(-rows // B)
    nbc = -(-cols // B)
    r = np.repeat(np.arange(rows, dtype=np.int64),
                  np.diff(indptr).astype(np.int64))
    c = indices.astype(np.int64)
    key = (r >> 7) * nbc + (c >> 7)
    uniq, inv = np.unique(key, return_inverse=True)
    # One zero block per empty block-row so y is fully written.
    missing = np.setdiff1d(
        np.arange(nbr, dtype=np.int64), uniq // nbc, assume_unique=False
    )
    nb = uniq.shape[0] + missing.shape[0]
    if nb > MAX_BLOCKS or nb * B * B > max_expand * nnz:
        return None
    all_keys = np.concatenate([uniq, missing * nbc])
    order = np.argsort(all_keys, kind="stable")
    all_keys = all_keys[order]
    # Where each original unique block landed after the merge-sort.
    pos_of_uniq = np.empty(nb, dtype=np.int64)
    pos_of_uniq[order] = np.arange(nb)
    bid = pos_of_uniq[inv]

    blkT = np.zeros((nb, B, B), dtype=np.float32)
    # Transposed fill: slot (block, c % B, r % B).
    flat = (bid * (B * B) + (c & (B - 1)) * B + (r & (B - 1)))
    np.add.at(blkT.reshape(-1), flat, data.astype(np.float32))
    brow = (all_keys // nbc).astype(np.int32)
    bcol = (all_keys % nbc).astype(np.int32)
    return blkT, brow, bcol, nbr, nbc


def _make_kernel(pl):
    def kernel(brow_ref, bcol_ref, blk_ref, x_ref, y_ref):
        i = pl.program_id(0)
        b = brow_ref[i]
        prev = brow_ref[jnp.maximum(i - 1, 0)]
        first = jnp.logical_or(i == 0, b != prev)

        @pl.when(first)
        def _():
            y_ref[...] = jnp.zeros_like(y_ref)

        xc = x_ref[0]            # (1, B)
        blkT = blk_ref[0]        # (B, B), blkT[c, r]
        # HIGHEST: an f32 matmul on the MXU otherwise runs one bf16
        # pass, ~1e-3 relative error (measured on the v5e, PR 21).
        y_ref[...] += jnp.dot(
            xc, blkT, preferred_element_type=y_ref.dtype,
            precision=jax.lax.Precision.HIGHEST,
        )[None]

    return kernel


@partial(jax.jit, static_argnames=("nbr", "nbc", "interpret"))
def bsr_spmv_pallas(blkT, brow, bcol, x3d, nbr: int, nbc: int,
                    interpret: bool = False):
    """y3d (nbr, 1, B) = A @ x over present blocks, one grid step each.

    ``x3d`` is x zero-padded and reshaped (nbc, 1, B): a (1, 1, B)
    block then equals the array's last two dimensions, which Mosaic
    requires of a block that is not (8, 128)-divisible (the SpMM
    kernel lays X out the same way).  Output rows beyond the matrix's
    true row count are garbage-free (zero blocks pad empty
    block-rows); the caller truncates after ravel.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nb = blkT.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, B, B), lambda i, brow, bcol: (i, _I0, _I0)),
            pl.BlockSpec((1, 1, B),
                         lambda i, brow, bcol: (bcol[i], _I0, _I0)),
        ],
        out_specs=pl.BlockSpec((1, 1, B),
                               lambda i, brow, bcol: (brow[i], _I0, _I0)),
    )
    return pl.pallas_call(
        _make_kernel(pl),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nbr, 1, B), jnp.float32),
        interpret=interpret,
    )(brow, bcol, blkT, x3d)


def _make_spmm_kernel(pl):
    def kernel(brow_ref, bcol_ref, blk_ref, xt_ref, y_ref):
        i = pl.program_id(0)
        b = brow_ref[i]
        prev = brow_ref[jnp.maximum(i - 1, 0)]
        first = jnp.logical_or(i == 0, b != prev)

        @pl.when(first)
        def _():
            y_ref[...] = jnp.zeros_like(y_ref)

        xt = xt_ref[0]           # (k_pad, B): X chunk transposed
        blkT = blk_ref[0]        # (B, B), blkT[c, r]
        y_ref[...] += jnp.dot(
            xt, blkT, preferred_element_type=y_ref.dtype,
            precision=jax.lax.Precision.HIGHEST,
        )[None]

    return kernel


# SpMM k cap: one (k, B) X chunk + (k, B) Y block must stay far inside
# VMEM next to the 64 KiB data block.
SPMM_MAX_K = 512


@partial(jax.jit, static_argnames=("nbr", "nbc", "interpret"))
def bsr_spmm_pallas(blkT, brow, bcol, xt3, nbr: int, nbc: int,
                    interpret: bool = False):
    """YT (nbr, k_pad, B) = A @ X over present blocks.

    ``xt3`` is X transposed and chunked: (nbc, k_pad, B) with
    ``xt3[c, :, l] = X[c*B + l, :]`` — the transposed layout makes the
    per-block product ``xt(k,B) @ blkT(B,B)`` land lane-major, same
    trick as the SpMV kernel's transposed blocks.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nb = blkT.shape[0]
    k_pad = xt3.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, B, B), lambda i, brow, bcol: (i, _I0, _I0)),
            pl.BlockSpec((1, k_pad, B),
                         lambda i, brow, bcol: (bcol[i], _I0, _I0)),
        ],
        out_specs=pl.BlockSpec((1, k_pad, B),
                               lambda i, brow, bcol: (brow[i], _I0, _I0)),
    )
    return pl.pallas_call(
        _make_spmm_kernel(pl),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nbr, k_pad, B), jnp.float32),
        interpret=interpret,
    )(brow, bcol, blkT, xt3)


@partial(jax.jit, static_argnames=("nbr", "nbc"))
def bsr_spmv_xla(blkT, brow, bcol, x2d, nbr: int, nbc: int):
    """XLA reference for the same BSR structure (differential testing
    and non-TPU platforms): gather x chunks, batched matvec, segment-sum
    rows of the result."""
    xg = x2d[bcol]                              # (nb, B)
    prod = jnp.einsum("bc,bcr->br", xg, blkT)   # (nb, B)
    return jax.ops.segment_sum(
        prod, brow, num_segments=nbr, indices_are_sorted=True
    )


class BsrStructure:
    """Device-resident pack + dispatch wrapper cached on csr_array.

    ``dtype`` is the matrix value dtype: f32 blocks stream as f32;
    bf16 matrices store bf16 blocks (half the HBM traffic — the
    dominant cost) with f32 MXU accumulation, and results come back
    in the matrix dtype either way.
    """

    def __init__(self, blkT, brow, bcol, nbr, nbc, rows, cols,
                 dtype=jnp.float32):
        self.dtype = jnp.dtype(dtype)
        self.blkT = jnp.asarray(blkT, dtype=self.dtype)
        self.brow = jnp.asarray(brow)
        self.bcol = jnp.asarray(bcol)
        self.nbr = int(nbr)
        self.nbc = int(nbc)
        self.rows = int(rows)
        self.cols = int(cols)
        self.nblocks = int(self.blkT.shape[0])

    def matvec(self, x, interpret: bool):
        pad = self.nbc * B - self.cols
        xf = jnp.asarray(x, dtype=self.dtype).ravel()
        if pad:
            xf = jnp.concatenate(
                [xf, jnp.zeros((pad,), dtype=self.dtype)]
            )
        y3d = bsr_spmv_pallas(
            self.blkT, self.brow, self.bcol, xf.reshape(self.nbc, 1, B),
            self.nbr, self.nbc, interpret=interpret,
        )
        return y3d.ravel()[: self.rows].astype(self.dtype)

    def matmat(self, X, interpret: bool):
        """Y = A @ X for dense (cols, k) X, k <= SPMM_MAX_K."""
        X = jnp.asarray(X, dtype=self.dtype)
        k = X.shape[1]
        if k > SPMM_MAX_K:
            raise ValueError(
                f"BSR SpMM supports k <= {SPMM_MAX_K}, got {k} "
                "(VMEM budget for the per-block X chunk)"
            )
        pad_r = self.nbc * B - self.cols
        if pad_r:
            X = jnp.concatenate(
                [X, jnp.zeros((pad_r, k), dtype=self.dtype)]
            )
        # Sublane-tile multiple: 8 for f32, 16 for the packed bf16 tile.
        sub = 16 if self.dtype == jnp.bfloat16 else 8
        k_pad = max(-(-k // sub) * sub, sub)
        if k_pad != k:
            X = jnp.concatenate(
                [X, jnp.zeros((X.shape[0], k_pad - k), self.dtype)],
                axis=1,
            )
        # (nbc*B, k_pad) -> (nbc, k_pad, B) transposed chunks.
        xt3 = jnp.swapaxes(X.reshape(self.nbc, B, k_pad), 1, 2)
        yt3 = bsr_spmm_pallas(
            self.blkT, self.brow, self.bcol, xt3, self.nbr, self.nbc,
            interpret=interpret,
        )
        Y = jnp.swapaxes(yt3, 1, 2).reshape(self.nbr * B, k_pad)
        return Y[: self.rows, :k].astype(self.dtype)
