# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""DIA (diagonal) format arrays.

Parity with the reference's ``dia_array`` (reference:
``legate_sparse/dia.py:65-190``): storage is a 2-D ``data`` array of
shape (num_diags, cols) plus a 1-D ``offsets`` array, with scipy's
layout convention ``A[j - offset[k], j] = data[k, j]``.

The DIA format is the TPU-sweet-spot representation for the banded
matrices the benchmarks use: SpMV in DIA is a sum of statically-shifted
elementwise products — no gathers at all (``ops/dia_ops.py``, wired
into ``dia_array.dot``).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .base import CompressedBase
from .types import check_nnz, coord_dtype_for, index_dtype, nnz_dtype
from .runtime import runtime


def _band_slot_gather(data, offs, extent: int):
    """Diagonal-realignment gather over scipy's column-aligned band
    layout: ``gathered[d, p] = data[d, p + offs[d]]`` when that source
    column is in ``[0, width)``, else 0.  Returns ``(gathered, valid,
    src)`` — shared by ``transpose`` (p = column of A.T) and ``tocsr``
    (p = row, so src is the CSR column index), so the clamp/mask
    semantics live in exactly one place."""
    num_d, width = data.shape
    src = jnp.arange(extent)[None, :] + offs[:, None]
    valid = (src >= 0) & (src < width)
    gathered = jnp.where(
        valid,
        data[jnp.arange(num_d)[:, None], jnp.clip(src, 0, width - 1)],
        jnp.zeros((), dtype=data.dtype),
    )
    return gathered, valid, src


def _band_csr_keep(data, offs, rows: int, w: int):
    """(values, CSR column, keep) over the (diag, row) slot grid of a
    column-aligned band: keep marks in-range nonzero slots (scipy drops
    explicit zeros)."""
    vals, _, col = _band_slot_gather(data, offs, rows)
    return vals, col, (col >= 0) & (col < w) & (vals != 0)


@partial(jax.jit, static_argnames=("rows", "w", "cdt"))
def _band_csr_nnz(data, offs, rows: int, w: int, cdt):
    return jnp.sum(_band_csr_keep(data, offs.astype(cdt), rows, w)[2])


@partial(jax.jit, static_argnames=("rows", "w", "nnz", "cdt"))
def _band_csr_parts(data, offs, rows: int, w: int, nnz: int, cdt):
    """(data, indices, indptr) of the band's CSR form, in one program
    (the eager ops compiled ~60, two seconds each on the chip).  With
    offsets ascending, row r's entries are its kept slots in diagonal
    order, so slot (d, r) lands at ``indptr[r]`` plus the kept slots
    above it in its column of the (diag, row) grid — one scatter, and
    no (rows, diags) transpose, which the TPU would pad to 128 lanes."""
    vals, col, keep = _band_csr_keep(data, offs.astype(cdt), rows, w)
    # indptr counts nnz, not coordinates: platform-width ints (int64
    # under x64, else int32 with the documented 2^31-1 per-process nnz
    # limit — the caller's check_nnz fails loudly first).
    k = keep.astype(nnz_dtype())
    cindptr = jnp.concatenate(
        [jnp.zeros((1,), dtype=nnz_dtype()), jnp.cumsum(jnp.sum(k, axis=0))]
    )
    # Dropped slots scatter out of range (mode="drop").
    pos = jnp.where(keep, cindptr[None, :-1] + jnp.cumsum(k, axis=0) - k,
                    nnz)
    cdata = jnp.zeros((nnz,), vals.dtype).at[pos].set(vals, mode="drop")
    cindices = jnp.zeros((nnz,), cdt).at[pos].set(col.astype(cdt),
                                                   mode="drop")
    return cdata, cindices, cindptr


class dia_array(CompressedBase):
    """Sparse matrix with DIAgonal storage, backed by jax.Arrays."""

    format = "dia"

    def __init__(self, arg, shape=None, dtype=None, copy: bool = False):
        if isinstance(arg, dia_array):
            data, offsets = arg.data, arg.offsets
            shape = arg.shape if shape is None else tuple(shape)
        elif isinstance(arg, tuple) and len(arg) == 2:
            data_in, offsets_in = arg
            # Shapes fixed on the host: an eager jnp.atleast_* is one
            # more compiled program per build.
            data = jnp.asarray(data_in)
            if data.ndim < 2:
                data = jnp.asarray(np.atleast_2d(np.asarray(data)))
            offsets = jnp.asarray(np.atleast_1d(np.asarray(offsets_in)),
                                  dtype=index_dtype())
            if shape is None:
                raise ValueError("dia_array from (data, offsets) needs shape")
        else:
            raise NotImplementedError(
                "dia_array supports (data, offsets) or dia_array inputs; "
                "use csr_array for dense/scipy sources"
            )
        if dtype is not None:
            data = data.astype(np.dtype(dtype))
        elif data.dtype == np.float16:
            data = data.astype(runtime.default_float)
        if copy:
            data = jnp.array(data)
            offsets = jnp.array(offsets)
        if int(offsets.shape[0]) != int(data.shape[0]):
            raise ValueError("number of diagonals != number of offsets")
        if len(set(np.asarray(offsets).tolist())) != offsets.shape[0]:
            raise ValueError("offset array contains duplicate values")
        self._data = data
        self._offsets = offsets
        self._pack = None  # cached Pallas band pack (built lazily)
        self.shape: Tuple[int, int] = tuple(int(s) for s in shape)

    @property
    def dtype(self) -> np.dtype:
        return np.dtype(self._data.dtype)

    @property
    def data(self):
        return self._data

    @property
    def offsets(self):
        return self._offsets

    @property
    def nnz(self) -> int:
        """Count of stored values inside the matrix bounds, computed
        analytically from offsets (reference ``dia.py:90-99``)."""
        rows, cols = self.shape
        offs = np.asarray(self._offsets)
        # diagonal k has min(rows + min(k,0), cols - max(k,0)) in-bounds slots
        lengths = np.minimum(rows + np.minimum(offs, 0), cols - np.maximum(offs, 0))
        return int(np.maximum(lengths, 0).sum())

    def copy(self):
        return dia_array((self._data, self._offsets), shape=self.shape,
                         copy=True)

    def _with_data(self, data, copy: bool = False):
        return type(self)((data, self._offsets), shape=self.shape,
                          copy=copy)

    def astype(self, dtype, casting: str = "unsafe", copy: bool = True):
        dtype = np.dtype(dtype)
        if self.dtype != dtype:
            return self._with_data(self._data.astype(dtype), copy=copy)
        return self.copy() if copy else self

    def transpose(self, axes=None, copy: bool = False):
        """Transpose by realigning each diagonal (reference
        ``dia.py:114-148`` fancy-index realignment, vectorized here).

        In the transposed matrix, diagonal k becomes diagonal -k; scipy's
        column-aligned layout means entry (i, j)=data[k, j] moves to
        data'[-k, i] with i = j - k.
        """
        if axes is not None:
            raise ValueError("axes parameter not supported")
        rows, cols = self.shape
        max_dim = max(rows, cols)
        offs = self._offsets
        # new_data[d, j'] = data[d, j' + offset[d]] for j' = column in A.T
        gathered, _, _ = _band_slot_gather(self._data, offs, max_dim)
        return dia_array(
            (gathered, -offs), shape=(cols, rows)
        )

    @property
    def T(self):
        return self.transpose()

    def todia(self, copy: bool = False):
        return self.copy() if copy else self

    def toscipy(self):
        """Host scipy ``dia_array`` (format-preserving)."""
        import numpy as _np

        import scipy.sparse as _sp

        return _sp.dia_array(
            (_np.asarray(self.data), _np.asarray(self.offsets)),
            shape=self.shape,
        )

    def tocsr(self, copy: bool = False):
        """DIA -> CSR, sort-free (``_band_csr_parts``).

        The reference routes through a transpose and a masked-cumsum CSC
        build (``dia.py:152-190``, scipy's DIA->CSC algorithm).  No
        global sort is ever needed: distinct offsets mean distinct
        columns within a row, and with offsets pre-sorted ascending
        (host-side, num_d elements) the slot grid is already in CSR
        order.  Two compiled programs: the nnz count, then the parts.
        """
        from .csr import csr_array

        rows, cols = self.shape
        num_d, width = self._data.shape
        w = min(width, cols)
        cdt = np.dtype(coord_dtype_for(max(rows, cols) + 1))
        order = np.argsort(np.asarray(self._offsets), kind="stable")
        if np.array_equal(order, np.arange(num_d)):
            offs, data = self._offsets, self._data
        else:   # gather copies the whole band; skip when already sorted
            offs = self._offsets[jnp.asarray(order)]
            data = self._data[jnp.asarray(order)]
        nnz = int(_band_csr_nnz(data, offs, rows, w, cdt))
        check_nnz(nnz)
        return csr_array._from_parts(
            *_band_csr_parts(data, offs, rows, w, nnz, cdt), self.shape
        )

    # ---------------- products (DIA fast path) ----------------
    def _get_pack(self):
        """Cached Pallas band pack (same layout/dispatch as csr's
        ``_get_dia_pack``; DIA has no holes, so the pack is unmasked —
        every in-bounds slot is an entry, matching ``dia_spmv``)."""
        from .csr import csr_array
        from .ops import pallas_dia

        if self._pack is not None:
            return self._pack if self._pack is not False else None
        if not csr_array._can_build_cache(self._data):
            return None
        offsets = tuple(int(o) for o in np.asarray(self._offsets))
        packed = pallas_dia.pack_band(self._data, offsets, self.shape)
        self._pack = packed if packed is not None else False
        return packed

    def dot(self, other, out=None):
        """SpMV/SpMM via the Mosaic band kernel on TPU (same dispatch
        as csr's banded path), else shifted adds (``ops/dia_ops.py``);
        sparse operands route through CSR."""
        from .ops.dia_ops import dia_spmm, dia_spmv
        from .ops.pallas_dia import (
            SPMM_MAX_K, dia_spmm_maybe_pallas, dia_spmv_maybe_pallas,
            pallas_dia_active,
        )
        from .utils import fill_out, require_supported_dtype

        require_supported_dtype(self.dtype)
        from .utils import is_sparse_matrix

        if is_sparse_matrix(other):
            return self.tocsr().dot(other)
        other = jnp.asarray(other)
        offsets = tuple(int(o) for o in np.asarray(self._offsets))
        squeeze = False
        if other.ndim == 2 and other.shape[1] == 1:
            other = other.reshape(-1)
            squeeze = True
        if other.ndim == 1:
            if other.shape[0] != self.shape[1]:
                raise ValueError(
                    f"dimension mismatch: {self.shape} @ {other.shape}"
                )
            y = (dia_spmv_maybe_pallas(self._get_pack(), other)
                 if (pallas_dia_active()
                     and other.dtype == self._data.dtype) else None)
            if y is None:
                y = dia_spmv(self._data, other, offsets, self.shape)
            if squeeze:
                y = y[:, None]
            return fill_out(y, out)
        if other.ndim == 2:
            if other.shape[0] != self.shape[1]:
                raise ValueError(
                    f"dimension mismatch: {self.shape} @ {other.shape}"
                )
            Y = (dia_spmm_maybe_pallas(self._get_pack(), other)
                 if (pallas_dia_active()
                     and 0 < other.shape[1] <= SPMM_MAX_K
                     and other.dtype == self._data.dtype) else None)
            if Y is None:
                Y = dia_spmm(self._data, other, offsets, self.shape)
            return fill_out(Y, out)
        raise ValueError(f"cannot multiply dia_array by ndim={other.ndim}")

    def __matmul__(self, other):
        return self.dot(other)

    def todense(self, order=None, out=None):
        return self.tocsr().todense(order=order, out=out)

    toarray = todense

    def __repr__(self) -> str:
        return (
            f"<{self.shape[0]}x{self.shape[1]} sparse array of type "
            f"'{self.dtype}' with {self.nnz} stored elements "
            f"({self._data.shape[0]} diagonals) in DIAgonal format>"
        )


class dia_matrix(dia_array):
    _is_spmatrix = True
    def __pow__(self, n):
        # spmatrix semantics: matrix power.
        from .csr import csr_matrix

        out = (csr_matrix(self.tocsr()) ** n).asformat("dia")
        out.__class__ = type(self)   # keep the matrix flavor
        return out

    pass
