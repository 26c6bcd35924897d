# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""CSR arrays on JAX/XLA — the core data structure.

Parity target: the reference's ``csr_array`` (reference:
``legate_sparse/csr.py:88-555``) and its free functions ``spmv``
(``csr.py:562-593``) and ``spgemm_csr_csr_csr`` (``csr.py:598-748``).

TPU-first re-design (not a port):

- Storage is three ``jax.Array``s — ``data`` (nnz), ``indices`` (nnz),
  ``indptr`` (rows+1) — instead of the reference's Legion stores with a
  Rect<1> ``pos`` encoding (``csr.py:88-107``).  ``indptr`` is what XLA
  consumes directly; rect packing/unpacking disappears.
- Every method is a thin driver over jitted kernels in ``ops/``; there is
  no task runtime, mapper, or CFFI layer.
- nnz is always concrete (host int): the XLA analog of the reference
  blocking on its nnz future (``csr.py:130,714``) — static shapes are
  what let XLA tile for the MXU/VPU.
- Distribution: a ``csr_array`` may carry a row-block sharding produced
  by ``legate_sparse_tpu.parallel`` (the analog of the reference's
  ``align``/``image`` constraints, ``csr.py:580-593``); single-device
  semantics are identical.
"""

from __future__ import annotations

import contextlib
import functools
import warnings
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from scipy.sparse import SparseEfficiencyWarning

from . import obs as _obs
from .obs import latency as _lat
from .engine import route_matmat as _engine_route_matmat
from .engine import route_matvec as _engine_route_matvec
from .autotune import route_matmat as _autotune_route_matmat
from .autotune import route_matvec as _autotune_route_matvec
from .resilience import faults as _rfaults
from .resilience import policy as _rpolicy
from .settings import settings as _rsettings
from .base import CompressedBase, DenseSparseBase
from .runtime import runtime
from .types import check_nnz, coord_dtype_for, index_dtype, nnz_dtype
from .utils import cast_to_common_type, fill_out, require_supported_dtype
from .ops import convert as _convert
from .ops import dia_ops as _dia_ops
from .ops import spmv as _spmv_ops
from .ops import spgemm as _spgemm_ops

try:  # scipy is an optional interop dependency, always present in tests
    import scipy.sparse as _scipy_sparse
except ImportError:  # pragma: no cover
    _scipy_sparse = None


def _is_scipy_sparse(obj) -> bool:
    return _scipy_sparse is not None and _scipy_sparse.issparse(obj)


def _is_sparse_like(obj) -> bool:
    """Sparse-format object of this package or another library (has a
    CSR conversion, is not dense-array-like)."""
    return hasattr(obj, "tocsr") and not hasattr(obj, "__array__")


@functools.lru_cache(maxsize=None)
def _result_itemsize(a_dtype, x_dtype, x64: bool) -> int:
    """Bytes of one element of ``A @ x``'s result (JAX promotion, which
    depends on the 64-bit mode).  Cached: the promotion lattice costs
    microseconds, and the traffic model runs inside every live
    ``spmv`` span."""
    return jnp.dtype(jnp.result_type(a_dtype, x_dtype)).itemsize


def _dia_xla_nopad() -> bool:
    """Pick the XLA banded-SpMV lowering (``settings.dia_xla_variant``):
    the interior/edge-split ``dia_spmv_nopad`` skips the padded-x
    materialization — a measured ~20-25% win on bandwidth-starved CPU
    backends — while TPU keeps the padded ``dia_spmv_fused`` layout
    whose same-length slices Mosaic/XLA:TPU handle best."""
    from .settings import settings

    variant = settings.dia_xla_variant
    if variant == "nopad":
        return True
    if variant == "auto":
        try:
            return jax.devices()[0].platform == "cpu"
        except Exception:
            return False
    return False


class csr_array(CompressedBase, DenseSparseBase):
    """Compressed Sparse Row array backed by jax.Arrays.

    Constructor forms (same set as reference ``csr.py:89-286``):

    - ``csr_array(dense_2d)`` — two-pass nonzero count + compaction
      (fully shardable, unlike the reference's single-process fill,
      ``csr.py:134-145``).
    - ``csr_array(scipy_sparse)`` — adopt scipy's buffers.
    - ``csr_array(other_csr, copy=...)``.
    - ``csr_array((data, (row, col)), shape=...)`` — COO with stable
      row sort (``csr.py:183-219`` semantics).
    - ``csr_array((data, indices, indptr), shape=...)``.
    """

    format = "csr"

    def __init__(self, arg, shape=None, dtype=None, copy: bool = False):
        self._sharding_info = None  # set by parallel.shard_csr
        # None = unknown (computed lazily by has_canonical_format).
        canonical: Optional[bool] = None
        if isinstance(arg, csr_array):
            shape = arg.shape if shape is None else tuple(shape)
            data, indices, indptr = arg.data, arg.indices, arg.indptr
            canonical = arg._canonical
            if dtype is not None and np.dtype(dtype) != arg.dtype:
                data = data.astype(np.dtype(dtype))
        elif _is_scipy_sparse(arg):
            arg = arg.tocsr()
            if shape is None:
                shape = arg.shape
            check_nnz(int(arg.nnz))
            data = jnp.asarray(arg.data)
            indices = jnp.asarray(
                arg.indices, dtype=coord_dtype_for(max(arg.shape))
            )
            indptr = jnp.asarray(arg.indptr, dtype=nnz_dtype())
            canonical = bool(arg.has_canonical_format)
            if dtype is not None:
                data = data.astype(np.dtype(dtype))
        elif (isinstance(arg, tuple) and len(arg) == 2
              and all(isinstance(s, (int, np.integer)) for s in arg)):
            # Empty matrix from a shape tuple (scipy ``csr_array((M, N))``).
            shape = (int(arg[0]), int(arg[1]))
            out_dtype = np.dtype(dtype) if dtype is not None else (
                runtime.default_float
            )
            data = jnp.zeros((0,), dtype=out_dtype)
            indices = jnp.zeros((0,), dtype=coord_dtype_for(max(shape)))
            indptr = jnp.zeros((shape[0] + 1,), dtype=nnz_dtype())
            canonical = True
        elif isinstance(arg, tuple) and len(arg) == 2 and isinstance(arg[1], tuple):
            # COO: (data, (row, col))
            data_in, (row, col) = arg
            row = jnp.asarray(row)
            col = jnp.asarray(col)
            data_in = jnp.asarray(data_in)
            check_nnz(int(data_in.shape[0]))
            if shape is None:
                shape = (int(row.max()) + 1, int(col.max()) + 1)
            shape = tuple(int(s) for s in shape)
            # Pow2 shape-bucketed COO-build counter (bounded
            # cardinality): repeated same-bucket rebuilds are the
            # doctor's delta-disabled-but-rebuilding signal — a
            # workload paying full CSR reconstruction for what the
            # delta layer serves as a streamed second term
            # (docs/MUTATION.md).
            _obs.inc("build.csr.coo."
                     f"{1 << max(shape[0] - 1, 0).bit_length()}x"
                     f"{1 << max(shape[1] - 1, 0).bit_length()}")
            cdt = coord_dtype_for(max(shape))
            data, indices, indptr = _convert.coo_to_csr(
                row.astype(cdt), col.astype(cdt), data_in, shape[0]
            )
            if dtype is not None:
                data = data.astype(np.dtype(dtype))
        elif isinstance(arg, tuple) and len(arg) == 3:
            data_in, indices_in, indptr_in = arg
            if hasattr(indices_in, "__len__") or hasattr(indices_in, "shape"):
                check_nnz(int(np.shape(indices_in)[0]))
            indptr = jnp.asarray(indptr_in, dtype=nnz_dtype())
            rows = indptr.shape[0] - 1
            if shape is None:
                cols = int(jnp.max(jnp.asarray(indices_in))) + 1 if len(indices_in) else 0
                shape = (rows, cols)
            shape = tuple(int(s) for s in shape)
            indices = jnp.asarray(indices_in, dtype=coord_dtype_for(max(shape)))
            data = jnp.asarray(data_in)
            if dtype is not None:
                data = data.astype(np.dtype(dtype))
        else:
            # Dense (jax / numpy / nested list).
            dense = jnp.asarray(arg)
            if dense.ndim != 2:
                raise ValueError(
                    f"csr_array requires a 2-D input, got ndim={dense.ndim}"
                )
            if dtype is not None:
                dense = dense.astype(np.dtype(dtype))
            if shape is not None and tuple(shape) != dense.shape:
                raise ValueError("shape mismatch with dense input")
            shape = dense.shape
            nnz = _convert.dense_nnz(dense)
            data, indices, indptr = _convert.dense_to_csr(dense, nnz)
            canonical = True

        if copy:
            data = jnp.array(data)
            indices = jnp.array(indices)
            indptr = jnp.array(indptr)

        self._data = data
        self._indices = indices
        self._indptr = indptr
        self._canonical = canonical
        self._sorted = True if canonical else None
        # Cached static structure for the SpMV hot path (the analog of
        # Legion caching image partitions across solver iterations,
        # reference §3.2): built lazily on first matvec.
        self._row_ids = None
        self._ell = None
        self._ell_width = None
        self._dia = None
        self._dia_offsets = None
        self._dia_pack = None
        self._dia_fused = None
        self._bsr = None
        # Engine bucket pack: (key terms, padded operands) — built by
        # legate_sparse_tpu.engine on first routed dispatch.
        self._engine_pack = None
        # Autotune caches: structure fingerprint (verdict-key term) and
        # the row-binned sliced-ELL pack (False = tried, not viable).
        self._fingerprint = None
        self._sliced_ell = None
        self._traffic = {}
        # Sharded graph push operators (graph/algorithms.py ``_build``),
        # keyed by every input of the build besides this array.
        self._graph_ops = None
        self.shape: Tuple[int, int] = tuple(int(s) for s in shape)
        assert self._indptr.shape[0] == self.shape[0] + 1, (
            f"indptr length {self._indptr.shape[0]} != rows+1 "
            f"({self.shape[0] + 1})"
        )
        from .settings import settings as _settings

        if _settings.check_bounds:
            self._check_bounds()

    def _check_bounds(self) -> None:
        """Debug-mode index validation (LEGATE_SPARSE_TPU_CHECK_BOUNDS;
        the accessor-bounds-check analog of the reference's
        ``Legion_BOUNDS_CHECKS``, ``install.py:375-381``).  Host syncs —
        only for debugging."""
        import numpy as _np

        indptr = _np.asarray(self._indptr)
        indices = _np.asarray(self._indices)
        if indptr[0] != 0 or indptr[-1] != indices.shape[0]:
            raise IndexError(
                f"indptr endpoints [{indptr[0]}, {indptr[-1]}] "
                f"inconsistent with nnz={indices.shape[0]}"
            )
        if _np.any(_np.diff(indptr) < 0):
            raise IndexError("indptr is not monotonically non-decreasing")
        if indices.size and (
            indices.min() < 0 or indices.max() >= self.shape[1]
        ):
            raise IndexError(
                f"column indices out of range [0, {self.shape[1]}): "
                f"min={indices.min()}, max={indices.max()}"
            )

    @classmethod
    def _from_parts(cls, data, indices, indptr, shape,
                    canonical: Optional[bool] = True) -> "csr_array":
        """Internal fast constructor for kernel outputs (which are always
        row-sorted; ``canonical=True`` unless duplicates may remain)."""
        obj = cls((data, indices, indptr), shape=shape)
        obj._canonical = canonical
        return obj

    # -- structure-sharing constructor (reference ``base.py:174-196``) --
    def _with_data(self, data, copy: bool = False):
        if copy:
            data = jnp.array(data)
        out = type(self)._from_parts(
            data, self._indices, self._indptr, self.shape,
            canonical=self._canonical,
        )
        out._row_ids = self._row_ids  # sparsity structure is shared
        out._ell_width = self._ell_width
        out._dia_offsets = self._dia_offsets
        out._fingerprint = self._fingerprint
        out._sorted = self._sorted
        return out

    # ---------------- properties ----------------
    @property
    def dim(self) -> int:
        return len(self.shape)

    @property
    def nnz(self) -> int:
        return int(self._data.shape[0])

    @property
    def dtype(self) -> np.dtype:
        return np.dtype(self._data.dtype)

    @property
    def data(self):
        return self._data

    @data.setter
    def data(self, value):
        value = jnp.asarray(value)
        if value.shape != self._data.shape:
            raise ValueError("cannot change nnz via data setter")
        self._data = value
        self._invalidate_caches(structure_changed=False)

    @property
    def indices(self):
        return self._indices

    @indices.setter
    def indices(self, value):
        value = jnp.asarray(value, dtype=self._indices.dtype)
        if value.shape != self._indices.shape:
            raise ValueError("cannot change nnz via indices setter")
        self._indices = value
        # Column indices changed but the row partition (indptr) did
        # not: every cache except the per-nnz row ids is stale.
        rid = self._row_ids
        self._invalidate_caches(structure_changed=True)
        self._row_ids = rid

    @property
    def indptr(self):
        return self._indptr

    @property
    def has_canonical_format(self) -> bool:
        """True when indices are strictly increasing within every row
        (sorted, no duplicates).  Computed lazily and cached for inputs
        whose canonicalness is unknown (COO / raw-triple constructors,
        which keep duplicates to match reference ``csr.py:183-219``)."""
        if self._canonical is None:
            if self.nnz < 2:
                self._canonical = True
            else:
                row_ids = _convert.row_ids_from_indptr(self._indptr, self.nnz)
                same_row = row_ids[1:] == row_ids[:-1]
                increasing = self._indices[1:] > self._indices[:-1]
                self._canonical = bool(
                    jnp.all(jnp.logical_or(~same_row, increasing))
                )
        return self._canonical

    @property
    def has_sorted_indices(self) -> bool:
        """Non-decreasing indices within every row (duplicates allowed —
        weaker than canonical; scipy's ``has_sorted_indices``)."""
        if self._canonical:
            return True
        if getattr(self, "_sorted", None) is None:
            if self.nnz < 2:
                self._sorted = True
            else:
                row_ids = _convert.row_ids_from_indptr(
                    self._indptr, self.nnz
                )
                same_row = row_ids[1:] == row_ids[:-1]
                nondecreasing = self._indices[1:] >= self._indices[:-1]
                self._sorted = bool(
                    jnp.all(jnp.logical_or(~same_row, nondecreasing))
                )
        return self._sorted

    def sum_duplicates(self) -> None:
        """Merge duplicate (row, col) entries in place (scipy contract)."""
        if self.has_canonical_format:
            return
        row_ids, cols, vals = self._coo_parts()
        data, indices, indptr = _spgemm_ops.coalesce_coo(
            row_ids, cols, vals, self.shape[0]
        )
        self._data = data
        self._indices = indices.astype(self._indices.dtype)
        self._indptr = indptr
        self._invalidate_caches(structure_changed=True)
        self._canonical = True
        self._sorted = True

    def _canonicalized(self) -> "csr_array":
        if self.has_canonical_format:
            return self
        out = csr_array(self, copy=False)
        out.sum_duplicates()
        return out

    # ---------------- storage compression ----------------
    def compress(self, values="bfloat16", indices="auto",
                 copy: bool = False) -> "csr_array":
        """Narrow the storage representation (structure shared).

        Every hot path here is bandwidth-bound, so shrinking the
        dominant byte streams — nnz values + nnz column indices — is
        speed.  ``values`` names the target value dtype (default
        ``"bfloat16"``; ``None`` keeps the current values; any
        supported dtype is accepted, so :meth:`astype_storage` can
        widen back).  ``indices`` is ``"auto"`` (int16 whenever the
        column extent fits ``int16``, else unchanged), ``None``
        (keep), or an explicit integer dtype — which raises when the
        column extent overflows it.

        ``.dtype`` stays honest (it reports the storage dtype) while
        ``.dot`` keeps f32-grade semantics: low-precision storage
        against an f32 operand dispatches the ``ops/spmv.py``
        ``*_f32acc`` kernels (f32 accumulation, f32 output) — or the
        DIA shifted-add lowerings, whose products promote to f32 per
        element — without ever materializing a widened copy of the
        matrix.

        Declared IEEE trade for banded matrices: compressed storage
        drops the DIA hole mask (the band data is zero-filled, so
        hole products are exact zeros for every *finite* operand).  A
        non-finite operand entry aligned with a band hole therefore
        propagates NaN where canonical f32 storage masks it — values
        are already rounded; compression is opt-in lossy.
        """
        data = self._data
        if values is not None:
            vdt = np.dtype(values)
            require_supported_dtype(vdt)
            if vdt != data.dtype:
                data = data.astype(vdt)
        idx = self._indices
        if indices is not None:
            if isinstance(indices, str) and indices == "auto":
                idt = (np.dtype(np.int16)
                       if self.shape[1] - 1 <= np.iinfo(np.int16).max
                       else None)
            else:
                idt = np.dtype(indices)
                if idt.kind != "i":
                    raise ValueError(
                        f"index storage must be a signed integer "
                        f"dtype, got {idt}")
                if self.shape[1] - 1 > np.iinfo(idt).max:
                    raise ValueError(
                        f"column extent {self.shape[1]} overflows "
                        f"index dtype {idt}")
            if idt is not None and idt != np.dtype(idx.dtype):
                idx = idx.astype(idt)
            elif copy:
                idx = jnp.array(idx)
        # _with_data shares the index-dtype-independent structure
        # caches (row ids, ELL width, DIA offsets, fingerprint); the
        # value/format packs rebuild lazily at the new storage dtypes.
        out = self._with_data(data, copy=copy and data is self._data)
        out._indices = idx
        return out

    def astype_storage(self, values=None, indices=None,
                       copy: bool = False) -> "csr_array":
        """Explicit storage-representation cast: :meth:`compress` with
        keep-by-default arguments (``astype`` changes the *logical*
        dtype and upcasts operands to match; this changes only how the
        bytes are stored)."""
        return self.compress(values=values, indices=indices, copy=copy)

    @property
    def T(self):
        return self.transpose()

    # ---------------- cached matvec structure ----------------
    @staticmethod
    def _can_build_cache(*arrays) -> bool:
        """True when structure caches may be built *now*: no tracer
        operands and no ambient trace (under omnistaging even ops on
        concrete arrays stage into an active trace, so caching their
        results on the Python object would leak tracers)."""
        if any(isinstance(a, jax.core.Tracer) for a in arrays):
            return False
        try:
            from jax._src.core import trace_state_clean
        except ImportError:  # pragma: no cover - jax internals moved
            # Unknown trace state: never cache (the uncached path is
            # always correct; caching inside a trace leaks tracers).
            return False
        return trace_state_clean()

    def _get_ell(self):
        """Cached ELL packing, or None (padding too big / can't build
        under an active trace).  The pack runs on device (one fused
        gather); only the max-row-width W is a host sync, cached with
        the structure."""
        if self._ell is not None:
            return self._ell if self._ell is not False else None
        if not self._can_build_cache(self._data, self._indices,
                                     self._indptr):
            return None
        from .settings import settings

        if self._ell_width is None:
            rows = self.shape[0]
            self._ell_width = (
                max(int(jnp.max(jnp.diff(self._indptr))), 1)
                if rows and self.nnz
                else 1
            )
        W = self._ell_width
        if not _spmv_ops.ell_within_budget(
            self.shape[0], W, self.nnz, settings.ell_max_expand
        ):
            self._ell = False
            return None
        self._ell = _spmv_ops.ell_pack_device(
            self._data, self._indices, self._indptr, self.shape[0], W
        )
        return self._ell

    def _get_bsr(self):
        """Cached block-sparse (BSR) structure, or None.

        The irregular-path kernel (``ops/bsr.py``): densified present
        128x128 blocks streamed through the MXU, skipping absent
        blocks.  Built only where it can win — on TPU (the XLA gather
        SpMV runs ~2 orders of magnitude under roofline there; on CPU
        the gather path is already fine and Pallas interpret mode is
        pure-Python slow), for f32/bf16 values, within the
        ``bsr_max_expand`` densification budget.  Matrices that are
        banded never reach here (``_get_dia`` wins the dispatch).
        ``LEGATE_SPARSE_TPU_BSR_FORCE=1`` builds it on any platform
        (differential tests run the kernel in interpret mode).

        Semantic note: densified zero slots inside *present* blocks
        multiply x (scipy's own ``bsr_array`` semantics), so a
        non-finite x entry in a column CSR never stores can produce
        NaN where exact-CSR paths stay finite.  Under
        ``LEGATE_SPARSE_TPU_CHECK_BOUNDS`` (which enables
        ``jax_debug_nans``) BSR is therefore disabled.
        """
        if self._bsr is not None:
            return self._bsr if self._bsr is not False else None
        if not self._can_build_cache(self._data, self._indices,
                                     self._indptr):
            return None
        from .settings import settings

        if not settings.bsr_force and jax.devices()[0].platform != "tpu":
            self._bsr = False
            return None
        if (settings.bsr_max_expand <= 0
                or settings.check_bounds
                or self.dtype not in (jnp.float32, jnp.bfloat16)
                or not self.has_canonical_format):
            self._bsr = False
            return None
        from .ops import bsr as _bsr_ops

        pack = _bsr_ops.bsr_pack(
            self._data, self._indices, self._indptr, self.shape,
            settings.bsr_max_expand,
        )
        if pack is None:
            self._bsr = False
            return None
        self._bsr = _bsr_ops.BsrStructure(*pack, *self.shape,
                                          dtype=self.dtype)
        return self._bsr

    def _get_dia(self, with_pack: bool = False):
        """Cached banded (DIA) structure, or None.

        On TPU, HBM gathers run orders of magnitude below roofline while
        shifted-add streams hit it (measured this chip: ELL gather 1.1
        GB/s vs DIA 38 GB/s at matched size).  When the matrix is
        banded — few distinct ``col - row`` diagonals within the
        expansion budget — SpMV runs gather-free.  Returns
        ``(dia_data, offsets, mask)`` where ``mask`` is None for an
        *exact* band (every in-bounds slot is an explicit nonzero:
        bit-identical semantics for free) or an explicit-entry mask for
        a *holey* band (e.g. ``diags().tocsr()`` dropped zeros), so a
        hole never multiplies x — IEEE behavior against non-finite x
        matches CSR exactly in both cases.  The reference always pays
        the CSR gather (``dia.py:152-190`` converts DIA→CSR before any
        matvec); keeping the band structure is a deliberate TPU-first
        improvement, and it covers every headline benchmark config
        (banded SpMV sweep, 5-pt Poisson PDE, GMG fine grids).

        The build runs once per structure inside one ``dia.build`` span
        (``_dia_build``).  ``with_pack`` (the SpMV dispatch, when the
        Pallas kernel may serve the call) builds the kernel's pack in
        the same span, so a first product records one build.
        """
        if self._dia is None and self._can_build_cache(
                self._data, self._indices, self._indptr):
            with self._dia_build():
                self._build_dia()
                if with_pack and self._dia:
                    from .ops.pallas_dia import pallas_dia_active

                    if pallas_dia_active():
                        self._build_dia_pack()
        return self._dia or None

    @contextlib.contextmanager
    def _dia_build(self):
        """The ``dia.build`` span around a band structure build, counted
        in ``dia.build.count``.  Its children are ``dia.detect`` (which
        fetches its answer, so it holds its device time), ``dia.scatter``
        and ``dia.pack``; those two time the host's part only (tracing
        must not stop the host from running ahead of their device
        work, which ends inside the first product)."""
        _obs.inc("dia.build.count")
        with _obs.span("dia.build", nnz=self.nnz) as sp:
            yield
            if sp is not None:
                dia, pack = self._dia or None, self._dia_pack or None
                sp.set(diags=len(dia[1]) if dia else 0,
                       exact=bool(dia) and dia[2] is None,
                       pack_bytes=pack.rdata.nbytes if pack else 0)

    def _build_dia(self):
        """Detect the band and scatter the values into DIA storage:
        ``_dia`` becomes ``(dia_data, offsets, mask)``, or False where
        the matrix is not banded."""
        from .settings import settings

        rows, cols = self.shape
        nnz = self.nnz
        with _obs.span("dia.detect"):
            if (settings.dia_max_expand <= 0 or not nnz or not rows
                    or not self.has_canonical_format):
                self._dia = False
                return
            if self._dia_offsets is None:
                max_nd = int(min(
                    settings.dia_max_diags,
                    settings.dia_max_expand * nnz / max(cols, 1),
                ))
                offsets = (
                    _dia_ops.csr_band_offsets(
                        self._indices, self._get_row_ids(), max_nd
                    )
                    if max_nd >= 1
                    else None
                )
                self._dia_offsets = (offsets if offsets is not None
                                     else False)
            if self._dia_offsets is False:
                self._dia = False
                return
            offsets = self._dia_offsets
            # Exact band (every in-bounds slot explicit): no mask needed.
            exact = _dia_ops.band_cover(offsets, self.shape, cols) == nnz
        # Compressed-value storage (``compress()``) declares the hole
        # trade: ``dia_from_csr`` zero-fills and no mask is kept, so
        # holes read as explicit zeros — exact products for finite x.
        # The cost is that a non-finite x entry at a hole propagates
        # (0*inf) where canonical storage masks it; values are already
        # rounded, and the compress() docstring documents both.
        if str(self.dtype) in ("bfloat16", "float16"):
            exact = True
        with _obs.span("dia.scatter"):
            if exact:
                dia_data = _dia_ops.dia_from_csr(
                    self._data, self._indices, self._get_row_ids(),
                    offsets, cols,
                )
                self._dia = (dia_data, offsets, None)
            else:
                dia_data, mask = _dia_ops.dia_from_csr(
                    self._data, self._indices, self._get_row_ids(),
                    offsets, cols, with_mask=True,
                )
                self._dia = (dia_data, offsets, mask)

    def _get_dia_pack(self):
        """Cached row-aligned band pack for the Pallas DIA kernel
        (``ops/pallas_dia.py``), or None when the matrix isn't banded
        or the kernel doesn't support it (f64, band reach > tile cap).
        Built once per structure, on top of ``_get_dia()``: in the same
        ``dia.build`` span where the band is not built yet."""
        if self._dia_pack is None and self._can_build_cache(
                self._data, self._indices, self._indptr):
            if self._dia is False:
                self._dia_pack = False
            else:
                with self._dia_build():
                    if self._dia is None:
                        self._build_dia()
                    if self._dia:
                        self._build_dia_pack()
                    else:
                        self._dia_pack = False
        return self._dia_pack or None

    def _build_dia_pack(self):
        from .ops import pallas_dia as _pallas_dia

        dia_data, offsets, mask = self._dia
        with _obs.span("dia.pack"):
            packed = _pallas_dia.pack_band(dia_data, offsets, self.shape,
                                           mask=mask)
        self._dia_pack = packed if packed is not None else False

    def _get_dia_fused(self):
        """Cached padded band layout for the fused XLA SpMV
        (``ops/dia_ops.py::dia_spmv_fused``), or None when not banded.
        One extra band-sized buffer, built once per structure; pays for
        itself on the first few matvecs (the fused form runs in one
        pass where the ``at[].add`` chain runs num_diags passes)."""
        if self._dia_fused is not None:
            return self._dia_fused if self._dia_fused is not False else None
        dia = self._get_dia()
        if dia is None:
            self._dia_fused = False
            return None
        dia_data, offsets, mask = dia
        fused = _dia_ops.pad_dia(dia_data, offsets, self.shape,
                                 mask=mask, with_mask=mask is not None)
        if self._can_build_cache(self._data, self._indices,
                                 self._indptr):
            self._dia_fused = fused      # else: inside a trace, no cache
        return fused

    def _get_row_ids(self):
        """Cached per-nnz row ids, or a non-cached computation when a
        cache can't be built (inside a trace / tracer structure)."""
        if self._row_ids is not None:
            return self._row_ids
        if not self._can_build_cache(self._indptr):
            return _convert.row_ids_from_indptr(self._indptr, self.nnz)
        self._row_ids = _convert.row_ids_from_indptr(self._indptr, self.nnz)
        return self._row_ids

    def _get_fingerprint(self):
        """Cached sparsity fingerprint (``autotune.Fingerprint``), or
        None when it can't be built now (tracer structure / ambient
        trace — fingerprints feed verdict keys, which only concrete
        dispatches consult)."""
        if self._fingerprint is not None:
            return self._fingerprint
        if not self._can_build_cache(self._data, self._indices,
                                     self._indptr):
            return None
        from .autotune import compute_fingerprint

        self._fingerprint = compute_fingerprint(self)
        return self._fingerprint

    def _get_sliced_ell(self):
        """Cached row-binned ("sliced") ELL pack, or None (empty /
        oversized / can't build under an active trace).  Unlike flat
        ELL there is no expansion budget: pow2 row bins bound padding
        below 2x nnz regardless of row-length skew."""
        if self._sliced_ell is not None:
            return self._sliced_ell if self._sliced_ell is not False else None
        if not self._can_build_cache(self._data, self._indices,
                                     self._indptr):
            return None
        rows = self.shape[0]
        if rows == 0 or self.nnz == 0 or rows > np.iinfo(np.int32).max:
            self._sliced_ell = False
            return None
        self._sliced_ell = _spmv_ops.sliced_ell_pack(
            self._data, self._indices, self._indptr, rows
        )
        if self._sliced_ell is None:
            self._sliced_ell = False
            return None
        return self._sliced_ell

    # ---------------- conversions ----------------
    def todense(self, order=None, out=None):
        if order is not None:
            raise NotImplementedError("order parameter is not supported")
        result = _convert.csr_to_dense(
            self._data, self._indices, self._indptr, self.shape
        )
        return fill_out(result, out)

    toarray = todense

    def tocsr(self, copy: bool = False):
        return self.copy() if copy else self

    def _coo_parts(self):
        """(row, col, data) coordinate view as jax arrays (internal —
        the public ``tocoo`` returns a ``coo_array`` like scipy)."""
        row_ids = _convert.row_ids_from_indptr(self._indptr, self.nnz)
        return row_ids.astype(self._indices.dtype), self._indices, self._data

    def tocoo(self, copy: bool = False):
        """COO-format view (scipy ``tocoo`` semantics)."""
        from .coo import coo_array

        return coo_array(self)

    def toscipy(self):
        """Interop: materialize as a scipy.sparse.csr_array on host."""
        return _scipy_sparse.csr_array(
            (
                np.asarray(self._data),
                np.asarray(self._indices),
                np.asarray(self._indptr),
            ),
            shape=self.shape,
        )

    def todia(self, copy: bool = False):
        """Convert to ``dia_array`` (scipy ``.todia()`` semantics: every
        distinct ``col - row`` becomes a stored diagonal).  Reuses the
        banded-structure machinery behind the SpMV fast path."""
        from .dia import dia_array

        a = self._canonicalized()
        rows, cols = self.shape
        if a.nnz == 0:
            # scipy parity: empty DIA (no stored diagonals).
            return dia_array(
                (jnp.zeros((0, 0), dtype=self.dtype),
                 jnp.zeros((0,), dtype=index_dtype())),
                shape=self.shape,
            )
        offsets = _dia_ops.csr_band_offsets(
            a._indices, a._get_row_ids(), max(rows + cols, 1)
        )
        dia_data = _dia_ops.dia_from_csr(
            a._data, a._indices, a._get_row_ids(), offsets, cols
        )
        return dia_array(
            (dia_data, jnp.asarray(offsets, dtype=index_dtype())),
            shape=self.shape,
        )

    def asformat(self, format, copy: bool = False):
        """Return this matrix in the given format, scipy ``asformat``
        semantics ('csr', 'csc', 'coo', 'dia')."""
        if format is None or format == "csr":
            return self.tocsr(copy=copy)
        if format == "dia":
            return self.todia(copy=copy)
        if format == "csc":
            return self.tocsc(copy=copy)
        if format == "coo":
            from .coo import coo_array

            return coo_array(self)
        raise ValueError(f"unsupported format: {format!r}")

    def tocsc(self, copy: bool = False):
        from .csc import csc_array

        return csc_array(self)

    # ---------------- structure maintenance ----------------
    def getnnz(self, axis=None):
        """nnz total, or per-row / per-column counts (scipy semantics)."""
        if axis is None:
            return self.nnz
        if axis in (1, -1):
            return jnp.diff(self._indptr)
        if axis == 0:
            return (
                jnp.zeros((self.shape[1],), dtype=nnz_dtype())
                .at[self._indices]
                .add(1)
            )
        raise ValueError(f"invalid axis: {axis}")

    def eliminate_zeros(self):
        """Drop explicit zero entries in place (scipy semantics; one
        host sync for the new nnz — the XLA static-shape analog of the
        reference's blocking ``int(nnz)``)."""
        mask = self._data != 0
        new_nnz = int(jnp.sum(mask))
        if new_nnz == self.nnz:
            return
        keep = jnp.nonzero(mask, size=new_nnz)[0]
        row_ids = _convert.row_ids_from_indptr(self._indptr, self.nnz)
        new_rows = row_ids[keep]
        self._data = self._data[keep]
        self._indices = self._indices[keep]
        self._indptr = _convert.indptr_from_row_ids(
            new_rows, self.shape[0]
        )
        canonical, sorted_ = self._canonical, self._sorted
        self._invalidate_caches(structure_changed=True)
        # Dropping entries keeps each row's order and uniqueness.
        self._canonical, self._sorted = canonical, sorted_

    def sort_indices(self):
        """Sort column indices within each row in place (stable; no
        duplicate merging — scipy ``sort_indices`` semantics)."""
        if self.has_sorted_indices:
            return
        row_ids = _convert.row_ids_from_indptr(self._indptr, self.nnz)
        _, indices, data = jax.lax.sort(
            [row_ids, self._indices, self._data], num_keys=2,
            is_stable=True,
        )
        self._data = data
        self._indices = indices
        # block_score (the fingerprint) reads stored-entry order, so
        # every structure cache goes; canonicalness is unknown again.
        self._invalidate_caches(structure_changed=True)
        self._sorted = True

    def power(self, n, dtype=None):
        """Element-wise power (scipy semantics: duplicates are summed
        first — scipy applies ``_deduped_data()`` — then each stored
        entry is raised)."""
        a = self._canonicalized()
        data = a._data
        if dtype is not None:
            data = data.astype(dtype)
        return a._with_data(data**n)

    # ---------------- element/structure ops ----------------
    def diagonal(self, k: int = 0):
        rows, cols = self.shape
        if k != 0:
            # Improvement over the reference (k=0 only, ``csr.py:345-368``):
            # any diagonal; length follows scipy convention.
            length = max(0, min(rows + min(k, 0), cols - max(k, 0)))
            full = _convert.csr_diagonal(
                self._data, self._indices, self._indptr, rows, k
            )
            start = -min(k, 0)
            return full[start : start + length]
        return _convert.csr_diagonal(
            self._data, self._indices, self._indptr, rows, 0
        )[: min(rows, cols)]

    def transpose(self, axes=None, copy: bool = False):
        if axes is not None:
            raise ValueError(
                "Sparse matrices do not support an 'axes' parameter"
            )
        rows, cols = self.shape
        data, indices, indptr = _convert.csr_transpose(
            self._data, self._indices, self._indptr, rows, cols
        )
        # Transpose of a canonical matrix is canonical; duplicates survive
        # transposition otherwise.  type(self) keeps the spmatrix flavor.
        return type(self)._from_parts(
            data, indices, indptr, (cols, rows), canonical=self._canonical
        )

    def conj(self, copy: bool = True):
        if np.issubdtype(self.dtype, np.complexfloating):
            return self._with_data(jnp.conj(self._data), copy=copy)
        return self.copy() if copy else self

    conjugate = conj

    def copy(self):
        return type(self)(self, copy=True)

    def trace(self, offset: int = 0):
        """Sum along diagonal ``offset`` (scipy ``trace``)."""
        return jnp.sum(self.diagonal(offset))

    def count_nonzero(self, axis=None):
        """Number of entries whose value is nonzero after duplicate
        merging (scipy semantics: explicit/cancelled zeros are not
        counted)."""
        a = self._canonicalized()
        nz = (a._data != 0)
        if axis is None:
            return int(jnp.sum(nz))
        if axis not in (0, 1, -1, -2):
            raise ValueError(f"invalid axis {axis}")
        axis = int(axis) % 2
        if axis == 0:
            counts = jnp.zeros(
                (a.shape[1],), jnp.int32
            ).at[a._indices].add(nz.astype(jnp.int32))
            return np.asarray(counts)
        row_ids = _convert.row_ids_from_indptr(a._indptr, a.nnz)
        return np.asarray(jax.ops.segment_sum(
            nz.astype(jnp.int32), row_ids, num_segments=a.shape[0],
            indices_are_sorted=True,
        ))

    def _minmax_binary(self, other, op):
        """Element-wise maximum/minimum vs a scalar or sparse operand
        over the union structure, implicit zeros included (scipy
        ``maximum``/``minimum`` semantics)."""
        if np.isscalar(other) or getattr(other, "ndim", None) == 0:
            # scipy materializes a dense result only for scalars that
            # beat the implicit zeros; match its sparse-where-possible
            # contract: op(v, s) at stored slots, op(0, s) elsewhere.
            # (Computed with the jnp op so complex scalars follow
            # numpy's ordering rather than crashing on float().)
            zero = jnp.zeros((), jnp.result_type(self.dtype, other))
            fill = op(zero, other)
            if bool(fill != 0):
                import warnings as _w

                _w.warn(
                    "Taking maximum/minimum with a scalar that is "
                    "nonzero against the zero fill produces a dense "
                    "result", SparseEfficiencyWarning, stacklevel=3,
                )
                dense = op(self.toarray(), other)
                return csr_array(np.asarray(dense))
            a = self._canonicalized()   # op distributes over values,
            return a._with_data(op(a._data, other))  # not duplicates
        if _is_scipy_sparse(other):
            other = csr_array(other)
        if not isinstance(other, csr_array):
            other = csr_array(jnp.asarray(other))
        if other.shape != self.shape:
            raise ValueError("inconsistent shapes")
        a, b = cast_to_common_type(self._canonicalized(),
                                   other._canonicalized())
        rows, cols = a.shape
        ra, ca, va = a._coo_parts()
        rb, cb, vb = b._coo_parts()
        # Union structure: where a key appears on one side only, the
        # other side contributes its implicit zero.
        row = jnp.concatenate([ra, rb])
        col = jnp.concatenate([ca, cb])
        try:
            # Union keys are row*cols+col: the key space is rows*cols,
            # not max(shape) — coord_dtype_for raises under no-x64.
            key_dt = coord_dtype_for(rows * cols)
        except OverflowError as e:
            raise OverflowError(
                f"maximum/minimum union keys: {e}"
            ) from None
        key = row.astype(key_dt) * cols + col.astype(key_dt)
        val = jnp.concatenate([va, vb])
        order = jnp.argsort(key, stable=True)
        key = key[order]
        val = val[order]
        nxt = jnp.concatenate([key[1:], jnp.full((1,), -1, key.dtype)])
        prv = jnp.concatenate([jnp.full((1,), -1, key.dtype), key[:-1]])
        paired = jnp.logical_or(key == nxt, key == prv)
        pair_val = jnp.where(
            key == nxt, op(val, jnp.roll(val, -1)), jnp.zeros_like(val)
        )
        single_val = op(val, jnp.zeros_like(val))
        out_val = jnp.where(
            paired,
            jnp.where(key == nxt, pair_val, jnp.zeros_like(val)),
            single_val,
        )
        out = csr_array(
            (out_val, (row[order], col[order])), shape=self.shape
        )
        out.sum_duplicates()   # merges the zeroed pair slot
        out.eliminate_zeros()
        return out

    def maximum(self, other):
        return self._minmax_binary(other, jnp.maximum)

    def minimum(self, other):
        return self._minmax_binary(other, jnp.minimum)

    def argmax(self, axis=None, out=None):
        """Index of the maximum element, implicit zeros included (host
        delegation — exact scipy tie-breaking; not a hot op)."""
        return self.toscipy().argmax(axis=axis, out=out)

    def argmin(self, axis=None, out=None):
        return self.toscipy().argmin(axis=axis, out=out)

    def reshape(self, *shape, order="C"):
        """Reshape preserving entry count (host structural op).  Only
        2-D targets: this package has no 1-D sparse type (scipy's
        sparray returns 1-D for a single-int shape)."""
        if len(shape) == 1:
            if isinstance(shape[0], (int, np.integer)):
                raise ValueError(
                    "1-D reshape targets are not supported (no 1-D "
                    "sparse type); pass a 2-D shape"
                )
            shape = tuple(shape[0])
        if len(shape) != 2:
            raise ValueError(f"expected a 2-D shape, got {shape}")
        return csr_array(
            self.toscipy().reshape(shape, order=order).tocsr()
        )

    def resize(self, *shape):
        """In-place resize: entries outside the new shape are dropped
        (scipy ``resize``)."""
        if len(shape) == 1:
            shape = tuple(shape[0])
        nr, nc = (int(shape[0]), int(shape[1]))
        r, c, v = self._coo_parts()
        keep = jnp.logical_and(r < nr, c < nc)
        nnz_new = int(jnp.sum(keep))
        r2, c2, v2 = _convert.compact_mask(keep, (r, c, v), nnz_new)
        new = csr_array((v2, (r2, c2)), shape=(nr, nc))
        self._data = new._data
        self._indices = new._indices
        self._indptr = new._indptr
        self.shape = (nr, nc)
        self._invalidate_caches(structure_changed=True)

    def todok(self, copy: bool = False):
        """Host conversion (no native DOK type — scipy's is returned)."""
        return self.toscipy().todok(copy=copy)

    def tolil(self, copy: bool = False):
        """Host conversion (no native LIL type — scipy's is returned)."""
        return self.toscipy().tolil(copy=copy)

    # ---------------- arithmetic ----------------
    def multiply(self, other):
        """Element-wise product with a scalar, dense array/vector, or any
        sparse operand (pattern intersection)."""
        if np.isscalar(other) or getattr(other, "ndim", None) == 0:
            return self._with_data(self._data * other)
        if _is_scipy_sparse(other):
            other = csr_array(other)
        elif not isinstance(other, csr_array) and _is_sparse_like(other):
            other = other.tocsr()   # csc/coo/dia operand
        if isinstance(other, csr_array):
            if other.shape != self.shape:
                raise ValueError("inconsistent shapes for multiply")
            a, b = cast_to_common_type(
                self._canonicalized(), other._canonicalized()
            )
            return _elementwise_intersect_multiply(a, b)
        other = jnp.asarray(other)
        if other.ndim == 2 and other.shape == self.shape:
            row_ids = _convert.row_ids_from_indptr(self._indptr, self.nnz)
            return self._with_data(self._data * other[row_ids, self._indices])
        if other.ndim == 1 and other.shape[0] == self.shape[1]:
            return self._with_data(self._data * other[self._indices])
        # scipy broadcasting: a (1, n) row or (m, 1) column vector
        # scales columns / rows without densifying.
        if other.ndim == 2 and other.shape == (1, self.shape[1]):
            return self._with_data(self._data * other[0, self._indices])
        if other.ndim == 2 and other.shape == (self.shape[0], 1):
            row_ids = _convert.row_ids_from_indptr(self._indptr, self.nnz)
            return self._with_data(self._data * other[row_ids, 0])
        raise ValueError(f"inconsistent shapes for multiply: {other.shape}")

    def __mul__(self, other):
        if np.isscalar(other) or getattr(other, "ndim", None) == 0:
            return self._with_data(self._data * other)
        # sparray semantics: ``*`` is element-wise (scipy's csr_array;
        # the spmatrix subclass below overrides to matmul).
        return self.multiply(other)

    def __rmul__(self, other):
        return self.__mul__(other)

    def __truediv__(self, other):
        if np.isscalar(other) or getattr(other, "ndim", None) == 0:
            return self._with_data(self._data / other)
        if _is_scipy_sparse(other) or _is_sparse_like(other):
            if tuple(other.shape) != self.shape:
                raise ValueError(
                    f"inconsistent shapes {self.shape} and "
                    f"{tuple(other.shape)}"
                )
            # scipy: sparse / sparse densifies (0/0 -> nan included).
            other = other.toarray() if hasattr(other, "toarray") else other
            return jnp.asarray(self.toarray()) / jnp.asarray(other)
        # Dense divisor: division applies at stored entries only
        # (implicit zeros stay zero — scipy returns sparse here too).
        # Row/column-vector divisors broadcast like scipy.
        recip = 1.0 / jnp.asarray(other)
        if recip.ndim == 2 and recip.shape != self.shape:
            recip = jnp.broadcast_to(recip, self.shape)
        return self.multiply(recip)

    def __neg__(self):
        return self._with_data(-self._data)

    def __abs__(self):
        return self._with_data(jnp.abs(self._data))

    def __pow__(self, n):
        if np.isscalar(n) and n == 0:
            raise NotImplementedError(
                "zero power is not supported as it would densify the "
                "matrix; use np.ones(A.shape, dtype=A.dtype)"
            )
        return self.power(n)

    # -- element-wise comparisons (scipy semantics: a bool sparse array
    #    storing the True positions).  Whether the True set is
    #    dense-shaped depends on the implicit-zero pair: op(0, fill) —
    #    those cases warn (like scipy) and materialize; the
    #    sparse-shaped cases stay sparse end to end. --
    def _compare(self, other, op):
        cls = type(self)
        scalar = np.isscalar(other) or getattr(other, "ndim", None) == 0
        sparse_other = _is_scipy_sparse(other) or _is_sparse_like(other)
        if sparse_other and tuple(other.shape) != self.shape:
            raise ValueError("inconsistent shapes")
        if not scalar and not sparse_other:
            # Dense operand: scipy returns a dense bool ndarray.
            return np.asarray(
                op(np.asarray(self.toarray()), np.asarray(other))
            )
        # Implicit-zero pair (full scalar value — complex included).
        fill_true = bool(np.asarray(op(0, other if scalar else 0)))
        if fill_true:
            warnings.warn(
                "Comparing a sparse array using a comparison that is "
                "True for implicit zeros is inefficient "
                "(dense-shaped result)",
                SparseEfficiencyWarning, stacklevel=3,
            )
        if scalar:
            if fill_true:
                res = op(np.asarray(self.toarray()), other)
                return cls(np.asarray(res))
            a = self._canonicalized()
            out = cls(a._with_data(op(a._data, other)))
            out.eliminate_zeros()
            return out
        if fill_true:
            res = op(np.asarray(self.toarray()),
                     np.asarray(other.toarray()))
            return cls(np.asarray(res))
        return self._compare_sparse_union(other, op)

    def _compare_sparse_union(self, other, op):
        """op over the union structure of two sparse operands (used for
        the sparse-result comparisons: no dense materialization).  Two-
        key sort — no fused integer key, safe for any rows*cols under
        x64-off (same pattern as ``_elementwise_intersect_multiply``)."""
        if not isinstance(other, csr_array):
            other = csr_array(other) if _is_scipy_sparse(other) \
                else other.tocsr()
        a, b = (self._canonicalized(), other._canonicalized())
        rows, cols = a.shape
        ra, ca, va = a._coo_parts()
        rb, cb, vb = b._coo_parts()
        row = jnp.concatenate([ra, rb])
        col = jnp.concatenate([ca, cb])
        cha = jnp.concatenate([va, jnp.zeros_like(vb)])
        chb = jnp.concatenate([jnp.zeros_like(va), vb])
        row, col, cha, chb = jax.lax.sort(
            [row, col, cha, chb], num_keys=2, is_stable=True
        )
        same_next = jnp.concatenate([
            jnp.logical_and(row[1:] == row[:-1], col[1:] == col[:-1]),
            jnp.zeros((1,), bool),
        ])
        same_prev = jnp.concatenate([
            jnp.zeros((1,), bool),
            jnp.logical_and(row[1:] == row[:-1], col[1:] == col[:-1]),
        ])
        first = jnp.logical_not(same_prev)
        # Merge pair channels onto the first slot of each key group.
        va_m = cha + jnp.where(same_next, jnp.roll(cha, -1), 0)
        vb_m = chb + jnp.where(same_next, jnp.roll(chb, -1), 0)
        res = jnp.logical_and(first, op(va_m, vb_m))
        out = type(self)((res, (row, col)), shape=self.shape)
        out.eliminate_zeros()
        return out

    def __eq__(self, other):
        return self._compare(other, jnp.equal)

    def __ne__(self, other):
        return self._compare(other, jnp.not_equal)

    def __lt__(self, other):
        return self._compare(other, jnp.less)

    def __gt__(self, other):
        return self._compare(other, jnp.greater)

    def __le__(self, other):
        return self._compare(other, jnp.less_equal)

    def __ge__(self, other):
        return self._compare(other, jnp.greater_equal)

    # Defining __eq__ clears the default hash; sparse arrays are
    # mutable and unhashable, same as scipy's.
    __hash__ = None

    def nonzero(self):
        """(row, col) of nonzero entries (scipy ``nonzero``)."""
        from .gallery import find as _find

        r, c, _v = _find(self)
        return r, c

    def _add_sub(self, other, sign):
        if not isinstance(other, csr_array):
            if np.isscalar(other) and other == 0:
                return self.copy()   # sum()/accumulate start at 0
            if _is_scipy_sparse(other):
                other = csr_array(other)
            elif _is_sparse_like(other):
                other = other.tocsr()   # csc/coo/dia operand
            else:
                raise NotImplementedError(
                    "sparse +/- dense is not supported; densify explicitly"
                )
        if other.shape != self.shape:
            raise ValueError("inconsistent shapes")
        a, b = cast_to_common_type(self, other)
        rows, cols = self.shape
        ra, ca, va = a._coo_parts()
        rb, cb, vb = b._coo_parts()
        row = jnp.concatenate([ra, rb])
        col = jnp.concatenate([ca, cb])
        val = jnp.concatenate([va, sign * vb])
        # Merge duplicates through the shared coalesce machinery.
        data, indices, indptr = _spgemm_ops.coalesce_coo(row, col, val, rows)
        return type(self)._from_parts(data, indices, indptr, self.shape)

    def __add__(self, other):
        return self._add_sub(other, 1)

    def __sub__(self, other):
        return self._add_sub(other, -1)

    # ---------------- matmul ----------------
    def __rmatmul__(self, other):
        raise NotImplementedError("dense @ csr is not yet supported")

    def __matmul__(self, other):
        return self.dot(other)

    def dot(self, other, out=None):
        """SpMV / SpMM / SpGEMM dispatch (reference ``csr.py:419-493``).

        With resilience on (``LEGATE_SPARSE_TPU_RESIL``,
        docs/RESILIENCE.md) the dispatch runs under the ``csr.dot``
        site policy: injectable via ``resilience.faults``, transient
        failures retried with deterministic backoff, K consecutive
        failures tripping the site breaker (typed fast-fail while
        open).  Off — the default — this is one flag read."""
        if _rsettings.resil and self._can_build_cache(self._data):
            # Eager contexts only: inside an ambient jax trace the
            # wrapper must vanish (a retry there would re-stage the
            # traced program, and injection is trace-suppressed
            # anyway).
            def attempt():
                # The fault hook wraps the VALUE so an armed
                # ``nonfinite`` fault can poison the product; error/
                # latency kinds fire before results are returned.
                return _rfaults.fault_point(
                    "csr.dot", self._dot_impl(other, out=out))

            return _rpolicy.run("csr.dot", attempt)
        return self._dot_impl(other, out=out)

    def _dot_impl(self, other, out=None):
        require_supported_dtype(self.dtype)
        if _is_scipy_sparse(other):
            other = csr_array(other)  # adopt scipy operand for SpGEMM
        elif not isinstance(other, csr_array) and _is_sparse_like(other):
            other = other.tocsr()  # csc/dia operand -> CSR SpGEMM
        if isinstance(other, csr_array):
            if out is not None:
                raise ValueError("out not supported for sparse-sparse matmul")
            return spgemm_csr_csr_csr(*cast_to_common_type(self, other))
        other_arr = jnp.asarray(other)
        squeeze = False
        if other_arr.ndim == 2 and other_arr.shape[1] == 1:
            # (N, 1) treated as a vector (reference ``csr.py:433-452``).
            other_arr = other_arr.reshape(-1)
            squeeze = True
        if other_arr.ndim == 1:
            if other_arr.shape[0] != self.shape[1]:
                raise ValueError(
                    f"dimension mismatch: {self.shape} @ {other_arr.shape}"
                )
            _obs.inc("op.spmv")
            # Low-precision-storage widening (bf16/f16 matrix, f32
            # operand): keep the compressed operand — the generic cast
            # below would materialize an f32 copy of the value stream,
            # undoing the whole byte win — and dispatch the
            # f32-accumulation kernels, whose output is
            # result_type(A, x) exactly as promotion demands.
            lowp = (str(self.dtype) in ("bfloat16", "float16")
                    and np.result_type(self.dtype, other_arr.dtype)
                    == np.float32
                    and other_arr.dtype != self.dtype)
            if lowp:
                A, x = self, other_arr
                src = self
            else:
                A, x = cast_to_common_type(self, other_arr)
                src = self if A is self else None
            # Always-on dispatch-latency histogram, keyed by the pow2
            # shape bucket (obs/latency.py): the distribution the
            # autotuner/serving arc consult — spans only exist while
            # tracing is enabled.
            with _lat.timer("lat.spmv."
                            + _lat.shape_bucket(self.shape[0])), \
                    _obs.span("spmv") as sp:
                if src is not None:
                    # Engine route (settings.engine): bucketed plan
                    # dispatch with zero retraces under n/nnz drift.
                    # Declines (off, tracer context, structure fast
                    # path) fall through to the normal chain.
                    y = _engine_route_matvec(src, x)
                    if y is not None:
                        if sp is not None:
                            # Traffic model: the engine kernel is the
                            # CSR gather path over padded operands.
                            sp.set(path="engine", rows=self.shape[0],
                                   nnz=self.nnz, flops=2 * self.nnz,
                                   bytes=A.spmv_traffic_bytes(
                                       x, path="csr"))
                        if squeeze:
                            y = y[:, None]
                        return fill_out(y, out)
                if src is not None:
                    # Autotune route (settings.autotune): a stored
                    # measured verdict picks the kernel.  Declines
                    # (off — the default, tracer context, dtype
                    # promotion, DIA/BSR structure, verdict miss)
                    # fall through to the heuristic chain below.
                    routed = _autotune_route_matvec(src, x)
                    if routed is not None:
                        y, path = routed
                        if sp is not None:
                            sp.set(path=path, rows=self.shape[0],
                                   nnz=self.nnz, flops=2 * self.nnz,
                                   bytes=A.spmv_traffic_bytes(
                                       x, path=path))
                        if squeeze:
                            y = y[:, None]
                        return fill_out(y, out)
                # Under the declared widening DIA keeps serving: its
                # XLA lowerings are shifted multiply-adds whose bf16 x
                # f32 products promote to f32 before the reduction —
                # f32-grade accumulation for free, band bytes halved.
                # BSR stands down (the Mosaic kernel is compiled
                # same-dtype); the gather-class f32acc kernels cover
                # the rest.
                dia = (src._get_dia(with_pack=not lowp)
                       if src is not None else None)
                bsr = (src._get_bsr()
                       if src is not None and not lowp and dia is None
                       else None)
                ell = (src._get_ell()
                       if src is not None and dia is None and bsr is None
                       else None)
                if dia is not None:
                    from .ops.pallas_dia import (
                        dia_spmv_maybe_pallas, pallas_dia_active,
                    )

                    y = (dia_spmv_maybe_pallas(src._get_dia_pack(), x)
                         if pallas_dia_active() and not lowp else None)
                    path = "dia-pallas"
                    if y is None:
                        offs = dia[1]
                        if _dia_xla_nopad():
                            y = _dia_ops.dia_spmv_nopad(
                                dia[0], dia[2], x, offs, self.shape)
                            path = "dia-xla-nopad"
                        else:
                            dpad, mpad = src._get_dia_fused()
                            y = _dia_ops.dia_spmv_fused(
                                dpad, mpad, x, offs, self.shape)
                            path = "dia-xla"
                elif bsr is not None:
                    y = bsr.matvec(
                        x, interpret=jax.devices()[0].platform != "tpu"
                    )
                    path = "bsr"
                elif ell is not None and lowp:
                    y = _spmv_ops.ell_spmv_f32acc(
                        ell[0], ell[1], ell[2], x)
                    path = "ell-bf16"
                elif ell is not None:
                    y = _spmv_ops.ell_spmv(ell[0], ell[1], ell[2], x)
                    path = "ell"
                elif src is not None and lowp:
                    y = _spmv_ops.csr_spmv_rowids_f32acc(
                        A.data, A.indices, src._get_row_ids(), x,
                        self.shape[0]
                    )
                    path = "csr-rowids-bf16"
                elif src is not None:
                    y = _spmv_ops.csr_spmv_rowids(
                        A.data, A.indices, src._get_row_ids(), x,
                        self.shape[0]
                    )
                    path = "csr-rowids"
                else:
                    y = _spmv_ops.csr_spmv(
                        A.data, A.indices, A.indptr, x, self.shape[0]
                    )
                    path = "csr"
                if sp is not None:
                    nnz = self.nnz
                    sp.set(path=path, rows=self.shape[0], nnz=nnz,
                           bytes=A.spmv_traffic_bytes(x, path=path),
                           flops=2 * nnz)
            if squeeze:
                y = y[:, None]
            return fill_out(y, out)
        if other_arr.ndim == 2:
            if other_arr.shape[0] != self.shape[1]:
                raise ValueError(
                    f"dimension mismatch: {self.shape} @ {other_arr.shape}"
                )
            _obs.inc("op.spmm")
            # Same declared widening as the SpMV branch: compressed
            # storage stays compressed, f32 accumulation serves.
            lowp = (str(self.dtype) in ("bfloat16", "float16")
                    and np.result_type(self.dtype, other_arr.dtype)
                    == np.float32
                    and other_arr.dtype != self.dtype)
            if lowp:
                A, X = self, other_arr
                src = self
            else:
                A, X = cast_to_common_type(self, other_arr)
                src = self if A is self else None
            with _lat.timer("lat.spmm."
                            + _lat.shape_bucket(self.shape[0])), \
                    _obs.span("spmm") as sp:
                if src is not None:
                    Y = _engine_route_matmat(src, X)
                    if Y is not None:
                        if sp is not None:
                            k = int(X.shape[1])
                            sp.set(path="engine", rows=self.shape[0],
                                   k=k, nnz=self.nnz,
                                   flops=2 * self.nnz * k,
                                   bytes=A.spmv_traffic_bytes(
                                       X, path="csr"))
                        return fill_out(Y, out)
                if src is not None:
                    routed = _autotune_route_matmat(src, X)
                    if routed is not None:
                        Y, path = routed
                        if sp is not None:
                            k = int(X.shape[1])
                            sp.set(path=path, rows=self.shape[0],
                                   k=k, nnz=self.nnz,
                                   flops=2 * self.nnz * k,
                                   bytes=A.spmv_traffic_bytes(
                                       X, path=path))
                        return fill_out(Y, out)
                # DIA serves under the widening (same promotion logic
                # as the SpMV branch); BSR/flat-ELL stand down — no
                # f32-accumulation spmm variants for those families.
                dia = src._get_dia() if src is not None else None
                from .ops.bsr import SPMM_MAX_K as _BSR_MAX_K

                bsr = (src._get_bsr()
                       if src is not None and not lowp and dia is None
                       and 0 < X.shape[1] <= _BSR_MAX_K
                       else None)
                ell = (src._get_ell()
                       if src is not None and not lowp
                       and dia is None and bsr is None
                       else None)
                if dia is not None:
                    from .ops.pallas_dia import (
                        SPMM_MAX_K, dia_spmm_maybe_pallas,
                        pallas_dia_active,
                    )

                    # Cheap k gate first: the pack build doubles band
                    # storage and must not run for calls that can only
                    # take the XLA path anyway.
                    Y = (
                        dia_spmm_maybe_pallas(src._get_dia_pack(), X)
                        if 0 < X.shape[1] <= SPMM_MAX_K
                        and pallas_dia_active() and not lowp
                        else None
                    )
                    path = "dia-pallas"
                    if Y is None:
                        offs = dia[1]
                        dpad, mpad = src._get_dia_fused()
                        Y = _dia_ops.dia_spmm_fused(dpad, mpad, X, offs,
                                                    self.shape)
                        path = "dia-xla"
                elif bsr is not None:
                    Y = bsr.matmat(
                        X, interpret=jax.devices()[0].platform != "tpu"
                    )
                    path = "bsr"
                elif ell is not None:
                    Y = _spmv_ops.ell_spmm(ell[0], ell[1], ell[2], X)
                    path = "ell"
                elif src is not None and lowp:
                    Y = _spmv_ops.csr_spmm_rowids_f32acc(
                        A.data, A.indices, src._get_row_ids(), X,
                        self.shape[0]
                    )
                    path = "csr-rowids-bf16"
                elif src is not None:
                    Y = _spmv_ops.csr_spmm_rowids(
                        A.data, A.indices, src._get_row_ids(), X,
                        self.shape[0]
                    )
                    path = "csr-rowids"
                else:
                    Y = _spmv_ops.csr_spmm(
                        A.data, A.indices, A.indptr, X, self.shape[0]
                    )
                    path = "csr"
                if sp is not None:
                    k = int(X.shape[1])
                    sp.set(path=path, rows=self.shape[0], k=k,
                           nnz=self.nnz, flops=2 * self.nnz * k,
                           bytes=A.spmv_traffic_bytes(X, path=path))
            return fill_out(Y, out)
        raise ValueError(f"cannot multiply csr_array by ndim={other_arr.ndim}")

    def spmv_traffic_bytes(self, x, path: str = None) -> int:
        """Useful-traffic byte model of one ``A @ x`` through the
        kernel named by ``path`` (the dispatch labels: dia-*, bsr,
        ell, csr*) — or, with ``path=None``, whatever kernel the
        structure caches say the dispatch WOULD pick (bench.py's
        usage).  Lower bound: x counted once even where a kernel
        re-reads neighbor windows.  Reads the already-built structure
        caches only — call after the op (``bench.py`` and the obs
        spans both do); an uncached matrix falls through to the CSR
        gather model.
        """
        if path is None:
            return self._traffic_model(x, None)
        # Once per (path, operand) pair: the live ``spmv`` span asks on
        # every call, and the structure caches the model reads change
        # only where ``_traffic`` is reset with them.
        key = (path, x.shape, x.dtype)
        got = self._traffic.get(key)
        if got is None:
            got = self._traffic[key] = self._traffic_model(x, path)
        return got

    def _traffic_model(self, x, path) -> int:
        n = self.shape[0]
        if path in ("csr-rowids-bf16", "ell-bf16", "sliced-ell-bf16"):
            # The f32-accumulation variants stream the same blocks as
            # their full-precision families — the models below read
            # the actual storage itemsizes, so the narrowing is
            # already priced.
            path = path[: -len("-bf16")]
        x_bytes = int(x.size) * x.dtype.itemsize
        out_bytes = n * _result_itemsize(self.dtype, np.dtype(x.dtype),
                                         jax.config.jax_enable_x64)
        if x.ndim == 2:
            out_bytes *= int(x.shape[1])
        # Caches use the False sentinel for "tried, not applicable".
        dia = self._dia if self._dia is not False else None
        if path is not None and not path.startswith("dia"):
            dia = None
        if path == "bsr" and self._bsr not in (None, False):
            # Present blocks stream densified through the MXU.
            return int(
                self._bsr.nblocks * 128 * 128 * self.dtype.itemsize
                + x_bytes + out_bytes
            )
        if dia is not None:
            dia_data, _offsets, mask = dia
            pallas = (path == "dia-pallas" if path is not None
                      else self._dia_pack not in (None, False))
            mask_bytes = 0
            if mask is not None and not pallas:
                # The XLA lowerings stream the bool hole mask (1
                # byte/slot); the Pallas pack carries the holes in the
                # sign of zero of its values.
                mask_bytes = mask.size
            return int(dia_data.size * dia_data.dtype.itemsize
                       + mask_bytes + x_bytes + out_bytes)
        if path == "sliced-ell" and self._sliced_ell not in (None, False):
            # Each pow2 row bin streams its (rows_b, W_b) data+cols
            # blocks plus the count/row-index sideband.
            total = x_bytes + out_bytes
            for ell_data, ell_cols, cnt, row_idx in self._sliced_ell:
                total += (ell_data.size * ell_data.dtype.itemsize
                          + ell_cols.size * ell_cols.dtype.itemsize
                          + cnt.size * cnt.dtype.itemsize
                          + row_idx.size * row_idx.dtype.itemsize)
            return int(total)
        ell = self._ell if self._ell is not False else None
        if path is not None and path != "ell":
            ell = None
        if ell is not None:
            ell_data, ell_cols, ell_counts = ell
            return int(
                ell_data.size * ell_data.dtype.itemsize
                + ell_cols.size * ell_cols.dtype.itemsize
                + ell_counts.size * ell_counts.dtype.itemsize
                + x_bytes + out_bytes
            )
        nnz = self.nnz
        rid_bytes = (self._row_ids.size * self._row_ids.dtype.itemsize
                     if self._row_ids is not None
                     else nnz * np.dtype(np.int32).itemsize)
        return int(
            nnz * (self.data.dtype.itemsize + self.indices.dtype.itemsize)
            + rid_bytes + x_bytes + out_bytes
        )

    def _invalidate_caches(self, structure_changed: bool) -> None:
        """Drop stale structure caches after in-place mutation.  With
        ``structure_changed`` False only value-derived caches reset
        (sparsity pattern intact)."""
        self._ell = None
        self._dia = None
        self._dia_pack = None
        self._dia_fused = None
        self._bsr = None
        self._engine_pack = None
        self._sliced_ell = None  # packs values, not just structure
        self._traffic = {}
        self._graph_ops = None  # the operators hold the weights too
        if structure_changed:
            self._row_ids = None
            self._ell_width = None
            self._dia_offsets = None
            self._fingerprint = None
            self._canonical = None
            self._sorted = None

    def setdiag(self, values, k: int = 0) -> None:
        """Set diagonal ``k`` in place (scipy ``setdiag``): existing
        stored entries are overwritten on device; rows whose diagonal
        slot has no stored entry get structure inserted (one COO
        rebuild — the scipy 'changing the sparsity structure' case)."""
        import numpy as _np

        rows, cols = self.shape
        if k <= -rows or k >= cols:
            raise ValueError("k exceeds matrix dimensions")
        length = min(rows + min(k, 0), cols - max(k, 0))
        vals = jnp.asarray(values, dtype=self.dtype)
        if vals.ndim == 0:
            vals = jnp.full((length,), vals)
        length = min(length, int(vals.shape[0]))
        vals = vals[:length]
        if length <= 0:
            # Zero-length values: scipy's setdiag silently no-ops.
            return
        if self.nnz and not self.has_canonical_format:
            self.sum_duplicates()

        i0 = max(0, -k)
        row_ids = self._get_row_ids()
        on_diag = jnp.logical_and(
            self._indices.astype(index_dtype())
            - row_ids.astype(index_dtype()) == k,
            row_ids < i0 + length,
        )
        # Overwrite stored diagonal entries.
        safe_rel = jnp.clip(row_ids.astype(index_dtype()) - i0, 0, length - 1)
        new_data = jnp.where(on_diag, vals[safe_rel], self._data)

        # Rows in [i0, i0+length) missing a stored diagonal slot.
        has = _np.zeros(length, dtype=bool)
        hit_rows = _np.asarray(row_ids)[_np.asarray(on_diag)]
        has[hit_rows - i0] = True
        missing = _np.nonzero(~has)[0]
        if missing.size == 0:
            self._data = new_data
            self._invalidate_caches(structure_changed=False)
            return
        cdt = coord_dtype_for(max(self.shape))
        add_rows = jnp.asarray(missing + i0, dtype=cdt)
        add_cols = jnp.asarray(missing + i0 + k, dtype=cdt)
        add_vals = vals[jnp.asarray(missing)]
        r, c, _ = self._coo_parts()
        self._data, self._indices, self._indptr = _convert.coo_to_csr(
            jnp.concatenate([r.astype(cdt), add_rows]),
            jnp.concatenate([c.astype(cdt), add_cols]),
            jnp.concatenate([new_data, add_vals]),
            rows,
        )
        self._invalidate_caches(structure_changed=True)

    # ---------------- indexing ----------------
    def _pointwise_get(self, rows_idx, cols_pt):
        """Vectorized A[rows, cols] pointwise gather: three host
        transfers total, then numpy searchsorted per pair (duplicates
        summed, matching element access)."""
        import numpy as _np

        n_rows, n_cols = self.shape
        out_shape = rows_idx.shape
        rows_idx = _np.where(rows_idx < 0, rows_idx + n_rows,
                             rows_idx).ravel()
        cols_pt = _np.where(cols_pt < 0, cols_pt + n_cols,
                            cols_pt).ravel()
        if rows_idx.size and (
            rows_idx.min() < 0 or rows_idx.max() >= n_rows
            or cols_pt.min() < 0 or cols_pt.max() >= n_cols
        ):
            raise IndexError("pointwise index out of range")
        indptr = _np.asarray(self._indptr)
        indices = _np.asarray(self._indices)
        data = _np.asarray(self._data)
        if rows_idx.shape[0] <= 64:
            # Small queries: per-row probes bounded by the row length —
            # the global key build below is O(nnz) and would make a
            # single A[i, j] scan the whole matrix.
            out = _np.zeros(rows_idx.shape[0], dtype=self.dtype)
            sorted_rows = bool(self.has_sorted_indices)
            for t, (i, j) in enumerate(zip(rows_idx, cols_pt)):
                lo, hi = int(indptr[i]), int(indptr[i + 1])
                seg = indices[lo:hi]
                if sorted_rows:
                    a = _np.searchsorted(seg, j, "left")
                    b = _np.searchsorted(seg, j, "right")
                    out[t] = data[lo + a: lo + b].sum()
                else:
                    out[t] = data[lo:hi][seg == j].sum()
            return out.reshape(out_shape)
        # Batched queries: one global binary search instead of a Python
        # loop per element — nnz keyed by row*ncols+col is globally
        # sorted once, then every (i, j) is two vectorized probes.
        row_ids = _np.repeat(
            _np.arange(n_rows, dtype=_np.int64), _np.diff(indptr)
        )
        key = row_ids * _np.int64(n_cols) + indices.astype(_np.int64)
        if not self.has_sorted_indices:
            order = _np.argsort(key, kind="stable")
            key = key[order]
            data = data[order]
        q = (rows_idx.astype(_np.int64) * _np.int64(n_cols)
             + cols_pt.astype(_np.int64))
        a = _np.searchsorted(key, q, "left")
        b = _np.searchsorted(key, q, "right")
        out = _np.zeros(q.shape[0], dtype=self.dtype)
        single = (b - a) == 1
        out[single] = data[a[single]]
        # Duplicate groups (non-canonical matrices only) sum exactly.
        for t in _np.nonzero(b - a > 1)[0]:
            out[t] = data[a[t]: b[t]].sum()
        return out.reshape(out_shape)

    def _select_rows(self, rows_idx) -> "csr_array":
        import numpy as _np

        rows_idx = _np.asarray(rows_idx, dtype=_np.int64)
        if rows_idx.ndim != 1:
            raise IndexError("row index arrays must be 1-D")
        n_rows = self.shape[0]
        if rows_idx.size and (
            rows_idx.min() < -n_rows or rows_idx.max() >= n_rows
        ):
            raise IndexError("row index out of range")
        rows_idx = _np.where(rows_idx < 0, rows_idx + n_rows, rows_idx)
        idx_d = jnp.asarray(rows_idx)
        counts = _np.asarray(
            self._indptr[idx_d + 1] - self._indptr[idx_d]
        )
        nnz_out = int(counts.sum())
        data, indices, indptr = _convert.select_rows(
            self._data, self._indices, self._indptr,
            jnp.asarray(rows_idx), nnz_out,
        )
        return csr_array._from_parts(
            data, indices, indptr, (len(rows_idx), self.shape[1]),
            canonical=self._canonical,
        )

    @staticmethod
    def _checked_index(i: int, extent: int, axis: str) -> int:
        if not -extent <= i < extent:
            raise IndexError(
                f"{axis} index {i} out of range for extent {extent}"
            )
        return i + extent if i < 0 else i

    @staticmethod
    def _bool_mask_to_idx(mask, extent: int, axis: str):
        import numpy as _np

        if mask.shape[0] != extent:
            raise IndexError(
                f"boolean {axis} mask length {mask.shape[0]} != {extent}"
            )
        return _np.nonzero(mask)[0]

    def __getitem__(self, key):
        """Row selection / element access (the scipy subset users hit
        in practice; the reference supports no indexing at all):

        - ``A[i]`` / ``A[i, :]`` -> (1, cols) csr row.  DEVIATION:
          scipy's ``csr_array`` (sparray) returns a 1-D result here;
          this package has no 1-D sparse type, so row access is always
          2-D (scipy's ``csr_matrix`` semantics).  Shape-sensitive
          callers should ``.toarray().ravel()``.
        - ``A[i, j]`` -> scalar (sum of duplicates at that coordinate)
        - ``A[i0:i1:step]`` / ``A[row_index_array]`` -> csr row subset
        - ``A[:, j0:j1]`` / ``A[rows, :]`` etc. via one row pass + a
          column mask compaction.
        """
        import numpy as _np

        col_key = None
        if isinstance(key, tuple):
            if len(key) != 2:
                raise IndexError("too many indices for 2-D sparse array")
            key, col_key = key

        # Element access A[i, j].
        if (col_key is not None
                and isinstance(key, (int, _np.integer))
                and isinstance(col_key, (int, _np.integer))):
            i = self._checked_index(int(key), self.shape[0], "row")
            j = self._checked_index(int(col_key), self.shape[1], "column")
            lo = int(self._indptr[i])
            hi = int(self._indptr[i + 1])
            seg = _np.asarray(self._indices[lo:hi])
            vals = _np.asarray(self._data[lo:hi])
            return self.dtype.type(vals[seg == j].sum())

        # Normalize the row key to an index array (or full slice).
        if isinstance(key, slice):
            rows_idx = _np.arange(*key.indices(self.shape[0]))
            full_rows = (key == slice(None))
        elif isinstance(key, (int, _np.integer)):
            rows_idx = _np.asarray([int(key)])
            full_rows = False
        else:
            rows_idx = _np.asarray(key)
            if rows_idx.dtype == bool:
                rows_idx = self._bool_mask_to_idx(
                    rows_idx, self.shape[0], "row"
                )
            full_rows = False
            # numpy/scipy pointwise semantics: two index ARRAYS pick
            # individual elements, not the outer-product submatrix.
            if (col_key is not None
                    and not isinstance(col_key, (slice, int, _np.integer))):
                cols_pt = _np.asarray(col_key)
                if cols_pt.dtype == bool:
                    cols_pt = self._bool_mask_to_idx(
                        cols_pt, self.shape[1], "column"
                    )
                if rows_idx.shape != cols_pt.shape:
                    raise IndexError(
                        "pointwise row/column index arrays must have "
                        "the same shape"
                    )
                return self._pointwise_get(rows_idx, cols_pt)

        # Full row slice: hand out an independent wrapper (buffers are
        # immutable jax arrays, so sharing them is safe; in-place
        # mutators replace per-instance references) — scipy's A[:]
        # copy semantics without the copy.
        out = (self._with_data(self._data) if full_rows
               else self._select_rows(rows_idx))

        if col_key is None or (isinstance(col_key, slice)
                               and col_key == slice(None)):
            return out

        # Column restriction.  Integer/bool arrays may carry duplicates
        # or arbitrary order, which a position remap cannot express —
        # go through the transpose and reuse row selection (duplicate-
        # capable).  Slices keep the cheaper mask + compact + rebase.
        if not isinstance(col_key, (slice, int, _np.integer)):
            cols_sel = _np.asarray(col_key)
            if cols_sel.dtype == bool:
                cols_sel = self._bool_mask_to_idx(
                    cols_sel, self.shape[1], "column"
                )
            return out.transpose()._select_rows(cols_sel).transpose()
        if isinstance(col_key, slice):
            start, stop, step = col_key.indices(self.shape[1])
            cols_sel = _np.arange(start, stop, step)
        else:
            cols_sel = _np.asarray([
                self._checked_index(int(col_key), self.shape[1], "column")
            ])
        remap = _np.full(self.shape[1], -1, dtype=_np.int64)
        remap[cols_sel] = _np.arange(len(cols_sel))
        remap_d = jnp.asarray(remap)
        new_cols = remap_d[out.indices]
        keep = new_cols >= 0
        nnz_new = int(jnp.sum(keep))
        row_ids = _convert.row_ids_from_indptr(out.indptr, out.nnz)
        data, cols2, rows_kept = _convert.compact_mask(
            keep, (out.data, new_cols, row_ids), nnz_new
        )
        return csr_array._from_parts(
            data, cols2.astype(coord_dtype_for(max(len(cols_sel), 1))),
            _convert.indptr_from_row_ids(rows_kept, out.shape[0]),
            (out.shape[0], len(cols_sel)),
            canonical=None,
        )

    def __str__(self) -> str:
        row_ids, cols, vals = self._coo_parts()
        lines = [
            f"  ({int(r)}, {int(c)})\t{v}"
            for r, c, v in zip(
                np.asarray(row_ids), np.asarray(cols), np.asarray(vals)
            )
        ]
        return "\n".join(lines)

    def __repr__(self) -> str:
        return (
            f"<{self.shape[0]}x{self.shape[1]} sparse array of type "
            f"'{self.dtype}' with {self.nnz} stored elements in "
            f"Compressed Sparse Row format>"
        )


# scipy.sparse.*_matrix alias (reference defines csr_matrix the same way).
class csr_matrix(csr_array):
    """spmatrix-flavored alias: ``*`` means matrix multiplication
    (scipy's csr_matrix), unlike the element-wise sparray ``*``; the
    legacy getrow/getcol/getH accessors exist here only, as in scipy."""

    _is_spmatrix = True

    def __pow__(self, n):
        # spmatrix semantics: matrix power (scipy's csr_matrix ** n),
        # not the element-wise sparray power.
        if not isinstance(n, (int, np.integer)) or n < 0:
            raise ValueError("matrix power requires a non-negative int")
        if self.shape[0] != self.shape[1]:
            raise TypeError("matrix is not square")
        from .gallery import identity as _identity

        result = csr_matrix(
            _identity(self.shape[0], dtype=self.dtype, format="csr")
        )
        base = self
        n = int(n)
        while n:
            if n & 1:
                result = csr_matrix(result.dot(base))
            n >>= 1
            if n:
                base = csr_matrix(base.dot(base))
        return result

    def getrow(self, i):
        return csr_matrix(self[int(i), :])

    def getcol(self, j):
        return csr_matrix(self[:, int(j)])

    def getH(self):
        return self.conj().transpose()

    def __mul__(self, other):
        if np.isscalar(other) or getattr(other, "ndim", None) == 0:
            return self._with_data(self._data * other)
        return self.dot(other)

    def __rmul__(self, other):
        if np.isscalar(other) or getattr(other, "ndim", None) == 0:
            return self._with_data(self._data * other)
        # scipy spmatrix: x * A is x @ A (row-vector matmul).
        other = np.asarray(other)
        AT = self.transpose()
        if other.ndim == 1:
            return np.asarray(AT @ other)
        return np.asarray(AT @ other.T).T


def _elementwise_intersect_multiply(a: csr_array, b: csr_array) -> csr_array:
    """Hadamard product of two canonical CSR matrices.

    Two-key sort of the concatenated coordinate lists with a value
    channel per operand: since both inputs are canonical, a coordinate
    present in both becomes an adjacent pair after the sort, and the
    product of the channel sums over the pair is the output value.  No
    fused integer key — safe for any rows*cols.
    """
    rows, cols = a.shape
    ra, ca, va = a._coo_parts()
    rb, cb, vb = b._coo_parts()
    r = jnp.concatenate([ra, rb])
    c = jnp.concatenate([ca, cb])
    ch_a = jnp.concatenate([va, jnp.zeros_like(vb)])
    ch_b = jnp.concatenate([jnp.zeros_like(va), vb])
    r, c, ch_a, ch_b = jax.lax.sort([r, c, ch_a, ch_b], num_keys=2)
    pair = jnp.logical_and(r[1:] == r[:-1], c[1:] == c[:-1])
    prod = (ch_a[:-1] + ch_a[1:]) * (ch_b[:-1] + ch_b[1:])
    nnz_out = int(jnp.sum(pair))
    idx = jnp.nonzero(pair, size=nnz_out, fill_value=0)[0]
    out_rows = r[idx]
    out_cols = c[idx]
    out_vals = prod[idx]
    indptr = _convert.indptr_from_row_ids(out_rows, rows)
    return csr_array._from_parts(
        out_vals, out_cols, indptr, (rows, cols)
    )


def spmv(A: csr_array, x, y):
    """Free-function SpMV: y <- A @ x (reference ``csr.py:562-593``)."""
    return A.dot(jnp.asarray(x), out=y)


def spgemm_csr_csr_csr(A: csr_array, B: csr_array) -> csr_array:
    """C = A @ B (reference ``csr.py:598-748``).

    Banded fast path: when both operands are *exact* bands (DIA caches
    with no hole mask), C is the Minkowski-sum band computed as
    nd_a*nd_b shifted elementwise multiplies — no expansion, no device
    sort.  This covers the SpGEMM microbenchmark's banded config and
    products of stencil operators.  Everything else runs the general
    expand-sort-compress kernel.
    """
    assert A.shape[1] == B.shape[0], "dimension mismatch in spgemm"
    m, k = A.shape
    n = B.shape[1]

    from .settings import settings

    _obs.inc("op.spgemm")
    with _lat.timer("lat.spgemm." + _lat.shape_bucket(m)), \
            _obs.span("spgemm", m=m, k=k, n=n, nnz_a=A.nnz,
                      nnz_b=B.nnz) as sp:
        dia_a = A._get_dia()
        dia_b = B._get_dia() if dia_a is not None else None
        if (
            dia_a is not None
            and dia_b is not None
            and dia_a[2] is None
            and dia_b[2] is None
        ):
            offs_c = _dia_ops.band_product_offsets(dia_a[1], dia_b[1])
            nnz_c = _dia_ops.band_cover(offs_c, (m, n), n)
            if (
                len(offs_c) <= settings.dia_max_diags
                and len(offs_c) * n
                <= settings.dia_max_expand * max(nnz_c, 1)
                # scipy pattern parity: every in-bounds product slot must
                # be structurally reachable, else the ESC kernel decides
                # nnz.
                and _dia_ops.band_product_is_full(
                    dia_a[1], dia_b[1], offs_c, A.shape, B.shape
                )
            ):
                from .ops.pallas_dia import (
                    dia_spgemm_maybe_pallas, pallas_dia_active,
                )

                Cd = (
                    dia_spgemm_maybe_pallas(
                        dia_a[0], dia_b[0], dia_a[1], dia_b[1], offs_c,
                        A.shape, B.shape,
                    )
                    if pallas_dia_active() else None
                )
                path = "dia-pallas"
                if Cd is None:
                    Cd = _dia_ops.dia_spgemm(
                        dia_a[0], dia_b[0], dia_a[1], dia_b[1], offs_c,
                        A.shape, B.shape,
                    )
                    path = "dia-xla"
                data, indices, indptr = _dia_ops.band_to_csr(
                    Cd, offs_c, (m, n), nnz_c
                )
                C = csr_array._from_parts(data, indices, indptr, (m, n))
                # The product band is exact by construction: warm C's own
                # fast-path cache for downstream matvecs (GMG coarse ops).
                C._dia_offsets = offs_c
                C._dia = (Cd, offs_c, None)
                if sp is not None:
                    itm = C.dtype.itemsize
                    sp.set(path=path, nnz=nnz_c,
                           bytes=(dia_a[0].size + dia_b[0].size
                                  + Cd.size) * itm,
                           flops=2 * len(dia_a[1]) * len(dia_b[1]) * n)
                return C

        data, indices, indptr = _spgemm_ops.spgemm_csr_csr_csr_impl(
            A.data, A.indices, A.indptr, B.data, B.indices, B.indptr,
            m, k, n
        )
        C = csr_array._from_parts(data, indices, indptr, (m, n))
        if sp is not None:
            itm = C.dtype.itemsize
            idx = C.indices.dtype.itemsize
            sp.set(path="esc", nnz=C.nnz,
                   chunks=_spgemm_ops._last_num_chunks,
                   bytes=(A.nnz + B.nnz + C.nnz) * (itm + idx))
        return C
