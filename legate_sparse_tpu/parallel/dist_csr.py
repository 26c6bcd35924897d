# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Row-block distributed CSR and collective SpMV / CG.

TPU-native re-expression of the reference's entire distribution story
(reference, §2.3 of SURVEY):

- Row-block data parallelism — ``align(y, A_pos)`` equi-partitioning of
  rows (reference ``csr.py:580-593``) becomes a 1-D mesh with the CSR
  arrays laid out as (num_shards, ...) blocks sharded on axis 0.
- Image partitioning — ``image(crd, x, MIN_MAX)`` bounding-box gathers
  (reference ``csr.py:587-591``, ``fast_image_partition.cu:29-55``)
  become build-time column-window computation; at solve time each shard
  either slices an ``all_gather``-ed x or exchanges fixed-width halos
  with mesh neighbors over ICI via ``ppermute`` (banded matrices).
- NCCL allgather of local nnz (reference ``spgemm_csr_csr_csr.cu:43-62``)
  becomes host-side padding to the max local nnz: XLA's static-shape
  analog of unbound stores.

Layout: each shard's rows are packed **ELL-style** — (rows_per_shard, W)
value/column blocks, W = the matrix's max nonzeros-per-row — so the
per-shard SpMV is one rectangular gather + a W-width masked row
reduction.  On TPU this runs at HBM roofline where flat
scatter/segment-sum kernels do not (the vector units consume the
(rows, W) tile directly; no scatter, no searchsorted).  Matrices whose
max row width would blow the padding budget fall back to padded-CSR
blocks + segment_sum.

Padding invariants: rows are padded to a multiple of the shard count
(appended rows have count 0).  Padded ELL slots replicate the row's
last valid column with value 0 and are masked out of the product by the
per-row counts (see ``ops.spmv.ell_pack``); padded CSR slots map to the
last local row with value 0.
"""

from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map

from .. import obs as _obs
from ..obs import context as _tctx
from ..obs import latency as _lat
from ..engine import engine_enabled as _engine_enabled
from ..engine import get_engine as _get_engine
from ..resilience import checkpoint as _rckpt
from ..resilience import faults as _rfaults
from ..resilience import guarded_call as _resil_guarded
from ..resilience.outcomes import ChecksumError, DeviceLost
from ..settings import settings as _rsettings
from ..types import index_dtype
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..csr import csr_array
from .mesh import (
    COL_AXIS, LAYOUT_1D_COL, LAYOUT_1D_ROW, LAYOUT_2D_BLOCK,
    LAYOUT_AUTO, ROW_AXIS, factor_grid, make_grid_mesh, make_row_mesh,
    resolve_layout, survivor_mesh,
)


@dataclass
class DistCSR:
    """Row-block sharded sparse matrix (ELL or padded-CSR layout).

    ELL layout (``ell=True``): ``data``/``cols`` are (R, rows_per_shard,
    W) and ``counts`` is (R, rows_per_shard) per-row nnz; ``cols`` holds
    *rebased* indices into the shard's halo-extended x window when
    ``halo >= 0``, else global indices.

    Padded-CSR layout: ``data``/``cols`` are (R, nnz_max) with
    ``row_ids`` (R, nnz_max) static local row ids and ``counts`` the
    (R,) per-shard valid nnz (padding suffix masked in-kernel).
    ``shard_csr`` also stores ``row_ptr`` (R, rows_per_shard + 1), each
    shard's local row bounds as offsets into its block.
    """

    # ELL/padded-CSR blocks.  May be None for a DIA-only matrix
    # (``dist_diags(materialize_ell=False)`` — the memory-lean scale
    # path): then only ``dia_*`` consumers (dist_spmv, dist_diagonal,
    # to_csr) work and block-consuming ops raise with guidance.
    data: Optional[jax.Array]
    cols: Optional[jax.Array]
    counts: Optional[jax.Array]
    row_ids: Optional[jax.Array]
    shape: Tuple[int, int]
    rows_per_shard: int
    halo: int           # -1 = no halo window -> all_gather realization
    ell: bool
    mesh: Mesh
    # Precise-image gather plan (LEGATE_SPARSE_PRECISE_IMAGES): (R, R, C)
    # local x indices shard ``src`` sends to ``dst`` via all_to_all; cols
    # are then rebased into the compact (R*C,) receive buffer.  None =
    # halo/all_gather realization.  (Reference ``settings.py:23-33``.)
    gather_idx: Optional[jax.Array] = None
    # Inverse map, sharded by *destination*: gather_globals[s, t, p] =
    # global column of compact position t*C+p on shard s.  Lets every
    # consumer (diagonal, SpGEMM, to_csr) recover global columns with
    # one flat lookup.
    gather_globals: Optional[jax.Array] = None
    cols_per_shard: int = 0
    # Banded fast path (exactly-banded matrices in halo mode): per-shard
    # DIA blocks (R, num_diags, rps) + static offsets.  ``dist_spmv``
    # then runs gather-free shifted-adds on the halo-extended x — HBM
    # gathers run far below roofline on TPU, shifted-add streams hit it.
    # Auxiliary to the ELL/CSR blocks (which all other consumers use).
    dia_data: Optional[jax.Array] = None
    dia_offsets: Optional[Tuple[int, ...]] = None
    # Explicit-entry mask blocks (R, num_diags, rps) for *holey* bands
    # (None = exact band, validity derivable from the offsets alone).
    dia_mask: Optional[jax.Array] = None
    # Pre-blocked Mosaic layout for the per-shard Pallas band kernel,
    # built once at shard time (the cached-partition analog — the shard
    # body does zero packing per call): pdia_data (R, nd, rps_pad) is
    # the tile-padded band, +0.0 at every slot outside the global
    # bounds, on a padding row or in a band hole, and never +0.0 at an
    # explicit entry (``pallas_dia.encode_band``).  ``pdia_tile`` is
    # the grid tile (0 = no prepack -> XLA shifted-add branch).
    pdia_data: Optional[jax.Array] = None
    pdia_tile: int = 0
    # Per-shard block-sparse pack for irregular (all_gather) matrices
    # (``attach_bsr_prepack``): (R, nb_max, 128, 128) transposed
    # blocks + (R, nb_max) block coordinates; ``bsr_grid`` = (nbr, nbc)
    # of the per-shard block grid (None = no BSR route).
    bsr_blocks: Optional[jax.Array] = None
    bsr_brow: Optional[jax.Array] = None
    bsr_bcol: Optional[jax.Array] = None
    bsr_grid: Optional[Tuple[int, int]] = None
    bsr_tried: bool = False
    # Host-side stored-entry count, set by the builders that know it
    # (shard_csr, dist_diags, dist_spgemm).  -1 = unknown; consumers
    # that need it (the sparsity-aware window-decline key) fall back to
    # ``global_nnz`` once and memoize here — keeping the device->host
    # counts fetch off every later call.
    nnz_hint: int = -1
    # Partition layout strategy (docs/DIST.md).  "1d-row" is the
    # historical row-block layout described above.  ``grid`` is set for
    # the 2-d family ("2d-block" / "1d-col" = a (1, R) grid): blocks
    # are (Rr, Rc, nnz_max) padded-CSR sharded P(rows, cols, None)
    # with BLOCK-LOCAL column indices (global - j*cols_per_shard),
    # counts (Rr, Rc) per-block valid nnz, and vectors sharded
    # P((rows, cols)) in row-major grid chunks.
    layout: str = LAYOUT_1D_ROW
    grid: Optional[Tuple[int, int]] = None
    # Padded-CSR row bounds (int32 offsets into each block; rows past
    # the shard's real rows repeat its end): (R, rps + 1), or
    # (Rr, Rc, rps + 1) on a 2-d grid.  The or-and kernels reduce rows
    # by a prefix count read at these bounds in place of a scatter.
    # None where the builder stores none (ELL, DIA-only, SpGEMM
    # products): those keep the segment reduction.
    row_ptr: Optional[jax.Array] = None

    @property
    def num_shards(self) -> int:
        if self.grid is not None:
            return self.grid[0] * self.grid[1]
        blocks = self.data if self.data is not None else self.dia_data
        return blocks.shape[0]

    @property
    def rows_padded(self) -> int:
        # 2-d grids: the row dimension is split over grid[0] mesh rows
        # only (each row block further column-split over grid[1]).
        if self.grid is not None:
            return self.grid[0] * self.rows_per_shard
        return self.num_shards * self.rows_per_shard

    @property
    def cols_padded(self) -> int:
        """Padded column count (2-d layouts; equals the padded x
        length the SpMV consumes)."""
        if self.grid is not None:
            return self.grid[1] * self.cols_per_shard
        return self.shape[1]

    # ---- int32-local / int64-global index split (SURVEY §7 hard part
    # 5; reference runs coord_ty = int64 throughout,
    # ``legate_sparse/types.py:20-25``).  Device-side structures are
    # shard-LOCAL int32 (column windows, local row ids, per-shard
    # counts); everything GLOBAL — row offsets, total nnz — lives here
    # as host-side int64/Python ints, never as device arrays, so a
    # no-x64 TPU process handles matrices whose *global* nnz exceeds
    # 2^31 while every shard stays within int32.  ``coord_dtype_for``'s
    # OverflowError remains the single-device (host-CSR) boundary only.

    @property
    def shard_row_starts(self) -> np.ndarray:
        """Global first-row of each shard, host-side int64."""
        return (np.arange(self.num_shards, dtype=np.int64)
                * np.int64(self.rows_per_shard))

    @property
    def global_nnz(self) -> int:
        """Total stored entries across shards, as a host Python int
        (exact past 2^31 with int32 device counts — the summation never
        touches a device-wide int64 array)."""
        if self.counts is not None:
            # ELL: (R, rps) per-row counts (padding rows are 0);
            # padded-CSR: (R,) per-shard totals.  Same exact int64 sum.
            return int(np.asarray(self.counts).astype(np.int64).sum())
        # DIA-only matrix.  Masked bands: the mask is 0 outside the
        # global range by construction, so its sum is the count.
        if self.dia_mask is not None:
            return int(np.asarray(self.dia_mask).astype(np.int64).sum())
        # Exact bands: per-diagonal in-range slot count, Python ints
        # (exact at any size).
        rows, cols = self.shape
        return sum(
            max(0, min(rows, cols - o) - max(0, -o))
            for o in self.dia_offsets
        )

    @property
    def dtype(self):
        blocks = self.data if self.data is not None else self.dia_data
        return np.dtype(blocks.dtype)

    def _require_blocks(self, op: str) -> None:
        if self.data is None:
            raise ValueError(
                f"{op} needs ELL/CSR blocks, but this DistCSR is "
                "DIA-only (built with materialize_ell=False); rebuild "
                "with materialize_ell=True"
            )

    def matvec_fn(self):
        """A jittable ``x_padded -> y_padded`` closure for solver loops."""
        return partial(dist_spmv, self)

    def to_csr(self):
        """Gather the distributed matrix back to a host csr_array.

        Test/inspection utility (the analog of the reference pulling a
        store through ``store_to_cupynumeric_array``); O(global nnz) on
        the host — not a scale path.
        """
        from ..csr import csr_array

        rows, cols = self.shape
        R = self.num_shards
        rps = self.rows_per_shard
        if self.data is None:
            return self._dia_to_csr_host()
        if self.grid is not None:
            return self._grid_to_csr_host()
        starts = np.arange(R) * rps
        data_b = np.asarray(self.data)
        cols_b = np.asarray(self.cols)
        ggl = (np.asarray(self.gather_globals)
               if self.gather_globals is not None else None)

        def to_global(s, col_local):
            if ggl is not None:      # precise: compact buffer position
                base = ggl[s].reshape(-1)
                rc = base.shape[0]
                col_local = col_local.astype(np.int64)
                own = col_local - rc + s * self.cols_per_shard
                return np.where(
                    col_local < rc, base[np.clip(col_local, 0, rc - 1)],
                    own,
                )
            if self.halo >= 0:
                return col_local.astype(np.int64) + (starts[s] - self.halo)
            return col_local.astype(np.int64)

        coo_r, coo_c, coo_v = [], [], []
        if self.ell:
            counts = np.asarray(self.counts)          # (R, rps)
            for s in range(R):
                for_r = np.arange(rps)[:, None]
                W = cols_b.shape[-1]
                slot = np.arange(W)[None, :]
                valid = slot < counts[s][:, None]
                gcol = to_global(s, cols_b[s])
                r_ids = np.broadcast_to(for_r + starts[s], (rps, W))
                coo_r.append(r_ids[valid])
                coo_c.append(gcol[valid])
                coo_v.append(data_b[s][valid])
        else:
            counts = np.asarray(self.counts)          # (R,)
            rids_b = np.asarray(self.row_ids)
            for s in range(R):
                ln = int(counts[s])
                gcol = to_global(s, cols_b[s, :ln])
                coo_r.append(rids_b[s, :ln].astype(np.int64) + starts[s])
                coo_c.append(gcol)
                coo_v.append(data_b[s, :ln])
        coo_r = np.concatenate(coo_r) if coo_r else np.zeros(0, np.int64)
        coo_c = np.concatenate(coo_c) if coo_c else np.zeros(0, np.int64)
        coo_v = (np.concatenate(coo_v) if coo_v
                 else np.zeros(0, self.dtype))
        keep = coo_r < rows  # drop padding rows
        return csr_array(
            (coo_v[keep], (coo_r[keep], coo_c[keep])), shape=self.shape
        )

    def _grid_to_csr_host(self):
        """2-d-block matrix back to a host csr_array (test/inspection;
        O(global nnz) on the host — not a scale path)."""
        from ..csr import csr_array

        Rr, Rc = self.grid
        rps = self.rows_per_shard
        cps = self.cols_per_shard
        data_b = np.asarray(self.data)        # (Rr, Rc, nnz_max)
        cols_b = np.asarray(self.cols)
        rids_b = np.asarray(self.row_ids)
        counts = np.asarray(self.counts)      # (Rr, Rc)
        coo_r, coo_c, coo_v = [], [], []
        for i in range(Rr):
            for j in range(Rc):
                ln = int(counts[i, j])
                coo_r.append(rids_b[i, j, :ln].astype(np.int64)
                             + i * rps)
                coo_c.append(cols_b[i, j, :ln].astype(np.int64)
                             + j * cps)
                coo_v.append(data_b[i, j, :ln])
        coo_r = np.concatenate(coo_r)
        coo_c = np.concatenate(coo_c)
        coo_v = np.concatenate(coo_v)
        keep = (coo_r < self.shape[0]) & (coo_c < self.shape[1])
        return csr_array(
            (coo_v[keep], (coo_r[keep], coo_c[keep])), shape=self.shape
        )

    def _dia_to_csr_host(self):
        """DIA-only matrix back to a host csr_array (test/inspection).

        Faithful: exact bands carry every in-range slot explicitly,
        masked bands use the stored explicit-entry mask — so explicit
        zeros and holes round-trip correctly."""
        from ..csr import csr_array

        rows, cols = self.shape
        R, nd, rps = self.dia_data.shape
        ddata = np.asarray(self.dia_data)
        dmask = (np.asarray(self.dia_mask)
                 if self.dia_mask is not None else None)
        r_pad = np.arange(R * rps, dtype=np.int64)
        coo_r, coo_c, coo_v = [], [], []
        for d, o in enumerate(self.dia_offsets):
            col = r_pad + o
            valid = (col >= 0) & (col < cols) & (r_pad < rows)
            if dmask is not None:
                valid &= dmask[:, d, :].reshape(-1)
            coo_r.append(r_pad[valid])
            coo_c.append(col[valid])
            coo_v.append(ddata[:, d, :].reshape(-1)[valid])
        return csr_array(
            (np.concatenate(coo_v), (np.concatenate(coo_r),
                                     np.concatenate(coo_c))),
            shape=self.shape,
        )

    def toscipy(self):
        return self.to_csr().toscipy()


def attach_dia_prepack(dist: DistCSR) -> DistCSR:
    """Pre-block the Mosaic band layout on a banded DistCSR, in place.

    Built once per matrix — the shard body of the Pallas dist SpMV then
    does zero packing per call (the cached-partition analog).  Shared
    by every banded builder (``shard_csr``, ``dist_diags``, the banded
    ``dist_spgemm`` product).  No-op when already built, not banded,
    over the Mosaic budget (``supported``), or the Pallas dist route is
    off (``pallas_dist_mode() == "0"`` — the default off-TPU — so pure
    XLA runs never pay the doubled band memory).

    The pack carries validity in its values (``encode_band``): a slot
    outside the global row/column bounds, on a padding row or in a band
    hole is +0.0, an explicit +0.0 entry is stored as -0.0, and the
    kernel multiplies only slots whose bits are not zero.  So the
    ring-wrapped halo never injects non-finite values (same IEEE
    invariant as the XLA branch), and since -0.0 and +0.0 products
    differ only in the sign of a zero that the +0.0-started sums
    absorb, the products are bit-identical to the masked form.  The
    in-range hole slots are counted under ``pallas.pack.hole_slots``.

    The tile covers the copied window's reach, the offsets shifted by
    +halo, so the shard body can take either route (``_dia_window``);
    the pack itself does not depend on the route.
    """
    from ..ops.pallas_dia import encode_band, pallas_dist_mode, supported

    if (dist.pdia_tile or dist.dia_data is None or dist.halo < 0
            or dist.dia_offsets is None or pallas_dist_mode() == "0"):
        return dist
    offsets = dist.dia_offsets
    offs2 = tuple(int(o) + dist.halo for o in offsets)
    tile = supported(offs2, dist.dtype)
    if tile is None:
        return dist
    R, nd, rps = dist.dia_data.shape
    n_rows = dist.shape[0]
    rps_pad = -(-rps // tile) * tile
    r_g = jnp.arange(R * rps, dtype=jnp.int32).reshape(R, 1, rps)
    offs_a = jnp.asarray(offsets, dtype=jnp.int32).reshape(1, nd, 1)
    entry = ((r_g + offs_a >= 0) & (r_g + offs_a < n_rows)
             & (r_g < n_rows))
    holes = 0
    if dist.dia_mask is not None:
        stored = jnp.asarray(dist.dia_mask) != 0
        holes = int(jnp.count_nonzero(entry & ~stored))
        entry = entry & stored
    _obs.inc("pallas.pack.hole_slots", holes)
    pad = ((0, 0), (0, 0), (0, rps_pad - rps))
    spec = NamedSharding(dist.mesh, P(ROW_AXIS, None, None))
    dist.pdia_data = jax.device_put(
        jnp.pad(encode_band(jnp.asarray(dist.dia_data), entry), pad), spec
    )
    dist.pdia_tile = tile
    return dist


def _precise_gather_plan(indices, indptr, starts, ends, R, cps, cols):
    """Per-shard precise image: exactly the x entries each shard reads
    (reference precise images, ``settings.py:23-33``), as an all_to_all
    send plan + a rebase map global col -> compact buffer position.

    A shard's *own* x block never rides the collective — the compact
    buffer is ``concat(recv.flat (R*C), x_local (cps))``, so C is the
    max count over *off-shard* pairs only (for a banded matrix with one
    long-range row, C stays O(1) instead of O(rps)).

    Returns (gather_idx (R_src, R_dst, C), gather_globals (R_dst, R_src,
    C), rebase: (shard, global cols) -> compact positions).
    """
    needed = []     # needed[s][t] = sorted unique cols shard s reads from t
    C = 1
    for s in range(R):
        win = np.unique(indices[indptr[starts[s]] : indptr[ends[s]]])
        per_t = []
        for t in range(R):
            sub = win[(win >= t * cps) & (win < (t + 1) * cps)]
            per_t.append(sub)
            if t != s:
                C = max(C, sub.shape[0])
        needed.append(per_t)
    gather_idx = np.zeros((R, R, C), dtype=np.int32)
    for s in range(R):
        for t in range(R):
            if t == s:
                continue
            sub = needed[s][t]
            gather_idx[t, s, : sub.shape[0]] = sub - t * cps
    gather_globals = (
        np.transpose(gather_idx, (1, 0, 2)).astype(np.int64)
        + (np.arange(R, dtype=np.int64) * cps)[None, :, None]
    )

    def rebase(s, cols_global):
        flat = cols_global.reshape(-1)
        t_of = np.clip(flat // cps, 0, R - 1)
        res = np.empty(flat.shape[0], dtype=np.int64)
        for t in range(R):
            m = t_of == t
            if not m.any():
                continue
            if t == s:     # own block: appended local region
                res[m] = R * C + (flat[m] - s * cps)
            else:
                res[m] = t * C + np.searchsorted(needed[s][t], flat[m])
        return np.clip(res.reshape(cols_global.shape), 0, R * C + cps - 1)

    return gather_idx, gather_globals, rebase


def _dia_shard_blocks(offs, dia_global, R, rps, rows, cols, dtype):
    """Per-shard DIA blocks: block[s, d, r] = A[start_s+r, start_s+r+o_d]
    (0 where out of range / padding rows)."""
    rows_p = R * rps
    nd = offs.shape[0]
    out = np.zeros((R, nd, rps), dtype=dtype)
    r_pad = np.arange(rows_p, dtype=np.int64)
    for d, o in enumerate(offs.tolist()):
        src = r_pad + o
        valid = (src >= 0) & (src < cols) & (r_pad < rows)
        tmp = np.zeros(rows_p, dtype=dtype)
        tmp[valid] = dia_global[d, src[valid]]
        out[:, d, :] = tmp.reshape(R, rps)
    return out


def _device_put_sharded(arr, sharding):
    """``jax.device_put`` onto a (possibly process-spanning) sharding.

    In multi-controller runs, plain ``device_put`` of a host array
    onto a NamedSharding that spans non-addressable devices performs a
    cross-host equality check that the installed jax cannot run on the
    CPU backend ("Multiprocess computations aren't implemented");
    ``make_array_from_callback`` sidesteps it and materializes only
    each process's addressable shards — which is also the right memory
    behavior at scale.  Single-process behavior is unchanged."""
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx]
    )


def _grid_of(mesh: Optional[Mesh], layout: str) -> Tuple[int, int]:
    """Resolve the (Rr, Rc) grid a 2-d-family layout would use on
    ``mesh`` (or on all devices when None): "1d-col" is the (1, N)
    degenerate grid, "2d-block" the mesh's own 2-D shape or the
    near-square factorization."""
    n = int(np.prod(mesh.devices.shape)) if mesh is not None \
        else len(jax.devices())
    if layout == LAYOUT_1D_COL:
        return (1, n)
    if (mesh is not None and len(mesh.devices.shape) == 2
            and int(mesh.shape[ROW_AXIS]) > 1):
        return (int(mesh.shape[ROW_AXIS]), int(mesh.shape[COL_AXIS]))
    return factor_grid(n)


def _grid_mesh_for(mesh: Optional[Mesh], grid: Tuple[int, int]) -> Mesh:
    """A (rows, cols) mesh of shape ``grid`` over ``mesh``'s devices
    (all devices when None), reusing ``mesh`` itself when it already
    has that shape."""
    if mesh is not None:
        if (tuple(mesh.axis_names) == (ROW_AXIS, COL_AXIS)
                and tuple(mesh.devices.shape) == tuple(grid)):
            return mesh
        return make_grid_mesh(list(mesh.devices.flat), shape=grid)
    return make_grid_mesh(shape=grid)


def _predict_1d_spmv_bytes(rows: int, cols: int, indptr, indices,
                           R: int, itemsize: int) -> int:
    """Predicted per-call x-realization bytes of the 1d-row SpMV at
    shard count ``R`` — the same halo-vs-all_gather analysis
    ``shard_csr`` performs, priced by ``obs.comm`` (precise images are
    ignored: auto routing compares the default realizations)."""
    from ..obs import comm as _comm

    rps = math.ceil(rows / R) if rows else 1
    if rows == cols and rows:
        starts = np.minimum(np.arange(R) * rps, rows)
        ends = np.minimum(starts + rps, rows)
        lo, hi = indptr[starts], indptr[ends]
        h = 0
        for s in range(R):
            if hi[s] > lo[s]:
                win = indices[lo[s]:hi[s]]
                h = max(h, int(max(starts[s] - win.min(),
                                   win.max() + 1 - ends[s], 0)))
        if h <= rps:
            return _comm.halo_exchange_bytes(h, itemsize, R)
    return _comm.all_gather_bytes(rps, itemsize, R)


def _route_layout(A: csr_array, mesh: Optional[Mesh]) -> str:
    """Evidence-based "auto" routing: pick 2d-block only when its
    predicted per-SpMV interconnect bytes strictly beat the 1d-row
    prediction at EQUAL device count, and record the decision (with
    both predictions) as a ``shard_csr.routing`` obs event — the
    layout analog of the SpGEMM window-vs-all_gather probe."""
    from ..obs import comm as _comm

    rows, cols = A.shape
    grid = _grid_of(mesh, LAYOUT_2D_BLOCK)
    Rr, Rc = grid
    N = Rr * Rc
    item = np.dtype(A.data.dtype).itemsize
    bytes_1d = _predict_1d_spmv_bytes(
        rows, cols, np.asarray(A.indptr), np.asarray(A.indices), N, item
    )
    rows_p = N * max(-(-rows // N), 1)
    cols_p = N * max(-(-cols // N), 1)
    vols_2d = _comm.spmv_volumes_2d(
        grid_rows=Rr, grid_cols=Rc, spc=cols_p // N,
        rps=rows_p // Rr, itemsize=item,
    )
    bytes_2d = _comm.total(vols_2d)
    choice = LAYOUT_2D_BLOCK if bytes_2d < bytes_1d else LAYOUT_1D_ROW
    _obs.event("shard_csr.routing", layout=choice, shards=N,
               grid=grid, rows=rows, nnz=int(A.indptr[-1]),
               predicted_1d_bytes=bytes_1d, predicted_2d_bytes=bytes_2d)
    return choice


def _row_bounds(row_counts: np.ndarray) -> np.ndarray:
    """(blocks, rps + 1) int32 row bounds of padded-CSR blocks from
    their (blocks, rps) per-row stored counts: 0, then the running
    count (``DistCSR.row_ptr``)."""
    out = np.zeros((row_counts.shape[0], row_counts.shape[1] + 1),
                   dtype=np.int32)
    np.cumsum(row_counts, axis=1, out=out[:, 1:])
    return out


def _shard_csr_2d(A: csr_array, mesh: Optional[Mesh],
                  layout: str) -> DistCSR:
    """2-d block partitioning: block (i, j) of the (Rr, Rc) grid holds
    rows [i*rps, (i+1)*rps) x cols [j*cps, (j+1)*cps) as padded-CSR
    with BLOCK-LOCAL column indices.  Rows/cols are padded to a
    multiple of Rr*Rc so the flat vector chunks (P((rows, cols))
    sharding, row-major grid order) divide evenly on both ends of the
    SpMV, and so the same blocks feed the SUMMA-style ``dist_spgemm``
    panels (A row panels gathered along mesh columns, B column panels
    staged along mesh rows) with no re-partitioning."""
    grid = _grid_of(mesh, layout)
    mesh = _grid_mesh_for(mesh, grid)
    Rr, Rc = grid
    N = Rr * Rc
    rows, cols = A.shape
    rows_p = N * max(-(-rows // N), 1)
    cols_p = N * max(-(-cols // N), 1)
    rps, cps = rows_p // Rr, cols_p // Rc

    indptr = np.asarray(A.indptr)
    indices = np.asarray(A.indices)
    data = np.asarray(A.data)
    nnz = int(indptr[-1])
    r_of = np.repeat(np.arange(rows, dtype=np.int64),
                     np.diff(indptr)) if nnz else np.zeros(0, np.int64)
    c_of = indices.astype(np.int64)

    def blocks_of(bid, row_local, col_local, n_blocks, rid_pad):
        """Pack entries into (n_blocks, nnz_max) padded-CSR arrays by
        block id (CSR traversal order stays row-sorted per block)."""
        per = np.bincount(bid, minlength=n_blocks) if nnz \
            else np.zeros(n_blocks, np.int64)
        cap = max(int(per.max()), 1) if nnz else 1
        d_b = np.zeros((n_blocks, cap), dtype=data.dtype)
        # Block-local column values live in [0, cps): whenever the
        # block width fits, the static index payload ships as int16 —
        # the panel gather upcasts in-register, so the narrow width is
        # pure HBM/interconnect savings (docs/DIST.md storage note).
        col_dt = (np.int16 if cps - 1 <= np.iinfo(np.int16).max
                  else np.int32)
        c_b = np.zeros((n_blocks, cap), dtype=col_dt)
        r_b = np.full((n_blocks, cap), rid_pad, dtype=np.int32)
        for g in range(n_blocks):
            m = bid == g
            ln = int(per[g])
            if ln:
                d_b[g, :ln] = data[m]
                c_b[g, :ln] = col_local[m]
                r_b[g, :ln] = row_local[m]
        return d_b, c_b, r_b, per.astype(np.int32)

    # Main blocks: grid-block id i*Rc + j.
    bi, bj = r_of // rps, c_of // cps
    bid, row_local = bi * Rc + bj, r_of - bi * rps
    d_b, c_b, r_b, cnt = blocks_of(
        bid, row_local, c_of - bj * cps, N, max(rps - 1, 0),
    )
    rp_b = _row_bounds(np.bincount(bid * rps + row_local,
                                   minlength=N * rps).reshape(N, rps))
    spec3 = NamedSharding(mesh, P(ROW_AXIS, COL_AXIS, None))
    spec2 = NamedSharding(mesh, P(ROW_AXIS, COL_AXIS))

    def put(arr, spec):
        a = jnp.asarray(arr)
        _obs.inc("transfer.shard_upload")
        _obs.inc("transfer.shard_upload_bytes",
                 int(a.size) * a.dtype.itemsize)
        return _device_put_sharded(a, spec)

    def grid3(arr):
        return put(arr.reshape(Rr, Rc, -1), spec3)

    _obs.event("shard_csr.layout", layout=layout, halo=-1,
               precise=False, shards=N, rows=rows, nnz=nnz,
               banded=False, grid=grid)
    return DistCSR(
        data=grid3(d_b), cols=grid3(c_b), counts=put(
            cnt.reshape(Rr, Rc), spec2),
        row_ids=grid3(r_b), shape=(rows, cols), rows_per_shard=rps,
        halo=-1, ell=False, mesh=mesh, cols_per_shard=cps,
        nnz_hint=nnz, layout=layout, grid=grid, row_ptr=grid3(rp_b),
    )


def shard_csr(A: csr_array, mesh: Optional[Mesh] = None,
              force_all_gather: bool = False,
              ell_max_expand: Optional[float] = None,
              precise: Optional[bool] = None,
              layout: Optional[str] = None) -> DistCSR:
    """Partition a csr_array over a device mesh per a layout strategy.

    ``layout`` picks the partition strategy (docs/DIST.md): "1d-row"
    (the historical default — row blocks, x realized via
    halo/all_gather/precise), "1d-col" / "2d-block" (the 2-d block
    family — x broadcast per mesh column, partial products
    reduce-scattered along mesh columns), or "auto" (route by
    predicted interconnect bytes, recorded as a ``shard_csr.routing``
    event).  Precedence is explicit: argument > the
    ``LEGATE_SPARSE_TPU_DIST_LAYOUT`` env knob > "1d-row".

    The 1d-row build is the host-side analog of Legion solving
    partition constraints once and caching them across solver
    iterations (reference §3.2 note on partition caching): it computes
    each shard's column window min/max — the FAST_IMAGE_RANGE analog
    (``fast_image_partition.cu:29-55``) — and picks halo-exchange when
    every window fits within one neighbor shard on each side.
    """
    from ..settings import settings

    _obs.inc("op.shard_csr")
    if precise and force_all_gather:
        # Both knobs name an x realization and they contradict: honor
        # neither silently (satellite of the argument>env precedence
        # contract — see tests/test_dist_layout.py).
        raise ValueError(
            "shard_csr: precise=True conflicts with "
            "force_all_gather=True — the two request different x "
            "realizations; pass at most one"
        )
    lay = resolve_layout(layout)
    if lay == LAYOUT_AUTO:
        lay = _route_layout(A, mesh)
    if lay in (LAYOUT_2D_BLOCK, LAYOUT_1D_COL):
        if precise:
            raise ValueError(
                f"shard_csr: precise images are a 1d-row realization; "
                f"not supported with layout={lay!r}"
            )
        dist = _shard_csr_2d(A, mesh, lay)
        dist._src_csr = A
        return dist
    if ell_max_expand is None:
        ell_max_expand = settings.ell_max_expand
    if precise is None:
        # Env default; an explicit force_all_gather argument wins over it
        # (explicit precise=True is a conflict, rejected above).
        precise = settings.precise_images and not force_all_gather
    if mesh is None:
        mesh = make_row_mesh()
    # Row-shard count: the size of the "rows" axis only (a 2-D
    # grid mesh replicates the matrix along "cols").
    R = int(mesh.shape[ROW_AXIS])
    rows, cols = A.shape
    rps = math.ceil(rows / R) if rows else 1
    # The shards are cut on the host: three blocking fetches.
    _obs.inc("transfer.host_sync.shard_csr", 3)
    indptr = np.asarray(A.indptr)
    indices = np.asarray(A.indices)
    data = np.asarray(A.data)
    counts = np.diff(indptr)
    nnz = int(indptr[-1])

    starts = np.minimum(np.arange(R) * rps, rows)
    ends = np.minimum(starts + rps, rows)

    # Column windows per shard (FAST_IMAGE_RANGE analog).
    col_min = np.zeros(R, dtype=np.int64)
    col_max = np.zeros(R, dtype=np.int64)
    lo = indptr[starts]
    hi = indptr[ends]
    for s in range(R):
        if hi[s] > lo[s]:
            win = indices[lo[s] : hi[s]]
            col_min[s] = win.min()
            col_max[s] = win.max()
        else:
            col_min[s] = min(starts[s], max(cols - 1, 0))
            col_max[s] = col_min[s]

    # Precise images replace the min/max window realization outright.
    gather_idx = gather_globals = rebase_precise = None
    cps = math.ceil(cols / R) if cols else 1   # x column-block size
    if precise:
        gather_idx, gather_globals, rebase_precise = _precise_gather_plan(
            indices, indptr, starts, ends, R, cps, cols
        )

    # Halo width: how far each shard's window reaches outside its own
    # row block (square matrices only — halo mode needs x and rows to be
    # conformally sharded).
    halo = -1
    if rows == cols and not force_all_gather and not precise:
        left_reach = np.maximum(starts - col_min, 0)
        right_reach = np.maximum(col_max + 1 - ends, 0)
        h = int(max(left_reach.max(), right_reach.max()))
        if h <= rps:
            halo = h
        else:
            # The global max-window is blown (e.g. one long-range row —
            # the reference's per-shard images keep every *other* shard
            # narrow, ``csr.py:587-591``).  Try the precise plan and keep
            # it if its buffer beats a full all_gather realization.
            gi, gg, rb = _precise_gather_plan(
                indices, indptr, starts, ends, R, cps, cols
            )
            if R * gi.shape[-1] + cps < R * rps:
                precise = True
                gather_idx, gather_globals, rebase_precise = gi, gg, rb

    # Banded fast path: banded matrices in halo mode also carry
    # per-shard DIA blocks so dist_spmv runs gather-free shifted-adds.
    # Detection, budgets and the exact/masked split all live in
    # ``csr_array._get_dia`` (single source of truth; this also warms
    # A's own single-chip cache).
    dia_offs = dia_blocks = dia_mask_blocks = None
    if halo >= 0:
        dia_cache = A._get_dia()
        if dia_cache is not None:
            dia_dev, offs_t, mask_dev = dia_cache
            offs_b = np.asarray(offs_t, dtype=np.int64)
            mo = int(max(offs_b.max(initial=0), -offs_b.min(initial=0)))
            if mo <= rps:
                halo = max(halo, mo)
                dia_offs = offs_t
                dia_blocks = _dia_shard_blocks(
                    offs_b, np.asarray(dia_dev), R, rps, rows, cols,
                    data.dtype,
                )
                if mask_dev is not None:
                    dia_mask_blocks = _dia_shard_blocks(
                        offs_b, np.asarray(mask_dev), R, rps, rows,
                        cols, bool,
                    )

    from ..ops.spmv import ell_pack, ell_within_budget

    rows_p = R * rps
    W = max(int(counts.max()), 1) if rows and nnz else 1
    # Budget uses the *padded* row count — what actually gets allocated.
    use_ell = ell_within_budget(rows_p, W, nnz, ell_max_expand)

    spec = NamedSharding(mesh, P(ROW_AXIS))

    def put(arr):
        a = jnp.asarray(arr)
        _obs.inc("transfer.shard_upload")
        _obs.inc("transfer.shard_upload_bytes",
                 int(a.size) * a.dtype.itemsize)
        return _device_put_sharded(a, spec)

    if use_ell:
        # Shared (rows, W) ELL pack, padded to R*rps rows, then reshaped
        # to (R, rps, W) row blocks.
        ell_data, ell_cols, ell_counts = ell_pack(
            data, indices, indptr, rows, W, xp=np
        )
        if rows_p > rows:
            pad = rows_p - rows
            ell_data = np.concatenate(
                [ell_data, np.zeros((pad, W), dtype=ell_data.dtype)]
            )
            ell_cols = np.concatenate(
                [ell_cols, np.zeros((pad, W), dtype=ell_cols.dtype)]
            )
            ell_counts = np.concatenate(
                [ell_counts, np.zeros((pad,), dtype=ell_counts.dtype)]
            )
        ell_cols = ell_cols.reshape(R, rps, W)
        ell_data = ell_data.reshape(R, rps, W)
        ell_counts = ell_counts.reshape(R, rps)
        if precise:
            ell_cols = np.stack(
                [rebase_precise(s, ell_cols[s]) for s in range(R)]
            ).astype(np.int32)
        elif halo >= 0:
            # Rebase to the halo-extended window: local = global-(start-h).
            reb = ell_cols - (starts - halo)[:, None, None]
            ell_cols = np.clip(reb, 0, rps + 2 * halo - 1).astype(
                indices.dtype
            )
        _obs.event("shard_csr.layout", layout="ell", halo=halo,
                   precise=bool(precise), shards=R, rows=rows, nnz=nnz,
                   banded=dia_offs is not None)
        dist = attach_dia_prepack(DistCSR(
            data=put(ell_data), cols=put(ell_cols), counts=put(ell_counts),
            row_ids=None, shape=(rows, cols), rows_per_shard=rps,
            halo=halo, ell=True, mesh=mesh,
            gather_idx=(put(gather_idx) if precise else None),
            gather_globals=(put(gather_globals) if precise else None),
            cols_per_shard=cps,
            dia_data=(put(dia_blocks) if dia_blocks is not None else None),
            dia_offsets=dia_offs,
            dia_mask=(put(dia_mask_blocks)
                      if dia_mask_blocks is not None else None),
            nnz_hint=nnz,
        ))
        # Retain the host source for parallel/reshard.py's repartition
        # path (recovery ladder: survivor-mesh re-shard after a device
        # loss) — a host reference, not a device copy.
        dist._src_csr = A
        return dist

    # Padded-CSR fallback: (R, nnz_max) + static row ids.
    local_nnz = hi - lo
    nnz_max = max(int(local_nnz.max()), 1) if nnz else 1
    data_b = np.zeros((R, nnz_max), dtype=data.dtype)
    idx_b = np.zeros((R, nnz_max), dtype=indices.dtype)
    rid_b = np.zeros((R, nnz_max), dtype=np.int32)
    for s in range(R):
        ln = int(local_nnz[s])
        data_b[s, :ln] = data[lo[s] : hi[s]]
        idx_b[s, :ln] = indices[lo[s] : hi[s]]
        local_counts = counts[starts[s] : ends[s]]
        rid = np.repeat(
            np.arange(ends[s] - starts[s], dtype=np.int32), local_counts
        )
        rid_b[s, :ln] = rid
        rid_b[s, ln:] = max(rps - 1, 0)  # padding -> last row, value 0
    counts_p = np.zeros(R * rps, dtype=np.int64)
    counts_p[:rows] = counts
    rp_b = _row_bounds(counts_p.reshape(R, rps))
    if precise:
        idx_b = np.stack(
            [rebase_precise(s, idx_b[s]) for s in range(R)]
        ).astype(np.int32)
    elif halo >= 0:
        reb = idx_b - (starts - halo)[:, None]
        idx_b = np.clip(reb, 0, rps + 2 * halo - 1).astype(indices.dtype)
    _obs.event("shard_csr.layout", layout="padded-csr", halo=halo,
               precise=bool(precise), shards=R, rows=rows, nnz=nnz,
               banded=dia_offs is not None)
    dist = attach_dia_prepack(DistCSR(
        data=put(data_b), cols=put(idx_b),
        counts=put(local_nnz.astype(np.int32)), row_ids=put(rid_b),
        row_ptr=put(rp_b),
        shape=(rows, cols), rows_per_shard=rps, halo=halo, ell=False,
        mesh=mesh,
        gather_idx=(put(gather_idx) if precise else None),
        gather_globals=(put(gather_globals) if precise else None),
        cols_per_shard=cps,
        dia_data=(put(dia_blocks) if dia_blocks is not None else None),
        dia_offsets=dia_offs,
        dia_mask=(put(dia_mask_blocks)
                  if dia_mask_blocks is not None else None),
        nnz_hint=nnz,
    ))
    dist._src_csr = A
    return dist


def shard_vector(x, mesh: Mesh, rows_padded: int,
                 layout: str = LAYOUT_1D_ROW) -> jax.Array:
    """Pad a global vector to the sharded length and lay it out per the
    matrix layout: row-block (P(rows)) for 1d-row, flat row-major grid
    chunks (P((rows, cols))) for the 2-d family."""
    x = jnp.asarray(x)
    pad = rows_padded - x.shape[0]
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), dtype=x.dtype)])
    _obs.inc("transfer.shard_upload")
    _obs.inc("transfer.shard_upload_bytes",
             int(x.size) * x.dtype.itemsize)
    spec = (P((ROW_AXIS, COL_AXIS))
            if layout in (LAYOUT_2D_BLOCK, LAYOUT_1D_COL)
            else P(ROW_AXIS))
    return _device_put_sharded(x, NamedSharding(mesh, spec))


def mesh_fingerprint(mesh: Mesh, layout: Optional[str] = None) -> str:
    """Stable identity of the physical device set behind a mesh:
    axis names/shape plus every device's (platform, id).

    The engine's plan-cache key term for distributed plans
    (``docs/ENGINE.md``): a compiled collective program is only
    reusable on the exact device topology it was lowered for, and two
    meshes over the same devices in the same order ARE the same
    topology even when the ``Mesh`` objects differ.

    ``layout`` optionally folds the partition strategy into the
    fingerprint: a 1d-row and a 2d-block partition over the SAME
    device grid lower to different collective programs, so the
    dist-plan ledger must not alias them."""
    import hashlib

    devs = tuple(
        (getattr(d, "platform", "?"), int(getattr(d, "id", -1)))
        for d in mesh.devices.flat
    )
    desc = repr((tuple(mesh.axis_names), tuple(mesh.devices.shape),
                 devs) + ((layout,) if layout is not None else ()))
    return hashlib.sha1(desc.encode()).hexdigest()[:16]


def dist_plan_fingerprint(A: DistCSR) -> str:
    """Mesh fingerprint + the layout terms the ``lru_cache``'d
    shard_map builders key on (partition strategy/grid, halo, ELL vs
    padded-CSR, precise gather, rows-per-shard, banded prepack): two
    DistCSRs with equal fingerprints reuse one compiled distributed
    program, and the engine's ``dist_spmv`` plan entries record
    exactly that reuse."""
    precise = A.gather_idx is not None
    grid = "-" if A.grid is None else f"{A.grid[0]}x{A.grid[1]}"
    return (f"{mesh_fingerprint(A.mesh, layout=A.layout)}"
            f":h{A.halo}:e{int(A.ell)}"
            f":p{int(precise)}:r{A.rows_per_shard}"
            f":d{int(A.dia_data is not None)}"
            f":t{A.pdia_tile}:g{grid}")


def _halo_exchange(x_local, halo: int, axis: int = 0):
    """Halo exchange: ppermute boundary slices to/from ring neighbors
    along ``axis`` of the local block.  Returns ``(from_left,
    from_right)``: the left neighbor's last ``halo`` slices and the
    right neighbor's first ``halo`` (ring-wrapped at the mesh's ends).

    Structurally the ring/context-parallel neighbor pattern: each shard
    never materializes the global x — this is what makes 1e8-row weak
    scaling possible where ``all_gather`` would not (SURVEY §7 hard
    part #4).
    """
    axis_size = jax.lax.axis_size(ROW_AXIS)
    right_perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    left_perm = [(i, (i - 1) % axis_size) for i in range(axis_size)]
    n = x_local.shape[axis]
    tail = jax.lax.slice_in_dim(x_local, n - halo, n, axis=axis)
    head = jax.lax.slice_in_dim(x_local, 0, halo, axis=axis)
    from_left = jax.lax.ppermute(tail, ROW_AXIS, right_perm)
    from_right = jax.lax.ppermute(head, ROW_AXIS, left_perm)
    return from_left, from_right


def _extend_x(x_local, halo: int, axis: int = 0):
    """The halo-extended window ``[from_left, x_local, from_right]``
    along ``axis`` (``_halo_exchange``): a fresh buffer ``2 * halo``
    longer than the local block."""
    if halo <= 0:
        return x_local
    from_left, from_right = _halo_exchange(x_local, halo, axis)
    return jnp.concatenate([from_left, x_local, from_right], axis=axis)


@lru_cache(maxsize=256)
def _dia_spmv_fn(mesh: Mesh, offsets: Tuple[int, ...], halo: int,
                 rps: int, n_rows: int, has_mask: bool):
    """Cached shard_map callable for the banded dist SpMV (XLA
    shifted-add branch).

    Structure-keyed caching is the Legion partition-cache analog: a
    fresh closure per call would be a new jit identity, so repeated
    direct ``dist_spmv`` calls (microbenchmarks, user loops outside
    ``dist_cg``) would re-trace and recompile every time.
    """
    _obs.inc("jit_miss.dist_csr.dia_spmv_fn")
    def dia_kernel(ddata, x_local, *rest):
        x_ext = _extend_x(x_local, halo)
        dd = ddata[0]                               # (nd, rps)
        dm = rest[0][0] if has_mask else None
        shard = jax.lax.axis_index(ROW_AXIS)
        r_g = shard.astype(index_dtype()) * rps + jnp.arange(
            rps, dtype=index_dtype()
        )
        y = jnp.zeros((rps,), dtype=dd.dtype)
        for d, o in enumerate(offsets):
            seg = jax.lax.slice_in_dim(
                x_ext, halo + o, halo + o + rps
            )
            # Mask *products* outside the matrix (and band holes in
            # masked mode): ring-wrapped halo values, padding rows
            # and holes carry weight 0, but 0*inf must not inject
            # NaN (same IEEE invariant as ell_spmv).
            if has_mask:
                valid = dm[d]
            else:
                valid = jnp.logical_and(
                    jnp.logical_and(r_g + o >= 0, r_g + o < n_rows),
                    r_g < n_rows,
                )
            y = y + jnp.where(valid, dd[d] * seg,
                              jnp.zeros((), dd.dtype))
        return y

    in_specs = (P(ROW_AXIS, None, None), P(ROW_AXIS)) + (
        (P(ROW_AXIS, None, None),) if has_mask else ()
    )
    # jit wrapper: shard_map alone re-lowers per call; under jit the
    # compiled executable is cached on (this fn, shapes).
    return jax.jit(shard_map(
        dia_kernel, mesh=mesh, in_specs=in_specs,
        out_specs=P(ROW_AXIS), check_vma=False,
    ))


def _dia_window(rps: int, halo: int, tile: int) -> str:
    """How the banded Pallas dist SpMV's shard body hands x to the
    kernel: ``"in-place"`` when the shard's rows are a whole number of
    tiles and the halo fits in one tile, else ``"copied"`` into the
    halo-extended window (``_dia_spmv_pallas_fn``)."""
    return "in-place" if rps % tile == 0 and halo <= tile else "copied"


@lru_cache(maxsize=256)
def _dia_spmv_pallas_fn(mesh: Mesh, offsets: Tuple[int, ...], halo: int,
                        rps: int, tile: int, interpret: bool):
    """Cached shard_map callable for the banded dist SpMV through the
    per-shard Mosaic kernel over the **pre-blocked** layout
    (``DistCSR.pdia_data``, built once at ``shard_csr`` time — the
    cached-partition analog): the shard body is one halo exchange plus
    one ``pallas_dia_spmv`` call, zero packing.

    The kernel takes x in one of two ways (``_dia_window``).  In place:
    the kernel reads ``x_local`` where it lies, with the band's own
    offsets, and the two halos ride as edge tiles — ``from_left`` at the
    end of a zero tile below the shard, ``from_right`` at the start of
    one above it (``pallas_dia_spmv``'s ``edges``).  Copied: x and its
    halos are concatenated into a window ``2 * halo`` longer, whose
    local problem is a rectangular band with offsets shifted by +halo.
    Either way each slot multiplies the same x element in the same
    diagonal order, so the two give the same bits.  Each build counts
    its route under ``dist_csr.dia_window.in_place`` / ``.copied``.

    Global bounds, ring-wrap slots, padding rows and band holes are
    +0.0 in the pack and every explicit entry has nonzero bits (an
    explicit +0.0 is stored as -0.0), so the kernel multiplies entries
    only: a non-finite value in a wrapped halo or in the edge tiles'
    zero fill injects nothing, and IEEE non-finite-x semantics match
    the XLA branch exactly (``attach_dia_prepack``).  The one
    difference is the sign of a zero: a row whose every live product
    is -0.0 reads -0.0 here and +0.0 in the XLA branch (the compiled
    kernel folds its +0.0 accumulator start).  The shard body runs
    inside shard_map's trace, so a Mosaic compile failure surfaces at
    the outer compile — callers gate on ``supported()`` having produced
    the prepack and on result-dtype equality.
    """
    _obs.inc("jit_miss.dist_csr.dia_spmv_pallas_fn")
    from ..ops.pallas_dia import L as _LANES
    from ..ops.pallas_dia import pallas_dia_spmv

    nd = len(offsets)
    in_place = _dia_window(rps, halo, tile) == "in-place"
    _obs.inc("dist_csr.dia_window.in_place" if in_place
             else "dist_csr.dia_window.copied")

    def edge_tiles(x_local):
        from_left, from_right = _halo_exchange(x_local, halo)
        lo = jnp.pad(from_left, (tile - halo, 0))
        hi = jnp.pad(from_right, (0, tile - halo))
        return lo.reshape(-1, _LANES), hi.reshape(-1, _LANES)

    def dia_kernel(pdata, x_local):
        band = pdata[0].reshape(nd, -1, _LANES)
        if in_place:
            return pallas_dia_spmv(
                band, x_local, offsets, (rps, rps), tile,
                interpret=interpret, edges=edge_tiles(x_local),
            )
        x_ext = _extend_x(x_local, halo)
        return pallas_dia_spmv(
            band, x_ext, tuple(int(o) + halo for o in offsets),
            (rps, x_ext.shape[0]), tile, interpret=interpret,
        )

    in_specs = (P(ROW_AXIS, None, None), P(ROW_AXIS))
    return jax.jit(shard_map(
        dia_kernel, mesh=mesh, in_specs=in_specs,
        out_specs=P(ROW_AXIS), check_vma=False,
    ))


@lru_cache(maxsize=256)
def _block_spmv_fn(mesh: Mesh, halo: int, precise: bool, ell: bool,
                   rps: int):
    """Cached shard_map callable for the ELL / padded-CSR dist SpMV
    (see ``_dia_spmv_fn`` for why caching matters)."""
    _obs.inc("jit_miss.dist_csr.block_spmv_fn")
    from ..ops import spmv as _spmv_ops

    def realize(x_local, gidx_local=None):
        """Per-shard x realization: precise all_to_all gather, halo
        ppermute, or tiled all_gather — the three image strategies."""
        if precise:
            parts = x_local[gidx_local]            # (R_dst, C) to send
            recv = jax.lax.all_to_all(
                parts, ROW_AXIS, split_axis=0, concat_axis=0, tiled=True
            )
            # pos = t*C + rank for off-shard cols; own block appended.
            return jnp.concatenate([recv.reshape(-1), x_local])
        if halo >= 0:
            return _extend_x(x_local, halo)
        return jax.lax.all_gather(x_local, ROW_AXIS, tiled=True)

    if ell:
        if precise:
            def kernel(data, cols, counts, gidx, x_local):
                x_src = realize(x_local, gidx[0])
                return _spmv_ops.ell_spmv(data[0], cols[0], counts[0], x_src)

            in_specs = (P(ROW_AXIS, None, None), P(ROW_AXIS, None, None),
                        P(ROW_AXIS, None), P(ROW_AXIS, None, None),
                        P(ROW_AXIS))
        else:
            def kernel(data, cols, counts, x_local):
                x_src = realize(x_local)
                return _spmv_ops.ell_spmv(data[0], cols[0], counts[0], x_src)

            in_specs = (P(ROW_AXIS, None, None), P(ROW_AXIS, None, None),
                        P(ROW_AXIS, None), P(ROW_AXIS))
    else:
        if precise:
            def kernel(data, cols, row_ids, counts, gidx, x_local):
                x_src = realize(x_local, gidx[0])
                return _spmv_ops.csr_spmv_rowids_masked(
                    data[0], cols[0], row_ids[0], counts[0], x_src, rps
                )

            in_specs = (P(ROW_AXIS, None), P(ROW_AXIS, None),
                        P(ROW_AXIS, None), P(ROW_AXIS),
                        P(ROW_AXIS, None, None), P(ROW_AXIS))
        else:
            def kernel(data, cols, row_ids, counts, x_local):
                x_src = realize(x_local)
                return _spmv_ops.csr_spmv_rowids_masked(
                    data[0], cols[0], row_ids[0], counts[0], x_src, rps
                )

            in_specs = (P(ROW_AXIS, None), P(ROW_AXIS, None),
                        P(ROW_AXIS, None), P(ROW_AXIS), P(ROW_AXIS))
    return jax.jit(shard_map(
        kernel, mesh=mesh, in_specs=in_specs, out_specs=P(ROW_AXIS),
        check_vma=False,
    ))


def _transpose_perm(grid: Tuple[int, int]) -> Tuple[Tuple[int, int], ...]:
    """The chunk-transpose ppermute over the flattened (rows, cols)
    grid: device l = i*Rc + j must end up holding vector chunk
    k = j*Rr + i, so chunk k (living on device k) goes to linear
    destination (k % Rr) * Rc + k // Rr.  Identity (no collective
    emitted) when either grid axis is 1."""
    Rr, Rc = grid
    n = Rr * Rc
    return tuple((k, (k % Rr) * Rc + k // Rr) for k in range(n))


@lru_cache(maxsize=256)
def _block_spmv_2d_fn(mesh: Mesh, grid: Tuple[int, int], rps: int,
                      lowp: bool = False):
    """Cached shard_map callable for the 2-d-block dist SpMV: the
    communication-avoiding program the layout exists for —

    1. chunk-transpose ``ppermute`` over the flattened grid (input
       fixup; elided on degenerate 1-D grids),
    2. tiled ``all_gather`` along MESH ROWS only — x replicated per
       mesh column (the panel each block's columns read), never
       globally,
    3. local padded-CSR SpMV of block (i, j) against its panel,
    4. tiled ``psum_scatter`` along MESH COLUMNS — partial row-block
       products reduced and scattered straight into the row-major
       output chunks, half the bytes of a full ``psum``.

    ``lowp`` (bf16/f16 block values) swaps step 3 for the
    f32-accumulation kernel: partial products sum in f32 and narrow
    back to ``result_type(A, x)`` BEFORE the psum_scatter, so a bf16
    x panel moves half the bytes on every collective while the
    per-block reduction keeps f32 grade.  The flag is part of the
    lru_cache key — one compiled program per storage class.
    """
    _obs.inc("jit_miss.dist_csr.block_spmv_2d_fn")
    from ..ops import spmv as _spmv_ops

    Rr, Rc = grid
    perm = _transpose_perm(grid)
    skip_perm = all(s == d for s, d in perm)
    local_spmv = (_spmv_ops.csr_spmv_rowids_masked_f32acc if lowp
                  else _spmv_ops.csr_spmv_rowids_masked)

    def kernel(data, cols, row_ids, counts, x_local):
        if not skip_perm:
            x_local = jax.lax.ppermute(
                x_local, (ROW_AXIS, COL_AXIS), perm
            )
        x_panel = jax.lax.all_gather(x_local, ROW_AXIS, tiled=True)
        y_part = local_spmv(
            data[0, 0], cols[0, 0], row_ids[0, 0], counts[0, 0],
            x_panel, rps,
        )
        return jax.lax.psum_scatter(
            y_part, COL_AXIS, scatter_dimension=0, tiled=True
        )

    in_specs = (P(ROW_AXIS, COL_AXIS, None), P(ROW_AXIS, COL_AXIS, None),
                P(ROW_AXIS, COL_AXIS, None), P(ROW_AXIS, COL_AXIS),
                P((ROW_AXIS, COL_AXIS)))
    return jax.jit(shard_map(
        kernel, mesh=mesh, in_specs=in_specs,
        out_specs=P((ROW_AXIS, COL_AXIS)), check_vma=False,
    ))


@lru_cache(maxsize=128)
def _block_semiring_spmv_fn(mesh: Mesh, halo: int, precise: bool,
                            ell: bool, rps: int, add: str, mul: str,
                            prefix: bool):
    """Cached shard_map callable for the semiring dist SpMV over ELL /
    padded-CSR blocks: the ``_block_spmv_fn`` program with the local
    kernel generalized to the (add, mul) pair (graph/semiring.py).
    The x realization (precise all_to_all / halo ppermute / tiled
    all_gather) is semiring-independent — on 1-d layouts output rows
    live with the row partition, so no cross-shard output reduction
    exists and the collectives are byte-identical to plus-times.
    ``prefix``: the padded-CSR kernel takes the shard's ``row_ptr``
    (after ``counts``) and reduces rows by a prefix count."""
    _obs.inc("jit_miss.dist_csr.block_semiring_spmv_fn")
    from ..ops import spmv as _spmv_ops

    def realize(x_local, gidx_local=None):
        if precise:
            parts = x_local[gidx_local]
            recv = jax.lax.all_to_all(
                parts, ROW_AXIS, split_axis=0, concat_axis=0, tiled=True
            )
            return jnp.concatenate([recv.reshape(-1), x_local])
        if halo >= 0:
            return _extend_x(x_local, halo)
        return jax.lax.all_gather(x_local, ROW_AXIS, tiled=True)

    if ell:
        if precise:
            def kernel(data, cols, counts, gidx, x_local):
                x_src = realize(x_local, gidx[0])
                return _spmv_ops.ell_semiring_spmv(
                    data[0], cols[0], counts[0], x_src, add, mul)

            in_specs = (P(ROW_AXIS, None, None), P(ROW_AXIS, None, None),
                        P(ROW_AXIS, None), P(ROW_AXIS, None, None),
                        P(ROW_AXIS))
        else:
            def kernel(data, cols, counts, x_local):
                x_src = realize(x_local)
                return _spmv_ops.ell_semiring_spmv(
                    data[0], cols[0], counts[0], x_src, add, mul)

            in_specs = (P(ROW_AXIS, None, None), P(ROW_AXIS, None, None),
                        P(ROW_AXIS, None), P(ROW_AXIS))
    else:
        def kernel(data, cols, row_ids, counts, *rest):
            row_ptr = rest[0][0] if prefix else None
            gidx = rest[-2][0] if precise else None
            x_src = realize(rest[-1], gidx)
            return _spmv_ops.csr_semiring_spmv_rowids_masked(
                data[0], cols[0], row_ids[0], counts[0], x_src,
                rps, add, mul, row_ptr=row_ptr)

        in_specs = (P(ROW_AXIS, None), P(ROW_AXIS, None),
                    P(ROW_AXIS, None), P(ROW_AXIS)) + (
            (P(ROW_AXIS, None),) if prefix else ()) + (
            (P(ROW_AXIS, None, None),) if precise else ()) + (
            P(ROW_AXIS),)
    return jax.jit(shard_map(
        kernel, mesh=mesh, in_specs=in_specs, out_specs=P(ROW_AXIS),
        check_vma=False,
    ))


@lru_cache(maxsize=128)
def _block_semiring_spmv_2d_fn(mesh: Mesh, grid: Tuple[int, int],
                               rps: int, add: str, mul: str,
                               prefix: bool):
    """Cached shard_map callable for the 2-d-block semiring dist SpMV.

    Steps 1-3 are ``_block_spmv_2d_fn`` verbatim (chunk-transpose
    ppermute, x panel all_gather along mesh rows, local semiring
    kernel).  Step 4 is where the semiring changes the wire program:
    ``psum_scatter`` only exists for sum, so the partial row blocks
    reduce with the semiring's add ALL-reduce along mesh columns
    (``jax.lax.pmin``/``pmax`` — lowered as a min/max ``all_reduce``)
    and each device then slices its own output chunk locally.  Ring
    cost is 2*(Rc-1)*rps elements per row group — twice the
    reduce-scatter half — priced under the semiring's collective kind
    (``comm.dist_spmv.pmin``/``pmax``/``por``).  ``prefix``: the block
    kernel takes the block's ``row_ptr`` (after ``counts``) and reduces
    rows by a prefix count."""
    _obs.inc("jit_miss.dist_csr.block_semiring_spmv_2d_fn")
    from ..ops import spmv as _spmv_ops

    Rr, Rc = grid
    perm = _transpose_perm(grid)
    skip_perm = all(s == d for s, d in perm)
    reduce_op = {"min": jax.lax.pmin, "max": jax.lax.pmax}[add]
    chunk = rps // Rc

    def kernel(data, cols, row_ids, counts, *rest):
        x_local = rest[-1]
        if not skip_perm:
            x_local = jax.lax.ppermute(
                x_local, (ROW_AXIS, COL_AXIS), perm
            )
        x_panel = jax.lax.all_gather(x_local, ROW_AXIS, tiled=True)
        y_part = _spmv_ops.csr_semiring_spmv_rowids_masked(
            data[0, 0], cols[0, 0], row_ids[0, 0], counts[0, 0],
            x_panel, rps, add, mul,
            row_ptr=rest[0][0, 0] if prefix else None,
        )
        y_full = reduce_op(y_part, COL_AXIS)
        j = jax.lax.axis_index(COL_AXIS)
        return jax.lax.dynamic_slice_in_dim(y_full, j * chunk, chunk)

    in_specs = (P(ROW_AXIS, COL_AXIS, None), P(ROW_AXIS, COL_AXIS, None),
                P(ROW_AXIS, COL_AXIS, None), P(ROW_AXIS, COL_AXIS)) + (
        (P(ROW_AXIS, COL_AXIS, None),) if prefix else ()) + (
        P((ROW_AXIS, COL_AXIS)),)
    return jax.jit(shard_map(
        kernel, mesh=mesh, in_specs=in_specs,
        out_specs=P((ROW_AXIS, COL_AXIS)), check_vma=False,
    ))


# The distributed plan shapes this module can lower, as static
# (entry point, layout, realization) triples — enumerable WITHOUT
# devices or meshes, so the contract gates (``tools/verify`` and the
# sparselint ``plan-contract`` rule) can walk the catalog at
# import/AST time.  Every triple names one distinct lowered program
# family: the realization axis is the collective structure the
# dispatch branches on (``_dist_spmv_impl``), not a tuning knob.
# ``dist_cg``/``dist_gmres`` cover the solver iteration/cycle bodies
# over the corresponding SpMV realization ("1d-col" is the (1, R)
# degenerate grid of the 2-d panel program and adds no distinct
# solver body).  Grow this tuple when a new dispatch branch lands —
# the plan-contract rule fails until its contract is committed.
DIST_PLAN_SHAPES: Tuple[Tuple[str, str, str], ...] = (
    ("dist_spmv", "1d-row", "halo"),
    ("dist_spmv", "1d-row", "all_gather"),
    ("dist_spmv", "1d-row", "precise"),
    ("dist_spmv", "1d-col", "panel"),
    ("dist_spmv", "2d-block", "panel"),
    ("dist_spmv_semiring", "1d-row", "halo"),
    ("dist_spmv_semiring", "1d-row", "all_gather"),
    ("dist_spmv_semiring", "1d-row", "precise"),
    ("dist_spmv_semiring", "1d-col", "panel"),
    ("dist_spmv_semiring", "2d-block", "panel"),
    ("dist_spmm", "1d-row", "halo"),
    ("dist_spmm_semiring", "1d-row", "halo"),
    ("dist_cg", "1d-row", "halo"),
    ("dist_cg", "2d-block", "panel"),
    ("dist_gmres", "1d-row", "halo"),
    ("dist_reshard", "1d-row", "chunk-permute"),
)


def spmv_comm_volumes(A: DistCSR, x_local_elems: int, itemsize: int,
                      cols: int = 1):
    """Per-call collective interconnect volumes of one ``dist_spmv``
    (or ``dist_spmm`` with ``cols`` > 1) on ``A`` — the realization
    choice (2-d panel broadcast + reduce-scatter / precise all_to_all /
    halo ppermute / tiled all_gather) read from the same static fields
    the dispatch branches on, priced by ``obs.comm``.
    ``x_local_elems`` is the per-device x block size (already
    including ``cols`` for dense operands)."""
    from ..obs import comm as _comm

    if A.grid is not None:
        return _comm.spmv_volumes_2d(
            grid_rows=A.grid[0], grid_cols=A.grid[1],
            spc=x_local_elems, rps=A.rows_per_shard, itemsize=itemsize,
        )
    precise_C = (int(A.gather_idx.shape[-1])
                 if A.gather_idx is not None else None)
    return _comm.spmv_volumes(
        shards=A.num_shards, halo=A.halo, precise_C=precise_C,
        x_local_elems=x_local_elems, itemsize=itemsize, cols=cols,
    )


def cg_comm_volumes(A: DistCSR, itemsize: int, iters: int):
    """Predicted interconnect volumes of an ``iters``-iteration
    distributed CG on ``A``, mirroring the fused ``_cg_loop`` program
    exactly: ``iters + 1`` SpMV realizations (the initial residual
    plus one per iteration) and three scalar psums per iteration
    (rho, pq, and the unconditional residual-norm vdot — see
    ``obs.comm.cg_iteration_volumes``).  Returns ``(vols, calls)`` —
    bytes and collective-op counts per kind (a two-sided halo exchange
    counts as one collective phase).  Shared by the ``dist_cg`` ledger
    and ``bench.py``'s dist phase."""
    from ..obs import comm as _comm

    R = A.num_shards
    spmv = spmv_comm_volumes(A, A.rows_padded // R, itemsize)
    per_iter = _comm.cg_iteration_volumes(spmv, itemsize, R)
    vols = _comm.merge(_comm.scale(per_iter, iters), spmv)
    calls = {k: iters + 1 for k in spmv}
    # Additive, not an overwrite: the 2-d-block SpMV realization already
    # carries a "psum" entry (its psum_scatter output reduction) that
    # the scalar-reduction count must stack on top of.
    calls["psum"] = calls.get("psum", 0) + 3 * iters
    return vols, calls


def semiring_spmv_comm_volumes(A: DistCSR, x_itemsize: int,
                               y_itemsize: int, collective: str,
                               cols: int = 1):
    """Per-call collective volumes of one semiring ``dist_spmv`` (or
    ``dist_spmm`` with ``cols`` > 1) on ``A``.  1-d layouts realize x
    exactly as plus-times (no output collective exists), so the
    volumes are ``spmv_comm_volumes`` at the x itemsize; 2-d-block
    swaps the psum_scatter for the semiring add all-reduce
    (``obs.comm.spmv_volumes_2d_semiring``)."""
    from ..obs import comm as _comm

    x_local = A.rows_padded // A.num_shards
    if A.grid is not None:
        return _comm.spmv_volumes_2d_semiring(
            grid_rows=A.grid[0], grid_cols=A.grid[1],
            spc=x_local, rps=A.rows_per_shard,
            x_itemsize=x_itemsize, y_itemsize=y_itemsize,
            collective=collective,
        )
    precise_C = (int(A.gather_idx.shape[-1])
                 if A.gather_idx is not None else None)
    return _comm.spmv_volumes(
        shards=A.num_shards, halo=A.halo, precise_C=precise_C,
        x_local_elems=x_local * max(cols, 1), itemsize=x_itemsize,
        cols=max(cols, 1),
    )


def _takes_prefix_or(A: DistCSR, sr, x_dtype) -> bool:
    """Whether a semiring dispatch on ``A`` reduces rows by the prefix
    count: padded-CSR blocks with stored row bounds, and a boolean OR
    (``ops.spmv.is_boolean_or``).  Counted once a dispatch in
    ``graph.dist_spmv.prefix_or``."""
    from ..ops.spmv import is_boolean_or

    prefix = (not A.ell and A.row_ptr is not None
              and is_boolean_or(sr.add, sr.mul, A.dtype, x_dtype))
    if prefix:
        _obs.inc("graph.dist_spmv.prefix_or")
    return prefix


def _dist_spmv_semiring(A: DistCSR, x: jax.Array, sr) -> jax.Array:
    """Semiring arm of ``dist_spmv`` (``sr`` a resolved non-plus-times
    :class:`~..graph.semiring.Semiring`): same accounting discipline
    as ``_dist_spmv_impl`` — comm volumes priced from static fields
    before dispatch, span with realization path and row reduction
    (``reduce``: "prefix", "segment", or "row" on ELL blocks) — plus
    the ``graph.*`` ledger row for the semiring family.
    Structure-specialized plus-times paths (DIA/BSR) don't generalize,
    so dispatch goes straight to the ELL / padded-CSR block
    programs."""
    _obs.inc("op.dist_spmv")
    _obs.inc("graph.dist_spmv." + sr.name)
    from ..obs import comm as _comm

    x_item = jnp.dtype(x.dtype).itemsize
    y_item = (1 if sr.mul == "and"
              else jnp.dtype(jnp.result_type(A.dtype, x.dtype)).itemsize)
    vols = semiring_spmv_comm_volumes(A, x_item, y_item, sr.collective)
    comm_bytes = _comm.record("dist_spmv", vols, layout=A.layout)
    with _tctx.profiler_scope("dist_spmv"), \
            _lat.timer("lat.dist_spmv."
                       + _lat.shape_bucket(A.shape[0])), \
            _obs.span("dist_spmv", shards=A.num_shards, halo=A.halo,
                      comm_bytes=comm_bytes,
                      comm_calls=sum(1 for b in vols.values() if b > 0)
                      ) as sp:
        A._require_blocks("dist_spmv")
        prefix = _takes_prefix_or(A, sr, x.dtype)
        reduce = "row" if A.ell else ("prefix" if prefix else "segment")
        bounds = (A.row_ptr,) if prefix else ()
        if A.grid is not None:
            fn = _block_semiring_spmv_2d_fn(
                A.mesh, A.grid, A.rows_per_shard, sr.add, sr.mul,
                prefix)
            if sp is not None:
                sp.set(path="2d-block", layout=A.layout,
                       semiring=sr.name, reduce=reduce)
            return fn(A.data, A.cols, A.row_ids, A.counts, *bounds, x)
        precise = A.gather_idx is not None
        fn = _block_semiring_spmv_fn(
            A.mesh, A.halo, precise, A.ell, A.rows_per_shard,
            sr.add, sr.mul, prefix)
        if A.ell:
            args = (A.data, A.cols, A.counts)
        else:
            args = (A.data, A.cols, A.row_ids, A.counts) + bounds
        args += ((A.gather_idx,) if precise else ()) + (x,)
        if sp is not None:
            sp.set(path="ell" if A.ell else "padded-csr",
                   precise=precise, semiring=sr.name, reduce=reduce)
        return fn(*args)


def _resolve_semiring_arg(semiring):
    """None for plus-times/absent (the standard program IS that
    semiring), else the resolved catalog entry."""
    if semiring is None:
        return None
    from ..graph.semiring import resolve as _resolve_sr

    sr = _resolve_sr(semiring)
    if sr.add == "sum" and sr.mul == "times":
        return None
    return sr


def dist_spmv(A: DistCSR, x: jax.Array, semiring=None) -> jax.Array:
    """y = A (x) with row-block parallelism (jittable).

    ``semiring`` generalizes the product to any catalog entry
    (``graph/semiring.py``): ``None``/"plus-times" runs the standard
    y = A @ x program below; other semirings dispatch the generalized
    block kernels, with the 2-d-block cross-shard reduction swapped
    for the semiring's add collective (psum -> pmin/pmax/por) — see
    docs/GRAPH.md.

    ``x`` and the result are row-block sharded vectors of length
    ``A.rows_padded``.  The distribution contract matches the reference
    SpMV task (``csr.py:562-593``): y aligned with the row partition,
    x gathered per the column image (halo ppermute or all_gather).
    The underlying shard_map computations are structure-cached, so
    repeated calls on the same matrix structure reuse one compilation.

    Resilience (``LEGATE_SPARSE_TPU_RESIL``, docs/RESILIENCE.md):
    eager dispatches run under the ``dist.spmv`` site policy —
    injectable, and transient collective failures retried with
    backoff.  Calls staged inside an ambient trace (solver loops via
    ``matvec_fn``) bypass the wrapper: a retry there would re-stage
    the traced program, and the driver-level sites (``dist.cg``,
    ``solver.*.conv``) own recovery for those.
    """
    sr = _resolve_semiring_arg(semiring)
    if sr is not None:
        # ABFT's checksum identity sum(y) = <w, x> is plus-times
        # algebra; semiring dispatches retry under the same site
        # policy but run unverified.
        if _rsettings.resil and csr_array._can_build_cache(x):
            return _resil_guarded(
                "dist.spmv", lambda: _dist_spmv_semiring(A, x, sr))
        return _dist_spmv_semiring(A, x, sr)
    if _rsettings.resil and csr_array._can_build_cache(x):
        if _rsettings.resil_abft:
            return _resil_guarded("dist.spmv",
                                  lambda: _dist_spmv_abft(A, x))
        return _resil_guarded("dist.spmv",
                              lambda: _dist_spmv_impl(A, x))
    return _dist_spmv_impl(A, x)


def _abft_checksum_vector(A: DistCSR, xlen: int):
    """The sharded column-checksum vector w (w_j = sum_i A_ij) an
    ABFT-verified SpMV dots against x, built once per matrix from the
    retained host source and cached on ``A``.  None when the matrix
    cannot carry one (no retained source, or non-square — the padded
    x and y lengths then differ and the identity sum(y) = <w, x> has
    no shared sharding)."""
    cached = getattr(A, "_abft_w", None)
    if cached is not None and cached[0] == xlen:
        return cached[1]
    src = getattr(A, "_src_csr", None)
    rows, cols = A.shape
    if src is None or rows != cols:
        return None
    wv = np.zeros(cols, dtype=np.float64)
    np.add.at(wv, np.asarray(src.indices),
              np.asarray(src.data, dtype=np.float64))
    w = shard_vector(jnp.asarray(wv, dtype=A.dtype), A.mesh, xlen,
                     layout=A.layout)
    A._abft_w = (xlen, w)
    return w


def _dist_spmv_abft(A: DistCSR, x: jax.Array) -> jax.Array:
    """Opt-in ABFT-checksummed eager SpMV (``settings.resil_abft``):
    carry the column checksum w through the dispatch and verify
    sum(y) = <w, x> at the fetch.  The comparison tolerance scales
    with <|w|, |x|> (the condition of the checksum sum), and the
    NaN-safe ``not (diff <= tol)`` form turns a poisoned y into a
    detection rather than a silent pass.  A mismatch raises the
    retryable :class:`~..resilience.outcomes.ChecksumError` — the
    ``dist.spmv`` policy site re-dispatches from the intact operands,
    turning a corrupted collective into a typed, counted retry.
    Matrices without a checksum vector run unverified (documented in
    docs/RESILIENCE.md; traced solver loops are covered by the
    conv-fetch health monitors instead)."""
    w = _abft_checksum_vector(A, int(x.shape[0]))
    y = _dist_spmv_impl(A, x)
    if w is None:
        return y
    # Value-carrying drill site: a nonfinite arm poisons y exactly as
    # a corrupted collective would.
    y = _rfaults.fault_point("dist.spmv.abft", y)
    stats = jnp.stack([jnp.sum(y), jnp.vdot(w, x),
                       jnp.vdot(jnp.abs(w), jnp.abs(x))])
    observed, expected, scale = (float(v) for v in np.asarray(stats))
    eps = float(jnp.finfo(jnp.result_type(A.dtype, x.dtype)).eps)
    tol = 64.0 * eps * (abs(scale) + 1.0)
    _obs.inc("resil.abft.checks")
    if not (abs(observed - expected) <= tol):
        _obs.inc("resil.abft.mismatch")
        _obs.event("resil.abft.mismatch", observed=observed,
                   expected=expected, tol=tol)
        raise ChecksumError("dist.spmv.abft", observed, expected)
    return y


def _dist_spmv_impl(A: DistCSR, x: jax.Array) -> jax.Array:
    halo = A.halo
    precise = A.gather_idx is not None
    _obs.inc("op.dist_spmv")
    # Engine plan ledger (docs/ENGINE.md): with routing enabled, every
    # production dist dispatch records against its plan identity (mesh
    # fingerprint + layout + dtype + epoch) — the reuse evidence for
    # the lru_cache'd shard_map programs below.  Disabled (default),
    # this is one flag read.
    if _engine_enabled():
        _get_engine().record_dist_plan(A)
    # Comm ledger: the realization (and so the collective volume) is a
    # function of A's static fields alone — price it once per dispatch
    # and account it whatever kernel branch runs below.
    from ..obs import comm as _comm

    vols = spmv_comm_volumes(
        A, int(x.shape[0]) // A.num_shards,
        jnp.dtype(x.dtype).itemsize,
    )
    comm_bytes = _comm.record("dist_spmv", vols, layout=A.layout)

    # Obs v4: a request-scoped dispatch (the trace context set by the
    # gateway/executor) auto-tags this span with its trace id AND
    # annotates the jax.profiler timeline (dist_spmv[<trace-id>]), so
    # a future on-TPU profiler capture joins obs flow arcs to XLA
    # rows.  Without a context both are no-ops.
    with _tctx.profiler_scope("dist_spmv"), \
            _lat.timer("lat.dist_spmv."
                       + _lat.shape_bucket(A.shape[0])), \
            _obs.span("dist_spmv", shards=A.num_shards, halo=halo,
                      comm_bytes=comm_bytes,
                      comm_calls=sum(1 for b in vols.values() if b > 0)
                      ) as sp:
        if A.grid is not None:
            lowp = str(A.dtype) in ("bfloat16", "float16")
            fn = _block_spmv_2d_fn(A.mesh, A.grid, A.rows_per_shard,
                                   lowp)
            if sp is not None:
                sp.set(path="2d-block-bf16" if lowp else "2d-block",
                       layout=A.layout)
            return fn(A.data, A.cols, A.row_ids, A.counts, x)

        if A.dia_data is not None and halo >= 0 and not precise:
            # Banded fast path: halo exchange + static shifted-adds,
            # zero gathers (per-shard analog of ``ops.dia_ops.dia_spmv``).
            from ..ops.pallas_dia import pallas_dist_mode

            mode = pallas_dist_mode()
            if (mode != "0" and A.pdia_tile
                    and jnp.result_type(A.dtype, x.dtype) == A.dtype):
                # Mosaic route over the pre-blocked layout (default on
                # TPU).  The dtype gate keeps promotion semantics (e.g.
                # bf16 matrix * f32 x -> f32) identical to the XLA
                # branch.
                fn = _dia_spmv_pallas_fn(
                    A.mesh, A.dia_offsets, halo, A.rows_per_shard,
                    A.pdia_tile, mode == "interpret",
                )
                if sp is not None:
                    sp.set(path="dia-pallas", window=_dia_window(
                        A.rows_per_shard, halo, A.pdia_tile))
                return fn(A.pdia_data, x)
            has_mask = A.dia_mask is not None
            fn = _dia_spmv_fn(
                A.mesh, A.dia_offsets, halo, A.rows_per_shard,
                A.shape[0], has_mask,
            )
            args = (A.dia_data, x) + ((A.dia_mask,) if has_mask else ())
            if sp is not None:
                sp.set(path="dia-xla")
            return fn(*args)

        A._require_blocks("dist_spmv")
        if not A.bsr_tried and A.bsr_blocks is None:
            # Lazy build on first SpMV (mirrors csr_array._get_bsr):
            # other consumers (dist_spmm/dist_spgemm) never pay the
            # densification.
            attach_bsr_prepack(A)
        if (A.bsr_blocks is not None
                and jnp.result_type(A.dtype, x.dtype) == A.dtype):
            from ..ops.pallas_dia import pallas_dist_mode

            mode = pallas_dist_mode()
            if mode != "0":
                nbr, nbc = A.bsr_grid
                fn = _bsr_spmv_dist_fn(
                    A.mesh, A.rows_per_shard, nbr, nbc,
                    mode == "interpret",
                )
                if sp is not None:
                    sp.set(path="bsr")
                return fn(A.bsr_blocks, A.bsr_brow, A.bsr_bcol, x)
        fn = _block_spmv_fn(A.mesh, halo, precise, A.ell,
                            A.rows_per_shard)
        if A.ell:
            args = (A.data, A.cols, A.counts) + (
                (A.gather_idx,) if precise else ()
            ) + (x,)
        else:
            args = (A.data, A.cols, A.row_ids, A.counts) + (
                (A.gather_idx,) if precise else ()
            ) + (x,)
        if sp is not None:
            sp.set(path="ell" if A.ell else "padded-csr",
                   precise=precise)
        return fn(*args)


def shard_dense(X, mesh: Mesh, rows_padded: int) -> jax.Array:
    """Pad and shard a dense (rows, k) operand: rows over the "rows"
    axis; columns over the "cols" axis too when ``mesh`` is a 2-D grid
    (k padded to a multiple of the grid's column count)."""
    X = jnp.asarray(X)
    pad_r = rows_padded - X.shape[0]
    if pad_r:
        X = jnp.concatenate(
            [X, jnp.zeros((pad_r, X.shape[1]), X.dtype)]
        )
    if COL_AXIS in mesh.shape:
        C = int(mesh.shape[COL_AXIS])
        pad_c = (-X.shape[1]) % C
        if pad_c:
            X = jnp.concatenate(
                [X, jnp.zeros((X.shape[0], pad_c), X.dtype)], axis=1
            )
        return _device_put_sharded(
            X, NamedSharding(mesh, P(ROW_AXIS, COL_AXIS)))
    return _device_put_sharded(X, NamedSharding(mesh, P(ROW_AXIS, None)))


@lru_cache(maxsize=128)
def _block_spmm_fn(mesh: Mesh, halo: int, precise: bool, ell: bool,
                   rps: int, col_sharded: bool):
    """Cached shard_map callable for distributed SpMM (Y = A @ X).

    The 2-D-grid answer to the reference's projection functors
    (``projections.cc:23-64``): X's rows follow A's row partition (the
    same halo / all_gather / precise realizations as ``dist_spmv``, one
    axis up), while X's *columns* shard over the grid's "cols" axis —
    independent columns, so the column axis adds zero communication.
    """
    _obs.inc("jit_miss.dist_csr.block_spmm_fn")
    from ..ops import spmv as _spmv_ops

    xcol = COL_AXIS if col_sharded else None

    def realize(x_local, gidx_local=None):
        if precise:
            parts = x_local[gidx_local]          # (R_dst, C, k_loc)
            recv = jax.lax.all_to_all(
                parts, ROW_AXIS, split_axis=0, concat_axis=0, tiled=True
            )
            return jnp.concatenate(
                [recv.reshape(-1, x_local.shape[1]), x_local]
            )
        if halo >= 0:
            return _extend_x(x_local, halo)      # axis 0
        return jax.lax.all_gather(x_local, ROW_AXIS, tiled=True)

    if ell:
        def kernel(data, cols, counts, *rest):
            gidx = rest[0][0] if precise else None
            X_local = rest[-1]
            X_src = realize(X_local, gidx)
            return _spmv_ops.ell_spmm(data[0], cols[0], counts[0], X_src)

        in_specs = (
            P(ROW_AXIS, None, None), P(ROW_AXIS, None, None),
            P(ROW_AXIS, None),
        ) + ((P(ROW_AXIS, None, None),) if precise else ()) + (
            P(ROW_AXIS, xcol),
        )
    else:
        def kernel(data, cols, row_ids, counts, *rest):
            gidx = rest[0][0] if precise else None
            X_local = rest[-1]
            X_src = realize(X_local, gidx)
            d, c, rid, cnt = data[0], cols[0], row_ids[0], counts[0]
            slot = jnp.arange(d.shape[0], dtype=jnp.int32)
            prod = jnp.where(
                (slot < cnt)[:, None], d[:, None] * X_src[c, :],
                jnp.zeros((1, 1), d.dtype),
            )
            return jax.ops.segment_sum(
                prod, rid, num_segments=rps, indices_are_sorted=True
            )

        in_specs = (
            P(ROW_AXIS, None), P(ROW_AXIS, None), P(ROW_AXIS, None),
            P(ROW_AXIS),
        ) + ((P(ROW_AXIS, None, None),) if precise else ()) + (
            P(ROW_AXIS, xcol),
        )
    return jax.jit(shard_map(
        kernel, mesh=mesh, in_specs=in_specs,
        out_specs=P(ROW_AXIS, xcol), check_vma=False,
    ))


@lru_cache(maxsize=128)
def _block_semiring_spmm_fn(mesh: Mesh, halo: int, precise: bool,
                            ell: bool, rps: int, col_sharded: bool,
                            add: str, mul: str, prefix: bool):
    """Cached shard_map callable for distributed semiring SpMM — the
    batched multi-source frontier program (k stacked sources ride one
    dispatch, the distributed arm of the PR-8 ``multi_matvec``
    packing).  Structure is ``_block_spmm_fn`` with the local kernel
    generalized; x realization collectives are semiring-independent
    (1-d layouts only, like ``dist_spmm`` itself).  ``prefix`` as in
    ``_block_semiring_spmv_fn``."""
    _obs.inc("jit_miss.dist_csr.block_semiring_spmm_fn")
    from ..ops import spmv as _spmv_ops

    xcol = COL_AXIS if col_sharded else None

    def realize(x_local, gidx_local=None):
        if precise:
            parts = x_local[gidx_local]
            recv = jax.lax.all_to_all(
                parts, ROW_AXIS, split_axis=0, concat_axis=0, tiled=True
            )
            return jnp.concatenate(
                [recv.reshape(-1, x_local.shape[1]), x_local]
            )
        if halo >= 0:
            return _extend_x(x_local, halo)
        return jax.lax.all_gather(x_local, ROW_AXIS, tiled=True)

    if ell:
        def kernel(data, cols, counts, *rest):
            gidx = rest[0][0] if precise else None
            X_local = rest[-1]
            X_src = realize(X_local, gidx)
            return _spmv_ops.ell_semiring_spmm(
                data[0], cols[0], counts[0], X_src, add, mul)

        in_specs = (
            P(ROW_AXIS, None, None), P(ROW_AXIS, None, None),
            P(ROW_AXIS, None),
        ) + ((P(ROW_AXIS, None, None),) if precise else ()) + (
            P(ROW_AXIS, xcol),
        )
    else:
        def kernel(data, cols, row_ids, counts, *rest):
            row_ptr = rest[0][0] if prefix else None
            gidx = rest[-2][0] if precise else None
            X_local = rest[-1]
            X_src = realize(X_local, gidx)
            return _spmv_ops.csr_semiring_spmm_rowids_masked(
                data[0], cols[0], row_ids[0], counts[0], X_src,
                rps, add, mul, row_ptr=row_ptr)

        in_specs = (
            P(ROW_AXIS, None), P(ROW_AXIS, None), P(ROW_AXIS, None),
            P(ROW_AXIS),
        ) + ((P(ROW_AXIS, None),) if prefix else ()) + (
            (P(ROW_AXIS, None, None),) if precise else ()) + (
            P(ROW_AXIS, xcol),
        )
    return jax.jit(shard_map(
        kernel, mesh=mesh, in_specs=in_specs,
        out_specs=P(ROW_AXIS, xcol), check_vma=False,
    ))


@lru_cache(maxsize=128)
def _dia_spmm_dist_fn(mesh: Mesh, offsets: Tuple[int, ...], halo: int,
                      rps: int, tile: int, col_sharded: bool,
                      interpret: bool):
    """Cached shard_map callable: banded distributed SpMM through the
    per-shard Mosaic band kernel over the pre-blocked layout (the SpMM
    arm of ``_dia_spmv_pallas_fn``; row shifts of a 2-D X are sublane
    rolls — cheaper than the SpMV lane decomposition)."""
    _obs.inc("jit_miss.dist_csr.dia_spmm_dist_fn")
    from ..ops.pallas_dia import L as _LANES
    from ..ops.pallas_dia import pallas_dia_spmm

    offs2 = tuple(int(o) + halo for o in offsets)
    nd = len(offsets)
    xcol = COL_AXIS if col_sharded else None

    def kernel(pdata, X_local):
        X_ext = _extend_x(X_local, halo)            # axis 0
        return pallas_dia_spmm(
            pdata[0].reshape(nd, -1, _LANES),
            X_ext, offs2, (rps, X_ext.shape[0]), tile,
            interpret=interpret,
        )

    in_specs = (P(ROW_AXIS, None, None), P(ROW_AXIS, xcol))
    return jax.jit(shard_map(
        kernel, mesh=mesh, in_specs=in_specs,
        out_specs=P(ROW_AXIS, xcol), check_vma=False,
    ))


def dist_spmm(A: DistCSR, X: jax.Array, semiring=None) -> jax.Array:
    """Y = A @ X for a dense (rows_padded, k) operand (jittable).

    Same distribution contract as ``dist_spmv`` lifted one axis: X and
    Y are row-block sharded over "rows"; on a 2-D grid mesh
    (``make_grid_mesh``) their columns additionally shard over "cols",
    with the sparse blocks replicated along that axis.  Use
    ``shard_dense`` to lay out X.

    ``semiring`` generalizes the product exactly as in ``dist_spmv``
    — the batched multi-source frontier path (k stacked sources per
    dispatch; docs/GRAPH.md).  1-d layouts only, like the plus-times
    program.
    """
    if A.grid is not None:
        raise NotImplementedError(
            "dist_spmm: 2-d-block layouts are SpMV/SpGEMM-only; "
            "shard with layout='1d-row' for dense operands"
        )
    A._require_blocks("dist_spmm")
    precise = A.gather_idx is not None
    col_sharded = COL_AXIS in A.mesh.shape
    _obs.inc("op.dist_spmm")
    # Comm ledger: per-device column block widens every realization
    # slice; the column axis itself adds zero communication.
    from ..obs import comm as _comm

    k_loc = int(X.shape[1]) // (int(A.mesh.shape[COL_AXIS])
                                if col_sharded else 1)
    _comm.record("dist_spmm", spmv_comm_volumes(
        A, (int(X.shape[0]) // A.num_shards) * max(k_loc, 1),
        jnp.dtype(X.dtype).itemsize, cols=max(k_loc, 1),
    ))
    sr = _resolve_semiring_arg(semiring)
    if sr is not None:
        _obs.inc("graph.dist_spmm." + sr.name)
        prefix = _takes_prefix_or(A, sr, X.dtype)
        fn = _block_semiring_spmm_fn(
            A.mesh, A.halo, precise, A.ell, A.rows_per_shard,
            col_sharded, sr.add, sr.mul, prefix)
        if A.ell:
            args = (A.data, A.cols, A.counts)
        else:
            args = (A.data, A.cols, A.row_ids, A.counts) + (
                (A.row_ptr,) if prefix else ())
        args += ((A.gather_idx,) if precise else ()) + (X,)
        return fn(*args)
    if (A.pdia_tile and A.halo >= 0 and not precise
            and jnp.result_type(A.dtype, X.dtype) == A.dtype):
        from ..ops.pallas_dia import (
            _VMEM_BUDGET, _spmm_vmem, pallas_dist_mode,
        )

        mode = pallas_dist_mode()
        nd = A.pdia_data.shape[1]
        item = np.dtype(A.dtype).itemsize
        vmem = _spmm_vmem(A.pdia_tile, max(k_loc, 1), nd, item)
        if mode != "0" and 0 < k_loc and vmem <= _VMEM_BUDGET:
            fn = _dia_spmm_dist_fn(
                A.mesh, A.dia_offsets, A.halo, A.rows_per_shard,
                A.pdia_tile, col_sharded, mode == "interpret",
            )
            return fn(A.pdia_data, X)
    fn = _block_spmm_fn(A.mesh, A.halo, precise, A.ell,
                        A.rows_per_shard, col_sharded)
    if A.ell:
        args = (A.data, A.cols, A.counts) + (
            (A.gather_idx,) if precise else ()
        ) + (X,)
    else:
        args = (A.data, A.cols, A.row_ids, A.counts) + (
            (A.gather_idx,) if precise else ()
        ) + (X,)
    return fn(*args)


def attach_bsr_prepack(dist: DistCSR, host_ell=None) -> DistCSR:
    """Per-shard block-sparse (BSR) pack for *irregular* distributed
    matrices, in place — the distributed arm of ``ops/bsr.py``.

    Applies to the all_gather realization (irregular matrices blow the
    halo window, and cols are then global — exactly the BSR pack's
    input).  Shards pack independently; block counts are padded to the
    max with all-zero blocks (zero data contributes nothing wherever
    its brow points).  Built only when the Pallas dist route is on and
    every shard stays within the densification budget; disabled under
    CHECK_BOUNDS like the single-chip BSR path (densified zeros
    multiply x — see ``csr_array._get_bsr``).

    ``host_ell`` is the (data, cols, counts) ELL pack as host numpy
    when the caller still holds it (``shard_csr`` does) — passing it
    avoids a device->host round trip of the whole pack.
    """
    from ..ops.bsr import MAX_BLOCKS, bsr_pack
    from ..ops.bsr import B as _B
    from ..ops.pallas_dia import pallas_dist_mode
    from ..settings import settings

    if (dist.bsr_blocks is not None or dist.bsr_tried
            or dist.data is None or not dist.ell or dist.halo >= 0
            or dist.gather_idx is not None
            or pallas_dist_mode() == "0"
            or settings.bsr_max_expand <= 0
            or settings.check_bounds
            or np.dtype(dist.dtype) not in (np.dtype(np.float32),)):
        return dist
    dist.bsr_tried = True
    R = dist.num_shards
    rps = dist.rows_per_shard
    cols = dist.shape[1]
    if host_ell is not None:
        data_b, cols_b, counts_b = (np.asarray(a) for a in host_ell)
    else:
        data_b = np.asarray(dist.data)      # (R, rps, W)
        cols_b = np.asarray(dist.cols)
        counts_b = np.asarray(dist.counts)  # (R, rps)
    packs = []
    for s in range(R):
        W = data_b.shape[2]
        slot = np.arange(W)[None, :]
        valid = slot < counts_b[s][:, None]
        indptr = np.zeros(rps + 1, np.int64)
        np.cumsum(counts_b[s], out=indptr[1:])
        pack = bsr_pack(
            data_b[s][valid], cols_b[s][valid].astype(np.int64),
            indptr, (rps, cols), settings.bsr_max_expand,
        )
        if pack is None:
            return dist
        packs.append(pack)
    nb_max = max(p[0].shape[0] for p in packs)
    if nb_max > MAX_BLOCKS:
        return dist
    nbr = packs[0][3]
    nbc = packs[0][4]
    blk = np.zeros((R, nb_max, _B, _B), np.float32)
    brow = np.zeros((R, nb_max), np.int32)
    bcol = np.zeros((R, nb_max), np.int32)
    for s, (bT, br, bc, _, _) in enumerate(packs):
        nb = bT.shape[0]
        blk[s, :nb] = bT
        brow[s, :nb] = br
        bcol[s, :nb] = bc
        # Padding blocks: zero data accumulated into the last block-row
        # (harmless), sorted order preserved.
        brow[s, nb:] = br[-1] if nb else 0
    spec3 = NamedSharding(dist.mesh, P(ROW_AXIS, None, None, None))
    spec2 = NamedSharding(dist.mesh, P(ROW_AXIS, None))
    dist.bsr_blocks = jax.device_put(jnp.asarray(blk), spec3)
    dist.bsr_brow = jax.device_put(jnp.asarray(brow), spec2)
    dist.bsr_bcol = jax.device_put(jnp.asarray(bcol), spec2)
    dist.bsr_grid = (int(nbr), int(nbc))
    return dist


@lru_cache(maxsize=128)
def _bsr_spmv_dist_fn(mesh: Mesh, rps: int, nbr: int, nbc: int,
                      interpret: bool):
    """Cached shard_map callable: all_gather x, then the per-shard
    Pallas BSR kernel over the pre-packed blocks."""
    _obs.inc("jit_miss.dist_csr.bsr_spmv_dist_fn")
    from ..ops.bsr import B as _B
    from ..ops.bsr import bsr_spmv_pallas

    def kernel(blk, brow, bcol, x_local):
        x_full = jax.lax.all_gather(x_local, ROW_AXIS, tiled=True)
        pad = nbc * _B - x_full.shape[0]
        if pad > 0:
            x_full = jnp.concatenate(
                [x_full, jnp.zeros((pad,), x_full.dtype)]
            )
        x3d = x_full[: nbc * _B].reshape(nbc, 1, _B)
        y3d = bsr_spmv_pallas(blk[0], brow[0], bcol[0], x3d, nbr, nbc,
                              interpret=interpret)
        return y3d.ravel()[:rps]

    in_specs = (P(ROW_AXIS, None, None, None), P(ROW_AXIS, None),
                P(ROW_AXIS, None), P(ROW_AXIS))
    return jax.jit(shard_map(
        kernel, mesh=mesh, in_specs=in_specs, out_specs=P(ROW_AXIS),
        check_vma=False,
    ))


def _padded_operator(A: DistCSR):
    """The distributed matrix as a LinearOperator over padded sharded
    vectors — lets every single-chip solver run distributed unchanged
    (the reference gets this transparency from Legion arrays; here the
    matvec is the shard_map'd ``dist_spmv`` and all reductions inside
    the jitted solver loops lower to ``psum`` over the mesh)."""
    from ..linalg import LinearOperator

    n = A.rows_padded
    return LinearOperator(shape=(n, n), matvec=A.matvec_fn(),
                          dtype=A.dtype)


def _padded_precond(M, A: DistCSR):
    if M is None or not callable(M):
        return M
    from ..linalg import LinearOperator

    n = A.rows_padded
    return LinearOperator(shape=(n, n), matvec=M, dtype=A.dtype)


def _shard_system(A: DistCSR, b, x0, maxiter, callback):
    """Shared solver preamble: shard b/x0 to the padded length, default
    the iteration budget, and truncate callback iterates to the true
    row count."""
    rows = A.shape[0]
    b_sh = shard_vector(b, A.mesh, A.rows_padded, layout=A.layout)
    x0_sh = (shard_vector(jnp.asarray(x0, dtype=b_sh.dtype), A.mesh,
                          A.rows_padded, layout=A.layout)
             if x0 is not None else None)
    if maxiter is None:
        maxiter = rows * 10
    cb = (None if callback is None
          else (lambda xk: callback(xk[:rows])))
    return rows, b_sh, x0_sh, maxiter, cb


@contextlib.contextmanager
def _maybe_ckpt_scope(site: str):
    """Open a checkpoint scope for a distributed solve when the knob
    asks for one (``settings.resil_ckpt_iters > 0``) and no caller
    scope is already bound — the caller's scope always wins (scopes do
    not compose; see resilience/checkpoint.py)."""
    if (_rsettings.resil and _rckpt.current() is None
            and _rsettings.resil_ckpt_iters > 0):
        with _rckpt.scope(site) as ck:
            yield ck
    else:
        yield _rckpt.current()


def _solve_with_recovery(site: str, A: "DistCSR", b, b_sh, x0_sh,
                         maxiter: int, solve_fn, guard: bool = True):
    """The device-loss recovery ladder (docs/RESILIENCE.md) around a
    distributed solve: **detect** (a ``DeviceLost`` escapes the retry
    policy un-retried, surfacing at the conv-fetch cadence) ->
    **shrink** (``survivor_mesh`` drops the lost flat ordinal) ->
    **reshard** (retained-source repartition of ``A`` onto the
    survivor grid) -> **restore** (the last checkpoint's iterate —
    else the original ``x0``) -> **resume** with the remaining
    iteration budget.  Converges to the same tolerance instead of
    raising.

    ``solve_fn(A_cur, b_sh_cur, x0_sh_cur, miter) -> (x, iters)``
    runs the solve over operands sharded for ``A_cur``; the ladder
    owns re-sharding ``b`` / the restart iterate after each shrink
    (from HOST state — the old mesh's arrays may be unreadable after
    a real loss, which is why the checkpoint path snapshots to host
    buffers).  ``guard=True`` wraps each attempt as the ``site``
    fault/retry site; gmres passes False (its cycle loop already owns
    the ``solver.gmres.conv`` site).  Recoveries are bounded by the
    shard count: each loss removes one device, and a single-shard
    solve has nothing to shrink to, so the ``DeviceLost`` re-raises.

    Accounting (pinned by tests): per recovery, one each of
    ``resil.recovery.attempts`` / ``.device_loss`` / ``.mesh_shrink``,
    ``resil.recovery.restored_iters`` by the checkpoint's credited
    iterations, ``resil.recovery.reshard_bytes`` by the measured
    ``transfer.shard_upload_bytes`` delta of the repartition, and one
    ``resil.recovery`` event; ``resil.recovery.succeeded`` once when
    a recovered solve completes.  Returns ``(x, total_iters, A_fin)``
    — iterations credited from restores count toward the total, and
    the comm ledger prices the final mesh.
    """
    from .reshard import reshard

    rows = A.shape[0]
    ck = _rckpt.current()
    A_cur, b_cur, x0_cur = A, b_sh, x0_sh
    miter = int(maxiter)
    base = 0          # iterations credited from restored checkpoints
    recovered = 0
    while True:
        try:
            if guard:
                x, iters = _resil_guarded(
                    site, partial(solve_fn, A_cur, b_cur, x0_cur,
                                  miter))
            else:
                x, iters = solve_fn(A_cur, b_cur, x0_cur, miter)
            if recovered:
                _obs.inc("resil.recovery.succeeded")
            return x, base + int(iters), A_cur
        except DeviceLost as e:
            if A_cur.num_shards <= 1:
                raise
            recovered += 1
            _obs.inc("resil.recovery.attempts")
            _obs.inc("resil.recovery.device_loss")
            survivors = survivor_mesh(A_cur.mesh, int(e.device))
            before = int(A_cur.num_shards)
            up0 = _obs.snapshot().get("transfer.shard_upload_bytes", 0)
            A_cur = reshard(A_cur, mesh=survivors, layout=A_cur.layout)
            moved = (_obs.snapshot().get("transfer.shard_upload_bytes",
                                         0) - up0)
            _obs.inc("resil.recovery.mesh_shrink")
            _obs.inc("resil.recovery.reshard_bytes", int(moved))
            b_cur = shard_vector(jnp.asarray(b), A_cur.mesh,
                                 A_cur.rows_padded, layout=A_cur.layout)
            snap = ck.restore() if ck is not None else None
            if snap is not None:
                it0, arrays = snap
                # Plain restart from the checkpointed x: r and p
                # re-derive from scratch, preserving convergence to
                # tolerance (not the exact iterate sequence).
                x_host = np.asarray(arrays[0])[:rows]
                base += int(it0)
                _obs.inc("resil.recovery.restored_iters", int(it0))
                ck.rebase()
            else:
                x_host = np.asarray(x0_sh)[:rows]
            x0_cur = shard_vector(jnp.asarray(x_host, dtype=b_cur.dtype),
                                  A_cur.mesh, A_cur.rows_padded,
                                  layout=A_cur.layout)
            miter = max(int(maxiter) - base, 1)
            _obs.event("resil.recovery", site=site,
                       device=int(e.device), shards_before=before,
                       shards_after=int(A_cur.num_shards),
                       restored_iters=(int(snap[0]) if snap else 0),
                       reshard_bytes=int(moved))


def dist_gmres(A: DistCSR, b, x0=None, tol=None, restart=None,
               maxiter=None, M=None, callback=None, atol: float = 0.0,
               callback_type=None, rtol: float = 1e-5):
    """Distributed restarted GMRES: the single-chip solver
    (``linalg.gmres``) over the padded sharded system.  Padding rows
    are zero rows with zero right-hand side, so the Krylov space keeps
    them at exactly 0 and residual norms match the unpadded system.
    ``M`` may be a jittable callable on padded sharded vectors.
    Returns ``(x[:rows], iters)``.

    Restart cycles inherit the single-chip sync-free design: Arnoldi +
    progressive Givens QR of the Hessenberg + the solution update run
    as one traced program over the sharded operands (reductions lower
    to ``psum`` over the mesh), with ONE stacked-scalar fetch per cycle
    as the convergence cadence (``transfer.host_sync.gmres_conv``) —
    no per-cycle Hessenberg transfer or host ``lstsq``, which cost a
    host round trip per restart.
    """
    from ..linalg import gmres as _gmres

    from ..obs import comm as _comm

    rows, b_sh, x0_sh, maxiter, cb = _shard_system(
        A, b, x0, maxiter, callback
    )
    if callback_type == "pr_norm":
        cb = callback   # scalar iterates: nothing to truncate
    restart_eff = min(int(restart) if restart else 20,
                      int(b_sh.shape[0]))
    with _tctx.profiler_scope("dist_gmres"), \
            _obs.span("dist_gmres", n=rows, shards=A.num_shards,
                      restart=restart_eff) as sp:
        # Resilience: the cycle loop inside ``_gmres`` owns the
        # ``solver.gmres.conv`` fault/retry site and the checkpoint
        # cadence (the Arnoldi seed x per cycle); a ``DeviceLost``
        # escaping it routes through the recovery ladder, which
        # re-seeds the restarted Arnoldi from the last snapshot on
        # the survivor mesh (guard=False: no second policy wrap).
        def _solve(A_cur, b_cur, x0_cur, miter):
            return _gmres(
                _padded_operator(A_cur), b_cur, x0=x0_cur, tol=tol,
                restart=restart, maxiter=miter,
                M=_padded_precond(M, A_cur), callback=cb, atol=atol,
                callback_type=callback_type, rtol=rtol,
            )

        if _rsettings.resil:
            with _maybe_ckpt_scope("dist.gmres"):
                x, info, A_fin = _solve_with_recovery(
                    "dist.gmres", A, b, b_sh, x0_sh, int(maxiter),
                    _solve, guard=False)
        else:
            x, info = _solve(A, b_sh, x0_sh, maxiter)
            A_fin = A
        # Comm ledger: the driver returns iterations as a host int, so
        # the cycle count is free (approximated as ceil(iters/restart);
        # a run converging at cycle start reports one cycle fewer than
        # it dispatched).  Per-cycle volumes: restart+1 SpMV
        # realizations + the Arnoldi/MGS scalar psums.
        cycles = max(1, -(-int(info) // restart_eff))
        item = jnp.dtype(b_sh.dtype).itemsize
        spmv = spmv_comm_volumes(
            A_fin, A_fin.rows_padded // A_fin.num_shards, item)
        vols = _comm.scale(
            _comm.gmres_cycle_volumes(spmv, restart_eff, item,
                                      A_fin.num_shards),
            cycles,
        )
        n_psum = cycles * (restart_eff * (restart_eff + 1) // 2
                           + restart_eff + 1)
        calls = {k: cycles * (restart_eff + 1) for k in spmv}
        # Additive: a 2-d-block SpMV realization already carries a
        # "psum" call count (its psum_scatter output reduction).
        calls["psum"] = calls.get("psum", 0) + n_psum
        comm_bytes = _comm.record("dist_gmres", vols, calls,
                                  layout=A_fin.layout)
        if sp is not None:
            sp.set(iters=int(info), cycles=cycles,
                   comm_bytes=comm_bytes,
                   comm_calls=sum(calls[k] for k, b in vols.items()
                                  if b > 0))
    return x[:rows], info


def dist_bicgstab(A: DistCSR, b, x0=None, tol=None, maxiter=None,
                  M=None, callback=None, atol: float = 0.0,
                  rtol: float = 1e-5, conv_test_iters: int = 25):
    """Distributed BiCGSTAB over the padded sharded system (see
    ``dist_gmres`` for the padding argument).  Returns
    ``(x[:rows], iters)``."""
    from ..linalg import bicgstab as _bicgstab

    rows, b_sh, x0_sh, maxiter, cb = _shard_system(
        A, b, x0, maxiter, callback
    )
    x, info = _bicgstab(
        _padded_operator(A), b_sh, x0=x0_sh, tol=tol, maxiter=maxiter,
        M=_padded_precond(M, A), callback=cb, atol=atol, rtol=rtol,
        conv_test_iters=conv_test_iters,
    )
    return x[:rows], info


def dist_minres(A: DistCSR, b, x0=None, shift=0.0, tol=None,
                maxiter=None, M=None, callback=None, atol: float = 0.0,
                rtol: float = 1e-5, conv_test_iters: int = 25):
    """Distributed MINRES over the padded sharded system (see
    ``dist_gmres`` for the padding argument — padded rows are zero rows
    with zero rhs, and MINRES tolerates the resulting singular-but-
    consistent system by construction).  For symmetric indefinite
    operators the reference has no equivalent solver at any scale.
    Returns ``(x[:rows], iters)``.

    NOTE: passing ``callback`` routes the solve through host scipy's
    Python iteration loop (one device round trip per iteration) —
    unlike dist_cg/dist_gmres whose callbacks stay native.  Use it for
    diagnostics, not production runs."""
    from ..linalg import minres as _minres

    rows, b_sh, x0_sh, maxiter, cb = _shard_system(
        A, b, x0, maxiter, callback
    )
    x, info = _minres(
        _padded_operator(A), b_sh, x0=x0_sh, shift=shift, tol=tol,
        maxiter=maxiter, M=_padded_precond(M, A), callback=cb,
        atol=atol, rtol=rtol, conv_test_iters=conv_test_iters,
    )
    return x[:rows], info


def dist_eigsh(A: DistCSR, k=6, which="LM", v0=None, ncv=None,
               maxiter=None, tol=0, return_eigenvectors=True,
               sigma=None):
    """Distributed symmetric eigensolver: the single-chip Lanczos
    (``linalg.eigsh``) over the padded sharded operator.

    The start vector is zero on padding rows, and the padded operator's
    padding rows/columns are zero — so the Krylov space stays in the
    orthogonal complement of the padding subspace and NO spurious zero
    eigenvalues appear.  All SpMVs and reductions inside the jitted
    Lanczos scan lower to shard_map collectives.

    ``sigma`` (and ``which='SM'``, served as sigma=0) runs the same
    native shift-invert as single-chip ``eigsh``: the inexact MINRES
    inner solve nests inside the Lanczos scan, so every inner iteration
    is one ppermute/psum round over the mesh — no factorization, which
    is what makes shift-invert possible at distributed scale at all.
    A stagnating probe (sigma at a pencil eigenvalue, singular A at
    SM) raises ``ArpackNoConvergence`` — there is no host fallback for
    a distributed operator.  Returns eigenvalues (and row-truncated
    eigenvectors).  The reference has no eigensolver at any scale."""
    from ..eigen import (
        _eigsh_shift_invert, _lanczos_eigsh, _require_real_sigma,
        _validate_be_k,
    )

    rows = A.shape[0]
    if A.shape[0] != A.shape[1]:
        raise ValueError("expected square matrix")
    if not (0 < k < rows):
        raise ValueError(f"k={k} must satisfy 0 < k < n={rows}")
    if which not in ("LM", "LA", "SA", "BE", "SM"):
        raise ValueError(
            f"which={which!r}: distributed eigsh supports "
            f"LM/LA/SA/BE/SM")
    _validate_be_k(which, k)
    if which == "SM" and sigma is None:
        sigma, which = 0.0, "LM"    # largest of A^{-1}
    if v0 is None:
        v0 = np.random.default_rng(0).standard_normal(rows)
    v0_sh = shard_vector(jnp.asarray(v0, dtype=A.dtype), A.mesh,
                         A.rows_padded, layout=A.layout)
    # Valid-row mask keeps breakdown restarts out of the padding
    # subspace; max_rank caps the Krylov dimension at the true rows.
    mask = shard_vector(jnp.ones((rows,), dtype=A.dtype), A.mesh,
                        A.rows_padded, layout=A.layout)
    if sigma is None:
        out = _lanczos_eigsh(
            A.matvec_fn(), A.rows_padded, np.dtype(A.dtype), int(k),
            which, v0_sh, ncv, maxiter, tol, return_eigenvectors,
            mask=mask, max_rank=rows)
        if not return_eigenvectors:
            return out
        w, X = out
        return w, X[:rows]

    # Distributed shift-invert: the shared single-chip driver with the
    # valid-subspace mask (the padding block of A - sigma I is
    # -sigma I, singular at sigma=0 — it must not leak into the probe
    # or the Krylov space), the true-rows rank cap, and row truncation
    # applied to every returned/raised eigenvector block.
    _require_real_sigma(sigma)
    return _eigsh_shift_invert(
        A.matvec_fn(), A.rows_padded, np.dtype(A.dtype), int(k),
        float(sigma), which, v0_sh, ncv, maxiter, tol,
        return_eigenvectors, mask=mask, max_rank=rows,
        name="dist_eigsh", trunc_rows=rows)


def dist_diagonal(A: DistCSR) -> jax.Array:
    """diag(A) as a row-block sharded padded vector (square A).

    Distributed analog of the get-diagonal task (reference
    ``src/sparse/array/csr/get_diagonal.cc``); feeds the Jacobi
    smoother in distributed GMG.
    """
    if A.grid is not None:
        raise NotImplementedError(
            "dist_diagonal: 2-d-block layouts are SpMV/SpGEMM-only; "
            "shard with layout='1d-row' for GMG/diagonal consumers"
        )
    rps = A.rows_per_shard

    if A.dia_data is not None:
        # Banded: the main diagonal is one (R, rps) slice of the DIA
        # blocks (0 at holes/padding already).
        offs = A.dia_offsets
        if 0 not in offs:
            return jnp.zeros((A.rows_padded,), dtype=A.dtype)
        d0 = offs.index(0)
        return jnp.reshape(A.dia_data[:, d0, :], (-1,))

    A._require_blocks("dist_diagonal")
    halo = A.halo
    precise = A.gather_globals is not None

    cps = A.cols_per_shard

    def global_cols(cols, shard, ggl=None):
        """Layout columns -> global columns for any realization."""
        if precise:
            base = ggl.reshape(-1)
            rc = base.shape[0]
            own = cols - rc + shard.astype(index_dtype()) * cps
            return jnp.where(
                cols < rc, base[jnp.clip(cols, 0, rc - 1)], own
            )
        if halo >= 0:
            return cols.astype(index_dtype()) + (
                shard.astype(index_dtype()) * rps - halo
            )
        return cols.astype(index_dtype())

    if A.ell:
        def kernel(data, cols, counts, *rest):
            data, cols, counts = data[0], cols[0], counts[0]
            ggl = rest[0][0] if precise else None
            shard = jax.lax.axis_index(ROW_AXIS)
            row_g = shard.astype(index_dtype()) * rps + jnp.arange(
                rps, dtype=index_dtype()
            )
            W = cols.shape[1]
            slot = jnp.arange(W, dtype=counts.dtype)
            valid = slot[None, :] < counts[:, None]
            g = global_cols(cols, shard, ggl)
            hit = jnp.logical_and(valid, g == row_g[:, None])
            return jnp.sum(
                jnp.where(hit, data, jnp.zeros((), data.dtype)), axis=1
            )

        args = (A.data, A.cols, A.counts) + (
            (A.gather_globals,) if precise else ()
        )
    else:
        def kernel(data, cols, row_ids, counts, *rest):
            data, cols, row_ids, counts = (
                data[0], cols[0], row_ids[0], counts[0]
            )
            ggl = rest[0][0] if precise else None
            shard = jax.lax.axis_index(ROW_AXIS)
            slot = jnp.arange(data.shape[0], dtype=jnp.int32)
            valid = slot < counts
            target = (row_ids.astype(index_dtype())
                      + shard.astype(index_dtype()) * rps)
            g = global_cols(cols, shard, ggl)
            hit = jnp.logical_and(valid, g == target)
            return jax.ops.segment_sum(
                jnp.where(hit, data, jnp.zeros((), data.dtype)),
                row_ids, num_segments=rps, indices_are_sorted=True,
            )

        args = (A.data, A.cols, A.row_ids, A.counts) + (
            (A.gather_globals,) if precise else ()
        )
    in_specs = tuple(P(ROW_AXIS, *([None] * (a.ndim - 1))) for a in args)
    return shard_map(
        kernel, mesh=A.mesh, in_specs=in_specs, out_specs=P(ROW_AXIS),
        check_vma=False,
    )(*args)


def dist_cg(
    A: DistCSR,
    b,
    x0=None,
    tol=None,
    maxiter: Optional[int] = None,
    M=None,
    callback=None,
    atol: float = 0.0,
    rtol: float = 1e-5,
    conv_test_iters: int = 25,
):
    """Distributed (optionally preconditioned) CG: ``linalg.cg``'s
    solve routine (``_cg_solve``) and loop over sharded state.

    Global reductions (rho, pq, convergence norm) are jnp.vdot on sharded
    vectors — GSPMD lowers them to local dots + ``psum`` over ICI,
    replacing the reference's future-based scalar plumbing
    (``linalg.py:507-533``).  ``M`` is a jittable preconditioner on
    padded sharded vectors (e.g. ``DistGMG.cycle`` — the reference's
    headline GMG-preconditioned configuration, ``examples/gmg.py:104-143``).
    Returns the solution truncated to the unpadded length, plus the
    iteration count; a callback sees iterates truncated the same way.

    The solve records the span tree of ``linalg.cg``: ``dist_cg`` ⊃
    ``dist_cg.launch`` (the ‖b‖ fetch, the loop's trace and enqueue)
    ⊃ ``dist_cg.norm`` (the fetch, one
    ``transfer.host_sync.dist_cg_norm``).  Where the iteration count
    reaches the host (tracing mode, or a callback solve) the comm
    ledger records ``cg_comm_volumes`` for it under ``dist_cg``.
    """
    from ..linalg import _cg_solve
    from ..obs import comm as _comm
    from ..obs import memory as _mem

    _obs.inc("op.dist_cg")
    rows, b_sh, x0_sh, maxiter, cb = _shard_system(
        A, b, x0, maxiter, callback
    )
    M_mv = M if M is not None else (lambda r: r)
    A_fin = A

    def solve(loop, b_sh, x0_sh, atol):
        # Resilience: the whole loop dispatch is the ``dist.cg`` site —
        # an injected (or real) collective failure retries the solve
        # from x0, which re-converges to the identical answer instead
        # of corrupting the Krylov state.  An active deadline scope /
        # health opt-in / checkpoint scope swaps in the chunked loop
        # (one fetch per conv_test_iters cycle — the existing cadence),
        # and a ``DeviceLost`` routes through the recovery ladder
        # (shrink -> reshard -> restore -> resume).  NOTE: after a
        # shrink, ``M`` is applied to survivor-mesh vectors — a
        # mesh-agnostic jittable callable recovers; a mesh-pinned
        # preconditioner will not.
        nonlocal A_fin

        def _solve(A_cur, b_cur, x0_cur, miter):
            return loop(A_cur.matvec_fn(), M_mv, b_cur, x0_cur, atol,
                        int(miter), int(conv_test_iters))

        if not _rsettings.resil:
            return _solve(A, b_sh, x0_sh, maxiter)
        with _maybe_ckpt_scope("dist.cg"):
            x, iters, A_fin = _solve_with_recovery(
                "dist.cg", A, b, b_sh, x0_sh, int(maxiter), _solve)
        return x, iters

    def ledger(it):
        # The loop body is traced once, so the per-iteration volumes
        # are multiplied out here rather than at the (trace-time)
        # dispatch; the final mesh prices them.
        vols, calls = cg_comm_volumes(
            A_fin, jnp.dtype(b_sh.dtype).itemsize, it)
        return dict(comm_bytes=_comm.record("dist_cg", vols, calls,
                                            layout=A_fin.layout),
                    comm_calls=sum(calls[k] for k, v in vols.items()
                                   if v > 0))

    with _tctx.profiler_scope("dist_cg"), \
            _mem.watermark("dist_cg", n=rows, shards=A.num_shards):
        x, iters = _cg_solve(
            "dist_cg", rows, solve, ledger, b_sh, x0_sh, tol, atol, rtol,
            maxiter, cb, shards=A.num_shards, preconditioned=M is not None)
    return x[:rows], iters
