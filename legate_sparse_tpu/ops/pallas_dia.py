# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Pallas TPU kernel for banded (DIA) SpMV — the roofline hot path.

Why this kernel exists (measured on one v5e, the ``spmv-band11``
benchmark cell's operator: 2^24 rows, 11 diagonals, f32, a CSR built
directly; per call, 32 resident x a batch, best of 5 batches; GB/s are
the yardstick's 4 * (nnz + 2 * rows) bytes over the call's time):

============================================  =========  ============
lowering of the same band product             ms/call    bandwidth
============================================  =========  ============
XLA ``.at[lo:hi].add`` (``dia_spmv``)         17.24      51 GB/s
XLA pad + slice (``dia_spmv_fused``)          11.64      75 GB/s
XLA interior/edge split (``dia_spmv_nopad``)  10.69      82 GB/s
**this kernel**                               **1.386**  **629 GB/s**
chip HBM roofline (v5e)                       1.065      819 GB/s
============================================  =========  ============

Through ``A @ x`` the benchmark cell reads 1.39-1.47 ms a call, the
kernel at 78.6% of that roofline and, counting the bytes its blocks
really stream (60 a row: 11 value slots, three x tiles, y), at 90.7%
of HBM peak: bound by bytes, not by its rolls.

Every XLA formulation of the stencil shift pays a full lane-relayout
per diagonal (a flat shift by ±1 moves every element across the
(8, 128) tiled layout), so the op runs 10-16x under roofline.  The
Mosaic-level fix: keep the shift *inside* VMEM as register rotates —
``pltpu.roll`` on the lane and sublane axes plus a lane-boundary
select — so HBM sees only perfectly aligned streaming loads.

Design (role parity with the reference's hand-tuned SpMV leaf,
``src/sparse/array/csr/spmv.cu:62-152``):

- **Row-aligned band layout**: ``rdata[d, i] = A[i, i + off_d]``
  (vs scipy DIA's column-aligned ``data[d, j] = A[j - off_d, j]``), so
  the kernel's data tile multiplies an x window shifted by ``off_d``
  with no data-side shift.  Out-of-range, padding and hole slots hold
  +0.0; an explicit entry never does (see the IEEE invariant below).
- The x vector is viewed as three aligned neighbor tiles
  (prev/center/next, clamped at the edges) so a shifted window never
  needs a misaligned HBM load; Mosaic requires dynamic vector loads to
  be 1024-element aligned, which is exactly what this avoids.  A shard
  of the distributed SpMV passes its two halos as edge tiles: the prev
  view at the grid's first step and the next view at its last come
  from them, so the kernel reads the shard's x where it lies.
- A flat shift by ``s = q*L + r`` (floor divmod, lane width L=128)
  becomes: sublane-roll by ``q`` (and ``q+1``), lane-roll by ``r``,
  then a lane-index select between the two — three register ops, no
  relayout.
- IEEE invariant, carried in the sign of zero: the pack stores every
  hole, out-of-range and padding slot as +0.0 (bits ``0x00000000``)
  and every explicit entry whose value is +0.0 as -0.0, so a slot is
  an entry iff its bits are not zero.  The kernel adds
  ``d * where(bits != 0, x, 0)``: a non-finite x entry a row never
  references cannot inject NaN (0 * inf), while an explicit zero still
  propagates it — CSR semantics exactly (same contract as
  ``ops/spmv.py``).  The encoding is exact: -0.0 * x differs from
  +0.0 * x only in the sign of a zero, and the accumulator starts at
  +0.0, where +0 + (-0) = +0, so every sum is bit-identical to the
  masked form, but for one sign of zero: the compiled kernel (Mosaic
  on the v5e, XLA in interpret mode) folds the +0.0 start away, so a
  row whose every live product is -0.0 (explicit zeros times positive
  x) reads -0.0 where CSR reads +0.0.  The band streams 4 bytes a slot
  (2 for bf16) and no mask.

Supported: f32/bf16 values (f64 is rejected — Mosaic has no 64-bit
vectors; the XLA path in ``ops/dia_ops.py`` serves f64),
``max|offset| <= tile`` (tile auto-grows to 2^17), any rectangular
shape.  bf16 tiles are widened to f32 inside VMEM before the rolls
(Mosaic rotates 32-bit data only) and accumulate in f32.  Whether the
kernel serves a band is decided before dispatch (``supported``, the
VMEM model, the platform); a kernel the dispatch chose either runs or
raises — no compile error is caught and replaced by the XLA path.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs as _obs
from . import dia_ops as _dia_ops

L = 128                 # TPU lane width
TILE_MIN = 1 << 14      # default rows per grid step (multiple of 1024)
TILE_MAX = 1 << 17      # beyond this the VMEM working set is too large
# Scoped-VMEM limit requested from Mosaic for every band kernel (the
# compiler's default is 16 MiB; v5e has 128 MiB per core), and the
# budget the VMEM models below hold a grid step's double-buffered
# blocks, lane padding included, to — the rest is headroom for the
# compiler's own temporaries (the rolled copies of the x window).
_VMEM_LIMIT = 64 << 20
_VMEM_BUDGET = 48 << 20
# int32 zero for index maps: a bare Python 0 binds as i64 in an
# x64-enabled process, which Mosaic cannot return from an index map.
_I0 = np.int32(0)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


def _spmv_vmem(tile: int, nd: int, itemsize: int) -> int:
    """Modeled VMEM of one SpMV grid step: the three x views, the
    output and the band as (tile/L, L) blocks — lane-exact, no padding
    — double-buffered, plus the f32 window and its rolled copies."""
    blocks = 4 * tile * itemsize + nd * tile * itemsize
    return 2 * blocks + 12 * tile * 4


def _spmm_vmem(tile: int, k: int, nd: int, itemsize: int) -> int:
    """Modeled VMEM of one SpMM grid step: the SpMV blocks with the x
    views and the output widened k-fold (transposed, lane-exact),
    double-buffered, plus the f32 window, its rolled copies and the
    accumulator."""
    blocks = 4 * k * tile * itemsize + nd * tile * itemsize
    return 2 * blocks + 12 * k * tile * 4


def stream_bytes(rdata, x_itemsize: int, k: int = 1) -> int:
    """Bytes one band-kernel call's blocks stream, as each grid step
    names them: the band values ``rdata``, the three x tiles and the
    output, for ``k`` right-hand sides."""
    nd, row_blocks, _ = rdata.shape
    itemsize = rdata.dtype.itemsize
    return row_blocks * L * (nd * itemsize
                             + k * (3 * x_itemsize + itemsize))


def _tile_override() -> Optional[int]:
    """Operator-forced rows-per-grid-step (``LEGATE_SPARSE_TPU_PALLAS_TILE``,
    power of two in [2^10, TILE_MAX]).  Exists for on-chip tuning and
    for tests: the tile sets the grid length (2^24 rows = 1024 steps at
    the default 2^14), so a small forced tile runs a multi-step grid at
    a CPU-sized band.  Read at dispatch time; invalid values are
    ignored with a warning."""
    v = os.environ.get("LEGATE_SPARSE_TPU_PALLAS_TILE")
    if not v:
        return None
    try:
        t = int(v)
        if t >= 1024 and t <= TILE_MAX and (t & (t - 1)) == 0:
            return t
    except ValueError:
        pass
    import sys

    sys.stderr.write(
        f"legate_sparse_tpu: ignoring invalid "
        f"LEGATE_SPARSE_TPU_PALLAS_TILE={v!r}\n"
    )
    return None


def _auto_tile(max_abs_off: int) -> Optional[int]:
    """Smallest default tile covering the band reach, or None."""
    tile = TILE_MIN
    while tile < max_abs_off and tile < TILE_MAX:
        tile *= 2
    return tile if max_abs_off <= tile else None


def choose_tile(max_abs_off: int) -> Optional[int]:
    """Smallest supported tile covering the band reach, or None.
    An operator override wins when it covers the reach."""
    forced = _tile_override()
    if forced is not None and max_abs_off <= forced:
        return forced
    return _auto_tile(max_abs_off)


def supported(offsets: Tuple[int, ...], dtype) -> Optional[int]:
    """Return the tile size to use, or None when the kernel can't run."""
    if np.dtype(dtype) not in (np.dtype(np.float32),
                               np.dtype(jnp.bfloat16)):
        return None
    if not offsets:
        return None
    nd = len(offsets)
    itemsize = np.dtype(dtype).itemsize

    def vmem_of(t: int) -> int:
        return _spmv_vmem(t, nd, itemsize)

    tile = choose_tile(max(abs(o) for o in offsets))
    if tile is None:
        return None
    if vmem_of(tile) > _VMEM_BUDGET:
        if _tile_override() == tile:
            # A forced tile that blows the VMEM budget must degrade to
            # the auto choice (warned), not silently disable the
            # kernel — same contract as an invalid override value.
            import sys

            auto = _auto_tile(max(abs(o) for o in offsets))
            if auto is not None and vmem_of(auto) <= _VMEM_BUDGET:
                sys.stderr.write(
                    f"legate_sparse_tpu: LEGATE_SPARSE_TPU_PALLAS_TILE="
                    f"{tile} exceeds the VMEM budget for this band; "
                    f"using tile {auto}\n"
                )
                return auto
        return None
    return tile


def encode_band(values, entry):
    """The pack's slot encoding: an explicit entry (``entry`` True)
    keeps its value, except +0.0, which becomes -0.0; every other slot
    is +0.0.  A slot is then an entry iff its bits are not zero."""
    signed = jnp.where(values == 0, jnp.asarray(-0.0, values.dtype),
                       values)
    return jnp.where(entry, signed, jnp.zeros((), values.dtype))


@partial(jax.jit, static_argnames=("offsets", "shape", "tile"))
def row_align(dia_data, offsets: Tuple[int, ...], shape: Tuple[int, int],
              tile: int, mask=None):
    """Repack scipy-layout DIA storage into the kernel's row-aligned,
    tile-padded 2-D block layout, (nd, rows_pad // L, L), with
    ``rdata[d, i] = dia_data[d, i + off_d]`` at explicit entries
    (``encode_band``: +0.0 stored as -0.0) and +0.0 at out-of-range,
    padding and hole slots.  ``mask`` marks the explicit entries of a
    holey band; without it every in-range slot is one.  Runs once per
    matrix at structure-cache build (the analog of Legion caching image
    partitions, ref §3.2).
    """
    rows, cols = shape
    rows_pad = -(-rows // tile) * tile
    width = dia_data.shape[1]

    def shift_one(row, off):
        # out[i] = row[i + off] for 0 <= i + off < width, else 0.
        # Right pad covers tall matrices (rows_pad > width) so the
        # slice end tile+off+rows_pad always stays in range.
        padded = jnp.pad(row, (tile, tile + rows_pad))
        return jax.lax.dynamic_slice(padded, (tile + off,), (rows_pad,))

    parts = []
    i = jnp.arange(rows_pad, dtype=jnp.int32)
    for d, off in enumerate(offsets):
        entry = (
            (i + off >= 0) & (i + off < min(cols, width)) & (i < rows)
        )
        if mask is not None:
            entry = entry & shift_one(mask[d], off)
        shifted = shift_one(dia_data[d], off)
        parts.append(encode_band(shifted, entry).reshape(-1, L))
    return jnp.stack(parts)


def _flat_shift(w, s: int, lane, interpret: bool, axis: int = 0):
    """xs with ``xs_flat[p] = w_flat[p + s]`` along the flattened last
    two dims of ``w`` (.., R, L); leading dims (axis base > 0) are
    batch.  Rows wrap modulo R — callers only read rows whose sources
    stay in bounds.  Lowered as sublane+lane rolls plus a lane select
    against the caller-built ``lane`` iota (same shape as ``w``)."""
    R = w.shape[axis]
    q, r = divmod(s, L)

    if interpret:
        roll = lambda a, amt, ax: jnp.roll(a, amt, ax)
    else:
        from jax.experimental.pallas import tpu as pltpu

        # The shift operand must be i32: a plain Python int binds as a
        # weak i64 constant in an x64-enabled process, which
        # tpu.dynamic_rotate rejects at Mosaic verification.
        roll = lambda a, amt, ax: pltpu.roll(a, np.int32(amt), ax)

    def rowroll(q_):
        amt = (R - q_) % R
        return roll(w, amt, axis) if amt else w

    if r == 0:
        return rowroll(q)
    a = roll(rowroll(q), L - r, axis + 1)
    b = roll(rowroll(q + 1), L - r, axis + 1)
    return jnp.where(lane < L - r, a, b)


def _band_term(d, xs):
    """One diagonal's products: ``d * xs`` where the slot holds an
    entry (bits not zero, ``encode_band``), exactly 0 elsewhere (+0.0
    times a zeroed x), so an x the row never references cannot inject
    NaN.  The select zeroes x, not the product, so every slot takes
    the one multiply-add, whether or not the compiler fuses it.  bf16
    is widened to f32 first, which keeps the sign of a zero."""
    d = d.astype(jnp.float32)
    live = jax.lax.bitcast_convert_type(d, jnp.int32) != 0
    return d * jnp.where(live, xs, jnp.zeros((), jnp.float32))


def _make_kernel(offsets: Tuple[int, ...], tile: int, interpret: bool,
                 last: Optional[int] = None):
    """The SpMV kernel.  With ``last`` (the grid's last step) it takes
    two edge tiles after the three x views: the prev view at step 0 is
    the low edge, the next view at step ``last`` the high edge."""
    Rt = tile // L

    def band(xm, xc, xp, d_ref, y_ref):
        # Widened before the rolls: Mosaic rotates 32-bit data only.
        w = jnp.concatenate([xm, xc, xp], axis=0).astype(jnp.float32)
        lane3 = jax.lax.broadcasted_iota(jnp.int32, (3 * Rt, L), 1)
        acc = jnp.zeros((Rt, L), jnp.float32)
        for di, off in enumerate(offsets):
            xs = _flat_shift(w, off, lane3, interpret)[Rt: 2 * Rt]
            acc = acc + _band_term(d_ref[di], xs)
        y_ref[:] = acc.astype(y_ref.dtype)

    if last is None:
        def kernel(xm_ref, xc_ref, xp_ref, d_ref, y_ref):
            band(xm_ref[:], xc_ref[:], xp_ref[:], d_ref, y_ref)

        return kernel

    import jax.experimental.pallas as pl

    def kernel(xm_ref, xc_ref, xp_ref, lo_ref, hi_ref, d_ref, y_ref):
        f32 = lambda ref: ref[:].astype(jnp.float32)
        step = jnp.full((Rt, L), pl.program_id(0), jnp.int32)
        xm = jnp.where(step == 0, f32(lo_ref), f32(xm_ref))
        xp = jnp.where(step == last, f32(hi_ref), f32(xp_ref))
        band(xm, f32(xc_ref), xp, d_ref, y_ref)

    return kernel


@partial(jax.jit,
         static_argnames=("offsets", "shape", "tile", "interpret"))
def pallas_dia_spmv(rdata, x, offsets: Tuple[int, ...],
                    shape: Tuple[int, int], tile: int,
                    interpret: bool = False, edges=None):
    """y = A @ x over the row-aligned band layout (see ``row_align``).

    ``rdata`` blocked (nd, rows_pad//L, L); x of length cols.

    ``edges``, a pair of (tile/L, L) tiles, stands for the x below
    row 0 and past the last row: the prev view of the grid's first
    step and the next view of its last.  A shard passes its halos so,
    and the kernel reads x where it lies; x then has to be a whole
    number of tiles, one per grid step.
    """
    import jax.experimental.pallas as pl

    rows, cols = shape
    Rt = tile // L
    nd = len(offsets)
    rows_pad = rdata.shape[1] * L
    nt = rows_pad // tile
    if edges is None:
        # x padded so every clamped neighbor-tile view is in range.
        x_pad = -(-max(cols, rows_pad) // tile) * tile
        ntx = x_pad // tile
        xv = jnp.pad(x, (0, x_pad - cols)).reshape(-1, L)
    else:
        if cols != rows_pad:
            raise ValueError(f"edge tiles need x of {rows_pad} rows, "
                             f"one tile a grid step; got {cols}")
        ntx = nt
        xv = x.reshape(-1, L)
    views = [
        pl.BlockSpec((Rt, L), lambda i: (jnp.maximum(i - 1, 0), _I0)),
        pl.BlockSpec((Rt, L), lambda i: (jnp.minimum(i, ntx - 1), _I0)),
        pl.BlockSpec((Rt, L),
                     lambda i: (jnp.minimum(i + 1, ntx - 1), _I0)),
    ]
    band = pl.BlockSpec((nd, Rt, L), lambda i: (_I0, i, _I0))
    if edges is None:
        kernel = _make_kernel(offsets, tile, interpret)
        in_specs, args = views + [band], (xv, xv, xv, rdata)
    else:
        # A constant block index: each edge tile is fetched once a call.
        edge = pl.BlockSpec((Rt, L), lambda i: (_I0, _I0))
        kernel = _make_kernel(offsets, tile, interpret, last=nt - 1)
        in_specs = views + [edge, edge, band]
        args = (xv, xv, xv, *edges, rdata)

    y2 = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows_pad // L, L), rdata.dtype),
        grid=(nt,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((Rt, L), lambda i: (i, _I0)),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*args)
    return y2.reshape(-1)[:rows]


def _make_spmm_kernel(offsets: Tuple[int, ...], tile: int,
                      interpret: bool):
    """SpMM (dense multi-RHS) variant: the SpMV kernel batched over the
    k columns of X.  X rides transposed as lane-dense (k, tile/L, L)
    blocks, so every shift is the SpMV's flat shift (batched over k)
    and the (tile/L, L) band block broadcasts over k.  A row-major
    (tile, k) X block would need the band as (tile, 1) columns, which
    the TPU pads to a full lane each — 128x the band's bytes."""
    Rt = tile // L

    def kernel(xm_ref, xc_ref, xp_ref, d_ref, y_ref):
        # Widened before the rolls: Mosaic rotates 32-bit data only.
        w = jnp.concatenate([xm_ref[:], xc_ref[:], xp_ref[:]],
                            axis=1).astype(jnp.float32)
        lane3 = jax.lax.broadcasted_iota(jnp.int32, w.shape, 2)
        acc = jnp.zeros((w.shape[0], Rt, L), jnp.float32)
        for di, off in enumerate(offsets):
            xs = _flat_shift3(w, off, lane3, interpret)[:, Rt: 2 * Rt, :]
            acc = acc + _band_term(d_ref[di][None], xs)
        y_ref[:] = acc.astype(y_ref.dtype)

    return kernel


# Widest dense X the SpMM kernel takes before falling back (VMEM: the
# three neighbor tiles + output at k lanes each).
SPMM_MAX_K = 1024


@partial(jax.jit,
         static_argnames=("offsets", "shape", "tile", "interpret"))
def pallas_dia_spmm(rdata, X, offsets: Tuple[int, ...],
                    shape: Tuple[int, int], tile: int,
                    interpret: bool = False):
    """Y = A @ X for dense X (cols, k) over the row-aligned band pack.

    ``tile`` divides the pack's SpMV tile (``_spmm_tile``).  X and Y
    cross HBM transposed, (k, rows/L, L), the layout the kernel reads.
    """
    import jax.experimental.pallas as pl

    rows, cols = shape
    nd = len(offsets)
    k = X.shape[1]
    Rt = tile // L
    rows_pad = rdata.shape[1] * L
    nt = rows_pad // tile
    x_pad = -(-max(cols, rows_pad) // tile) * tile
    ntx = x_pad // tile
    XT = jnp.pad(X, ((0, x_pad - cols), (0, 0))).T.reshape(k, -1, L)

    YT = pl.pallas_call(
        _make_spmm_kernel(offsets, tile, interpret),
        out_shape=jax.ShapeDtypeStruct((k, rows_pad // L, L),
                                       rdata.dtype),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((k, Rt, L),
                         lambda i: (_I0, jnp.maximum(i - 1, 0), _I0)),
            pl.BlockSpec((k, Rt, L),
                         lambda i: (_I0, jnp.minimum(i, ntx - 1), _I0)),
            pl.BlockSpec((k, Rt, L),
                         lambda i: (_I0, jnp.minimum(i + 1, ntx - 1), _I0)),
            pl.BlockSpec((nd, Rt, L), lambda i: (_I0, i, _I0)),
        ],
        out_specs=pl.BlockSpec((k, Rt, L), lambda i: (_I0, i, _I0)),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(XT, XT, XT, rdata)
    return YT.reshape(k, rows_pad)[:, :rows].T


def _spmm_tile(packed, k: int) -> Optional[int]:
    """Row-tile for the SpMM kernel: VMEM scales with k, so it is
    chosen per (band, k) — the largest power-of-two divisor of the
    SpMV tile that still covers the band reach (and whole (8, L) row
    blocks) and fits the modeled VMEM."""
    max_off = max(abs(o) for o in packed.offsets)
    itemsize = np.dtype(packed.rdata.dtype).itemsize
    nd = len(packed.offsets)
    tile = packed.tile
    while tile >= max(8 * L, max_off):
        if _spmm_vmem(tile, k, nd, itemsize) <= _VMEM_BUDGET:
            return tile
        tile //= 2
    return None


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def dia_spmm_maybe_pallas(packed, X):
    """SpMM through the Pallas kernel, or None when the kernel does not
    serve this operand (the caller runs the XLA path).  The decision is
    made here, before dispatch; a kernel chosen runs or raises."""
    mode = _mode()
    if mode == "0" or packed is None:
        return None
    k = X.shape[1]
    if k == 0 or k > SPMM_MAX_K:
        return None
    interpret = mode == "interpret"
    if not interpret and not _on_tpu():
        return None
    tile = _spmm_tile(packed, k)
    if tile is None:
        return None
    with _obs.span("pallas.spmm", tile=tile, k=int(k),
                   num_diags=len(packed.offsets),
                   bytes=stream_bytes(packed.rdata, X.dtype.itemsize,
                                      int(k))):
        return pallas_dia_spmm(
            packed.rdata, X, packed.offsets, packed.shape, tile,
            interpret=interpret,
        )


def _make_spgemm_kernel(offs_a: Tuple[int, ...], offs_b: Tuple[int, ...],
                        offs_c: Tuple[int, ...], shape_a, shape_b,
                        tile: int, interpret: bool):
    """Banded SpGEMM: C[oc, j] += A[oa, j-ob] * B[ob, j] over all
    (oa, ob) pairs.  B and C are j-aligned; only A needs the roll-shift
    (by -ob), so per B-diagonal ALL of A's diagonals shift together.
    Exact bands only (the dispatch gates on no hole masks), so validity
    is the static per-pair range [j_lo, j_hi)."""
    m, k = shape_a
    _, n = shape_b
    Rt = tile // L
    idx_c = {o: i for i, o in enumerate(offs_c)}

    def kernel(am_ref, ac_ref, ap_ref, b_ref, c_ref):
        import jax.experimental.pallas as pl

        base = pl.program_id(0) * tile
        dtype = b_ref.dtype
        acc_dtype = jnp.float32 if dtype != jnp.float64 else dtype
        # Widened before the rolls: Mosaic rotates 32-bit data only.
        wA = jnp.concatenate([am_ref[:], ac_ref[:], ap_ref[:]],
                             axis=1).astype(acc_dtype)
        lane3 = jax.lax.broadcasted_iota(
            jnp.int32, (wA.shape[0], 3 * Rt, L), 2
        )
        row_t = jax.lax.broadcasted_iota(jnp.int32, (Rt, L), 0)
        lane_t = jax.lax.broadcasted_iota(jnp.int32, (Rt, L), 1)
        gj = base + row_t * L + lane_t           # global output column
        accs = [jnp.zeros((Rt, L), acc_dtype) for _ in offs_c]
        for b_i, ob in enumerate(offs_b):
            # One shift serves every A diagonal for this ob.
            xsA = _flat_shift3(wA, -ob, lane3, interpret)[:, Rt: 2 * Rt, :]
            bt = b_ref[b_i].astype(acc_dtype)
            for a_i, oa in enumerate(offs_a):
                oc = oa + ob
                j_lo = max(0, ob, oc)
                j_hi = min(n, k + ob, m + oc)
                if j_hi <= j_lo:
                    continue
                valid = (gj >= j_lo) & (gj < j_hi)
                contrib = jnp.where(valid, xsA[a_i] * bt,
                                    jnp.zeros((), acc_dtype))
                ci = idx_c[oc]
                accs[ci] = accs[ci] + contrib
        c_ref[:] = jnp.stack(accs).astype(dtype)

    return kernel


def _flat_shift3(w3, s: int, lane3, interpret: bool):
    """Batched ``_flat_shift`` over a (nd, R, L) stack (axis base 1)."""
    return _flat_shift(w3, s, lane3, interpret, axis=1)


@partial(jax.jit, static_argnames=("offs_a", "offs_b", "offs_c",
                                   "shape_a", "shape_b", "tile",
                                   "interpret"))
def pallas_dia_spgemm(a_data, b_data, offs_a: Tuple[int, ...],
                      offs_b: Tuple[int, ...], offs_c: Tuple[int, ...],
                      shape_a: Tuple[int, int],
                      shape_b: Tuple[int, int], tile: int,
                      interpret: bool = False):
    """C_dia = A_dia @ B_dia (scipy column-aligned layout in and out,
    C width = cols of B), Mosaic-rolled — the banded-SpGEMM analog of
    ``pallas_dia_spmv``.  Returns (ndc, n)."""
    import jax.experimental.pallas as pl

    _, k = shape_a
    n = shape_b[1]
    Rt = tile // L
    nda, ndb, ndc = len(offs_a), len(offs_b), len(offs_c)

    # Pad both bands' widths to tile multiples; A's far enough that a
    # clamped neighbor view always exists for the C grid.
    pc = -(-n // tile) * tile
    pa = -(-max(k, pc) // tile) * tile
    nta = pa // tile
    av = jnp.pad(a_data, ((0, 0), (0, pa - k))).reshape(nda, -1, L)
    bv = jnp.pad(b_data, ((0, 0), (0, pc - n))).reshape(ndb, -1, L)

    kernel = _make_spgemm_kernel(offs_a, offs_b, offs_c, shape_a,
                                 shape_b, tile, interpret)
    a_specs = [
        pl.BlockSpec((nda, Rt, L),
                     lambda i: (_I0, jnp.maximum(i - 1, 0), _I0)),
        pl.BlockSpec((nda, Rt, L),
                     lambda i: (_I0, jnp.minimum(i, nta - 1), _I0)),
        pl.BlockSpec((nda, Rt, L),
                     lambda i: (_I0, jnp.minimum(i + 1, nta - 1), _I0)),
    ]
    C = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((ndc, pc // L, L), b_data.dtype),
        grid=(pc // tile,),
        in_specs=[*a_specs,
                  pl.BlockSpec((ndb, Rt, L), lambda i: (_I0, i, _I0))],
        out_specs=pl.BlockSpec((ndc, Rt, L), lambda i: (_I0, i, _I0)),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(av, av, av, bv)
    return C.reshape(ndc, -1)[:, :n]


def _spgemm_tile(offs_b, nda, ndb, ndc, dtype) -> Optional[int]:
    """Tile for the banded SpGEMM kernel: must cover the B-offset
    reach (A is shifted by -ob) and fit the modeled VMEM: the three A
    views, B and C as lane-exact blocks, double-buffered, plus the f32
    A window, its rolled copies and the C accumulators."""
    max_ob = max(abs(o) for o in offs_b) if offs_b else 0
    tile = choose_tile(max_ob)
    if tile is None:
        return None
    itemsize = np.dtype(dtype).itemsize

    def vmem_of(t: int) -> int:
        return (2 * (3 * nda + ndb + ndc) * t * itemsize
                + (9 * nda + ndc) * t * 4)

    while vmem_of(tile) > _VMEM_BUDGET and tile > TILE_MIN:
        tile //= 2
    if tile < max_ob or vmem_of(tile) > _VMEM_BUDGET:
        return None
    return tile


def dia_spgemm_maybe_pallas(a_data, b_data, offs_a, offs_b, offs_c,
                            shape_a, shape_b):
    """Banded SpGEMM through the Pallas kernel, or None when the kernel
    does not serve these bands (the caller runs the XLA path)."""
    mode = _mode()
    if mode == "0":
        return None
    if np.dtype(a_data.dtype) not in (np.dtype(np.float32),
                                      np.dtype(jnp.bfloat16)):
        return None
    if a_data.dtype != b_data.dtype:
        # The XLA path promotes to result_type(a, b); the kernel emits
        # b's dtype — mixed inputs must not change result dtype by
        # backend.
        return None
    interpret = mode == "interpret"
    if not interpret and not _on_tpu():
        return None
    tile = _spgemm_tile(offs_b, len(offs_a), len(offs_b), len(offs_c),
                        a_data.dtype)
    if tile is None:
        return None
    with _obs.span("pallas.spgemm", tile=tile, num_diags_c=len(offs_c)):
        return pallas_dia_spgemm(a_data, b_data, offs_a, offs_b, offs_c,
                                 shape_a, shape_b, tile,
                                 interpret=interpret)


# Runtime dispatch gate: default ON for TPU backends, opt out with
# LEGATE_SPARSE_TPU_PALLAS_DIA=0.  "interpret" forces the
# interpret-mode kernel on CPU (differential testing of the exact
# kernel logic without a chip).
def _mode() -> str:
    return os.environ.get("LEGATE_SPARSE_TPU_PALLAS_DIA", "1")


def pallas_dist_mode() -> str:
    """Mode for the *distributed* per-shard Pallas route: env override
    (``LEGATE_SPARSE_TPU_PALLAS_DIST`` = 0|1|interpret), else default-on
    on TPU and off elsewhere (interpret mode is pure-Python slow; tests
    opt in explicitly)."""
    v = os.environ.get("LEGATE_SPARSE_TPU_PALLAS_DIST")
    if v is not None:
        return v
    return "1" if _on_tpu() else "0"


def pallas_dia_active() -> bool:
    """Cheap pre-check so callers skip building the row-aligned pack
    (which doubles band storage) when the kernel can never run."""
    mode = _mode()
    if mode == "0":
        return False
    return mode == "interpret" or _on_tpu()


def dia_spmv_maybe_pallas(packed, x):
    """Run the Pallas kernel from a ``PackedBand``, or return None so
    the caller uses the XLA path (no pack, kernel off, or not on TPU).
    A pack exists only for bands ``supported`` accepted, so the kernel
    chosen here runs or raises."""
    mode = _mode()
    if mode == "0" or packed is None:
        return None
    interpret = mode == "interpret"
    if not interpret and not _on_tpu():
        return None
    with _obs.span("pallas.spmv", tile=packed.tile,
                   num_diags=len(packed.offsets),
                   bytes=stream_bytes(packed.rdata, x.dtype.itemsize)):
        return pallas_dia_spmv(
            packed.rdata, x, packed.offsets, packed.shape, packed.tile,
            interpret=interpret,
        )


class PackedBand:
    """Cached row-aligned band pack (built once per matrix structure)."""

    __slots__ = ("rdata", "offsets", "shape", "tile")

    def __init__(self, rdata, offsets, shape, tile):
        self.rdata = rdata
        self.offsets = offsets
        self.shape = shape
        self.tile = tile


def pack_band(dia_data, offsets: Tuple[int, ...], shape: Tuple[int, int],
              mask=None) -> Optional[PackedBand]:
    """Build the kernel's layout from the scipy-layout DIA cache
    (``csr_array._get_dia()`` output: ``mask`` marks a holey band's
    explicit entries), or None when ``supported`` declines the band.
    Counts the in-range slots it folds to +0.0 under
    ``pallas.pack.hole_slots``."""
    tile = supported(offsets, dia_data.dtype)
    if tile is None:
        return None
    holes = 0
    if mask is not None:
        covered = _dia_ops.band_cover(offsets, shape, dia_data.shape[1])
        holes = covered - int(jnp.count_nonzero(mask))
    _obs.inc("pallas.pack.hole_slots", holes)
    rdata = row_align(dia_data, offsets, shape, tile, mask=mask)
    return PackedBand(rdata, offsets, shape, tile)
