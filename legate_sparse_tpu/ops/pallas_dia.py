# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Pallas TPU kernel for banded (DIA) SpMV — the roofline hot path.

Why this kernel exists (measured on the target v5e chip, 2^24 rows,
11 diagonals, f32, loop-amortized timing):

=====================================  ==========
formulation                            bandwidth
=====================================  ==========
XLA ``.at[lo:hi].add`` shifted adds     51 GB/s
XLA pad + slice shifted adds            84 GB/s
XLA ``jnp.roll`` shifted adds           74 GB/s
MXU shift-matmul                        49 GB/s
**this kernel**                        **622 GB/s**
chip HBM roofline (v5e)                 819 GB/s
=====================================  ==========

Every XLA formulation of the stencil shift pays a full lane-relayout
per diagonal (a flat shift by ±1 moves every element across the
(8, 128) tiled layout), so the op runs ~10x under roofline.  The
Mosaic-level fix: keep the shift *inside* VMEM as register rotates —
``pltpu.roll`` on the lane and sublane axes plus a lane-boundary
select — so HBM sees only perfectly aligned streaming loads.

Design (role parity with the reference's hand-tuned SpMV leaf,
``src/sparse/array/csr/spmv.cu:62-152``):

- **Row-aligned band layout**: ``rdata[d, i] = A[i, i + off_d]``
  (vs scipy DIA's column-aligned ``data[d, j] = A[j - off_d, j]``), so
  the kernel's data tile multiplies an x window shifted by ``off_d``
  with no data-side shift.  Out-of-range and hole slots hold 0.
- The x vector is viewed as three aligned neighbor tiles
  (prev/center/next, clamped at the edges) so a shifted window never
  needs a misaligned HBM load; Mosaic requires dynamic vector loads to
  be 1024-element aligned, which is exactly what this avoids.
- A flat shift by ``s = q*L + r`` (floor divmod, lane width L=128)
  becomes: sublane-roll by ``q`` (and ``q+1``), lane-roll by ``r``,
  then a lane-index select between the two — three register ops, no
  relayout.
- IEEE invariant: shifted x values are zeroed *before* the multiply at
  out-of-range slots and band holes (explicit-entry mask), so a
  non-finite x entry a row never references cannot inject NaN —
  matching CSR semantics exactly (same contract as ``ops/spmv.py``).

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


def _spmv_vmem(tile: int, nd: int, itemsize: int, masked: bool) -> int:
    """Modeled VMEM of one SpMV grid step: the three x views, the
    output and the band (plus its int8 mask) as (tile/L, L) blocks —
    lane-exact, no padding — double-buffered, plus the f32 window and
    its rolled copies."""
    blocks = 4 * tile * itemsize + nd * tile * (itemsize + masked)
    return 2 * blocks + 12 * tile * 4


def _spmm_vmem(tile: int, k: int, nd: int, itemsize: int,
               masked: bool) -> int:
    """Modeled VMEM of one SpMM grid step: the SpMV blocks with the x
    views and the output widened k-fold (transposed, lane-exact),
    double-buffered, plus the f32 window, its rolled copies and the
    accumulator."""
    blocks = 4 * k * tile * itemsize + nd * tile * (itemsize + masked)
    return 2 * blocks + 12 * k * tile * 4


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


def supported(offsets: Tuple[int, ...], dtype, masked: bool) -> Optional[int]:
    """Return the tile size to use, or None when the kernel can't run."""
    if np.dtype(dtype) not in (np.dtype(np.float32),
                               np.dtype(jnp.bfloat16)):
        return None
    if not offsets:
        return None
    nd = len(offsets)
    itemsize = np.dtype(dtype).itemsize

    def vmem_of(t: int) -> int:
        return _spmv_vmem(t, nd, itemsize, masked)

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


@partial(jax.jit, static_argnames=("offsets", "shape", "tile", "with_mask"))
def row_align(dia_data, offsets: Tuple[int, ...], shape: Tuple[int, int],
              tile: int, mask=None, with_mask: bool = False):
    """Repack scipy-layout DIA storage into the kernel's row-aligned,
    tile-padded 2-D block layout.

    Returns ``(rdata, rmask)``: rdata is (nd, rows_pad // L, L) with
    ``rdata[d, i] = dia_data[d, i + off_d]`` for in-range slots else 0;
    rmask (int8, same blocking) is all-1 at explicit entries when
    ``with_mask`` else None.  Runs once per matrix at structure-cache
    build (the analog of Legion caching image partitions, ref §3.2).
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
    mparts = []
    i = jnp.arange(rows_pad, dtype=jnp.int32)
    for d, off in enumerate(offsets):
        valid = (
            (i + off >= 0) & (i + off < min(cols, width)) & (i < rows)
        )
        shifted = shift_one(dia_data[d], off)
        parts.append(jnp.where(valid, shifted, 0).reshape(-1, L))
        if with_mask:
            ms = shift_one(mask[d].astype(jnp.int8), off)
            mparts.append(
                jnp.where(valid, ms, 0).astype(jnp.int8).reshape(-1, L)
            )
    rdata = jnp.stack(parts)
    rmask = jnp.stack(mparts) if with_mask else None
    return rdata, rmask


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


def _make_kernel(offsets: Tuple[int, ...], rows: int, cols: int,
                 tile: int, masked: bool, interpret: bool):
    Rt = tile // L

    def kernel(*refs):
        if masked:
            xm_ref, xc_ref, xp_ref, d_ref, m_ref, y_ref = refs
        else:
            xm_ref, xc_ref, xp_ref, d_ref, y_ref = refs
            m_ref = None
        import jax.experimental.pallas as pl

        base = pl.program_id(0) * tile
        dtype = d_ref.dtype
        acc_dtype = jnp.float32 if dtype != jnp.float64 else dtype
        # Widened before the rolls: Mosaic rotates 32-bit data only.
        w = jnp.concatenate([xm_ref[:], xc_ref[:], xp_ref[:]],
                            axis=0).astype(acc_dtype)
        lane3 = jax.lax.broadcasted_iota(jnp.int32, (3 * Rt, L), 1)
        row_t = jax.lax.broadcasted_iota(jnp.int32, (Rt, L), 0)
        lane_t = jax.lax.broadcasted_iota(jnp.int32, (Rt, L), 1)
        gi = base + row_t * L + lane_t            # global output row
        acc = jnp.zeros((Rt, L), acc_dtype)
        for di, off in enumerate(offsets):
            xs = _flat_shift(w, off, lane3, interpret)[Rt: 2 * Rt]
            valid = (gi + off >= 0) & (gi + off < cols) & (gi < rows)
            if masked:
                # v5e has no int8 vector compare: widen first.
                valid = valid & (m_ref[di].astype(jnp.int32) > 0)
            xsafe = jnp.where(valid, xs, jnp.zeros((), acc_dtype))
            acc = acc + d_ref[di].astype(acc_dtype) * xsafe
        y_ref[:] = acc.astype(dtype)

    return kernel


@partial(jax.jit,
         static_argnames=("offsets", "shape", "tile", "interpret"))
def pallas_dia_spmv(rdata, rmask, x, offsets: Tuple[int, ...],
                    shape: Tuple[int, int], tile: int,
                    interpret: bool = False):
    """y = A @ x over the row-aligned band layout (see ``row_align``).

    ``rdata``/``rmask`` blocked (nd, rows_pad//L, L); x of length cols.
    """
    import jax.experimental.pallas as pl

    rows, cols = shape
    Rt = tile // L
    nd = len(offsets)
    rows_pad = rdata.shape[1] * L
    nt = rows_pad // tile
    # x padded so every clamped neighbor-tile view is in range.
    x_pad = -(-max(cols, rows_pad) // tile) * tile
    ntx = x_pad // tile
    xv = jnp.pad(x, (0, x_pad - cols)).reshape(-1, L)

    masked = rmask is not None
    kernel = _make_kernel(offsets, rows, cols, tile, masked, interpret)

    in_specs = [
        pl.BlockSpec((Rt, L), lambda i: (jnp.maximum(i - 1, 0), _I0)),
        pl.BlockSpec((Rt, L), lambda i: (jnp.minimum(i, ntx - 1), _I0)),
        pl.BlockSpec((Rt, L), lambda i: (jnp.minimum(i + 1, ntx - 1), _I0)),
        pl.BlockSpec((nd, Rt, L), lambda i: (_I0, i, _I0)),
    ]
    args = [xv, xv, xv, rdata]
    if masked:
        in_specs.append(
            pl.BlockSpec((nd, Rt, L), lambda i: (_I0, i, _I0)))
        args.append(rmask)

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


def _make_spmm_kernel(offsets: Tuple[int, ...], rows: int, cols: int,
                      tile: int, masked: bool, interpret: bool):
    """SpMM (dense multi-RHS) variant: the SpMV kernel batched over the
    k columns of X.  X rides transposed as lane-dense (k, tile/L, L)
    blocks, so every shift is the SpMV's flat shift (batched over k)
    and the (tile/L, L) band block broadcasts over k.  A row-major
    (tile, k) X block would need the band as (tile, 1) columns, which
    the TPU pads to a full lane each — 128x the band's bytes."""
    Rt = tile // L

    def kernel(*refs):
        if masked:
            xm_ref, xc_ref, xp_ref, d_ref, m_ref, y_ref = refs
        else:
            xm_ref, xc_ref, xp_ref, d_ref, y_ref = refs
            m_ref = None
        import jax.experimental.pallas as pl

        base = pl.program_id(0) * tile
        dtype = d_ref.dtype
        acc_dtype = jnp.float32 if dtype != jnp.float64 else dtype
        # Widened before the rolls: Mosaic rotates 32-bit data only.
        w = jnp.concatenate([xm_ref[:], xc_ref[:], xp_ref[:]],
                            axis=1).astype(acc_dtype)
        lane3 = jax.lax.broadcasted_iota(jnp.int32, w.shape, 2)
        row_t = jax.lax.broadcasted_iota(jnp.int32, (Rt, L), 0)
        lane_t = jax.lax.broadcasted_iota(jnp.int32, (Rt, L), 1)
        gi = base + row_t * L + lane_t            # global output row
        acc = jnp.zeros((w.shape[0], Rt, L), acc_dtype)
        for di, off in enumerate(offsets):
            xs = _flat_shift3(w, off, lane3, interpret)[:, Rt: 2 * Rt, :]
            valid = (gi + off >= 0) & (gi + off < cols) & (gi < rows)
            if masked:
                valid = valid & (m_ref[di].astype(jnp.int32) > 0)
            xsafe = jnp.where(valid[None], xs, jnp.zeros((), acc_dtype))
            acc = acc + d_ref[di].astype(acc_dtype)[None] * xsafe
        y_ref[:] = acc.astype(dtype)

    return kernel


# Widest dense X the SpMM kernel takes before falling back (VMEM: the
# three neighbor tiles + output at k lanes each).
SPMM_MAX_K = 1024


@partial(jax.jit,
         static_argnames=("offsets", "shape", "tile", "interpret"))
def pallas_dia_spmm(rdata, rmask, X, offsets: Tuple[int, ...],
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

    masked = rmask is not None
    kernel = _make_spmm_kernel(offsets, rows, cols, tile, masked,
                               interpret)
    in_specs = [
        pl.BlockSpec((k, Rt, L),
                     lambda i: (_I0, jnp.maximum(i - 1, 0), _I0)),
        pl.BlockSpec((k, Rt, L),
                     lambda i: (_I0, jnp.minimum(i, ntx - 1), _I0)),
        pl.BlockSpec((k, Rt, L),
                     lambda i: (_I0, jnp.minimum(i + 1, ntx - 1), _I0)),
    ]
    args = [XT, XT, XT, rdata]
    in_specs.append(pl.BlockSpec((nd, Rt, L), lambda i: (_I0, i, _I0)))
    if masked:
        in_specs.append(
            pl.BlockSpec((nd, Rt, L), lambda i: (_I0, i, _I0)))
        args.append(rmask)

    YT = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((k, rows_pad // L, L),
                                       rdata.dtype),
        grid=(nt,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((k, Rt, L), lambda i: (_I0, i, _I0)),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*args)
    return YT.reshape(k, rows_pad)[:, :rows].T


def _spmm_tile(packed, k: int) -> Optional[int]:
    """Row-tile for the SpMM kernel: VMEM scales with k, so it is
    chosen per (band, k) — the largest power-of-two divisor of the
    SpMV tile that still covers the band reach (and whole (8, L) row
    blocks) and fits the modeled VMEM."""
    max_off = max(abs(o) for o in packed.offsets)
    itemsize = np.dtype(packed.rdata.dtype).itemsize
    nd = len(packed.offsets)
    masked = packed.rmask is not None
    tile = packed.tile
    while tile >= max(8 * L, max_off):
        if _spmm_vmem(tile, k, nd, itemsize, masked) <= _VMEM_BUDGET:
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
                   num_diags=len(packed.offsets)):
        return pallas_dia_spmm(
            packed.rdata, packed.rmask, X, packed.offsets,
            packed.shape, tile, interpret=interpret,
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
                   num_diags=len(packed.offsets)):
        return pallas_dia_spmv(
            packed.rdata, packed.rmask, x, packed.offsets,
            packed.shape, packed.tile, interpret=interpret,
        )


class PackedBand:
    """Cached row-aligned band pack (built once per matrix structure)."""

    __slots__ = ("rdata", "rmask", "offsets", "shape", "tile")

    def __init__(self, rdata, rmask, offsets, shape, tile):
        self.rdata = rdata
        self.rmask = rmask
        self.offsets = offsets
        self.shape = shape
        self.tile = tile


def pack_band(dia_data, offsets: Tuple[int, ...], shape: Tuple[int, int],
              mask=None) -> Optional[PackedBand]:
    """Build the kernel's layout from the scipy-layout DIA cache
    (``csr_array._get_dia()`` output), or None when ``supported``
    declines the band."""
    tile = supported(offsets, dia_data.dtype, mask is not None)
    if tile is None:
        return None
    rdata, rmask = row_align(
        dia_data, offsets, shape, tile,
        mask=mask, with_mask=mask is not None,
    )
    return PackedBand(rdata, rmask, offsets, shape, tile)
