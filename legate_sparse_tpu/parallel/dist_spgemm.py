# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Distributed SpGEMM: C = A @ B over a row mesh.

TPU-native analog of the reference's flagship multi-node operation — the
GPU single-phase SpGEMM with NCCL nnz-allgather (reference
``src/sparse/array/csr/spgemm_csr_csr_csr.cu:43-62`` global offsets,
driven from ``legate_sparse/csr.py:603-684``):

- Each shard computes its row block of C with the same ESC
  (expand-sort-compress) formulation as the single-device kernel
  (``ops/spgemm.py``) — vectorized over the shard's products, not a
  Gustavson scalar loop.
- The reference's *unbound stores* + NCCL allgather of local nnz become
  XLA's static-shape analog: two tiny collective phases that produce the
  per-shard product count and output nnz, a host sync of their maxima
  (exactly the role of the reference's blocking ``int(nnz)``,
  ``csr.py:714``), and padded (R, cap) output blocks.
- B's rows are realized per shard through a min/max column image of A
  (the reference's image-gather, ``legate_sparse/csr.py:640-666`` +
  ``src/sparse/partition/fast_image_partition.cu:29-55``): a host-side
  window plan maps each shard's A-column range onto B's row blocks, and
  only those blocks ride ring ``ppermute`` rotations — per-shard memory
  O(window · nnz(B)/R), not O(nnz(B)).  When the window covers most of
  the ring (dense/irregular A) the full ``all_gather`` realization is
  used instead (``_B_WINDOW_DENSE_FRAC``).

Phases (each one jitted shard_map over the row mesh):

1. ``T_local``  = per-shard product count        -> host max = T_cap
2. ``nnz_local`` = per-shard distinct (i,j) count -> host max = nnz_cap
3. numeric ESC -> padded-CSR row blocks (R, nnz_cap)

Returns a padded-CSR ``DistCSR`` whose cols are global indices
(all_gather realization; ``shard_csr``-style windows can rebase later).
"""

from __future__ import annotations

import threading
from functools import lru_cache, partial
from typing import NamedTuple, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs as _obs
from ..obs import latency as _lat
from ..types import index_dtype
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .dist_csr import DistCSR
from .mesh import COL_AXIS, ROW_AXIS


class _Layout(NamedTuple):
    """Static layout signature of a DistCSR — everything the ESC
    kernels read about an operand besides its arrays.  Used as the
    lru_cache key for the compiled shard_map phases, so it MUST capture
    every operand attribute the kernel closures consult (adding a new
    attribute read to a kernel without extending this key would leak
    stale compilations)."""

    ell: bool
    rps: int
    halo: int
    cps: int
    has_ggl: bool
    shape: Tuple[int, int]
    rows_padded: int
    num_shards: int
    inner: int          # W for ELL blocks, nnz_max for padded-CSR


def _layout_of(M: DistCSR) -> _Layout:
    return _Layout(
        ell=M.ell, rps=M.rows_per_shard, halo=M.halo,
        cps=M.cols_per_shard, has_ggl=M.gather_globals is not None,
        shape=M.shape, rows_padded=M.rows_padded,
        num_shards=M.num_shards, inner=int(M.cols.shape[-1]),
    )


def _a_local_flat(A: _Layout, data, cols, counts, row_ids, ggl=None):
    """Normalize a shard's A block to flat (a_row, a_col_global, a_val,
    a_valid) arrays of static length L.

    ``data``/``cols``/... are the shard-local blocks (leading R axis
    already consumed by shard_map).  Column indices are rebased back to
    global whatever the layout stores (halo-window-local or precise
    compact positions via ``ggl`` = the shard's gather_globals row).
    """
    rps = A.rps
    shard = jax.lax.axis_index(ROW_AXIS)
    start = shard.astype(index_dtype()) * rps

    if A.ell:
        R_, W = cols.shape  # (rps, W)
        a_row = jnp.broadcast_to(
            jnp.arange(rps, dtype=jnp.int32)[:, None], (rps, W)
        ).reshape(-1)
        slot = jnp.arange(W, dtype=counts.dtype)
        a_valid = (slot[None, :] < counts[:, None]).reshape(-1)
        a_col = cols.reshape(-1).astype(index_dtype())
        a_val = data.reshape(-1)
    else:
        a_row = row_ids
        nnz_max = data.shape[0]
        slot = jnp.arange(nnz_max, dtype=jnp.int32)
        a_valid = slot < counts
        a_col = cols.astype(index_dtype())
        a_val = data

    if A.has_ggl:
        base = ggl.reshape(-1)
        rc = base.shape[0]
        own = a_col - rc + shard.astype(index_dtype()) * A.cps
        a_col = jnp.where(
            a_col < rc, base[jnp.clip(a_col, 0, rc - 1)], own
        )
    elif A.halo >= 0:
        a_col = a_col + (start - A.halo)
    a_col = jnp.clip(a_col, 0, A.shape[1] - 1)
    return a_row, a_col, a_val, a_valid


def _b_global_flat(B: _Layout, data, cols, counts, row_ids, ggl=None):
    """All-gather B's blocks and expose flat per-row random access:
    (b_data_g, b_cols_g, b_start, b_counts) with global column indices.

    The ICI realization of the reference's image-gather of B
    (``csr.py:640-666``); one all_gather per phase, O(nnz(B)/R) words
    per link hop.  Precise-layout blocks are un-rebased per source
    block via the gathered ``gather_globals``.
    """
    R = B.num_shards
    rps = B.rps
    rows_p = B.rows_padded

    data_g = jax.lax.all_gather(data, ROW_AXIS)    # (R, ...) blocks
    cols_g = jax.lax.all_gather(cols, ROW_AXIS)
    counts_g = jax.lax.all_gather(counts, ROW_AXIS)
    if B.has_ggl:
        ggl_g = jax.lax.all_gather(ggl, ROW_AXIS)  # (R, R, C)
        # Un-rebase each source block with its own inverse map; the
        # appended-local region maps back to the block's own columns.
        per_block = cols_g.reshape(R, -1).astype(index_dtype())
        cps_b = B.cps
        s_ids = jnp.arange(R, dtype=index_dtype())

        def unreb(inv, c, s):
            base = inv.reshape(-1)
            rc = base.shape[0]
            own = c - rc + s * cps_b
            return jnp.where(c < rc, base[jnp.clip(c, 0, rc - 1)], own)

        cols_g = jax.vmap(unreb)(ggl_g, per_block, s_ids).reshape(
            cols_g.shape
        )

    if B.ell:
        W = cols.shape[-1]
        b_data_g = data_g.reshape(rows_p, W).reshape(-1)
        b_cols_g = cols_g.reshape(rows_p, W).reshape(-1).astype(index_dtype())
        b_counts = counts_g.reshape(rows_p).astype(jnp.int32)
        b_start = jnp.arange(rows_p, dtype=index_dtype()) * W
    else:
        rid_g = jax.lax.all_gather(row_ids, ROW_AXIS)   # (R, nnz_max)
        nnz_max = data.shape[-1]
        b_data_g = data_g.reshape(-1)
        b_cols_g = cols_g.reshape(-1).astype(index_dtype())
        # Per-row counts from the sorted local row ids: row r of block s
        # occupies [indptr_local[s, r], indptr_local[s, r+1]) clamped to
        # the block's valid prefix (padding replicates the last row id).
        slot = jnp.arange(nnz_max, dtype=jnp.int32)
        valid = slot[None, :] < counts_g[:, None]          # (R, nnz_max)
        ids_2d = jnp.where(valid, rid_g, rps)              # pad -> rps
        one = jnp.ones_like(ids_2d, dtype=jnp.int32)
        percount = jax.vmap(
            lambda ids, on: jax.ops.segment_sum(on, ids, num_segments=rps + 1)
        )(ids_2d, one)[:, :rps]                            # (R, rps)
        b_counts = percount.reshape(rows_p)
        starts_local = jnp.cumsum(percount, axis=1) - percount  # exclusive
        b_start = (
            starts_local.astype(index_dtype())
            + (jnp.arange(R, dtype=index_dtype()) * nnz_max)[:, None]
        ).reshape(rows_p)

    if B.halo >= 0:
        b_cols_g = _unrebase_b(B, b_cols_g, rps)
    b_cols_g = jnp.clip(b_cols_g, 0, B.shape[1] - 1)
    return b_data_g, b_cols_g, b_start, b_counts


def _unrebase_b(B: _Layout, b_cols_g, rps):
    """Undo halo-window rebasing on the gathered flat cols: entry j of
    block s stores local = global - (s*rps - halo)."""
    if B.ell:
        per_block = rps * B.inner
    else:
        per_block = B.inner
    block_of = jnp.arange(b_cols_g.shape[0], dtype=index_dtype()) // per_block
    return b_cols_g + block_of * rps - B.halo


# Window wider than this fraction of the ring -> the ppermute rotation
# chain stops paying for itself; use the one-shot all_gather.
_B_WINDOW_DENSE_FRAC = 0.75

# Legacy introspection globals: how dist_spgemm's last general-path
# call realized B ("window" | "all_gather"), and the plan used.  The
# SUPPORTED inspection mechanism is now the obs subsystem — the
# ``dist_spgemm`` span records ``b_realization``/``b_plan`` attributes
# and the ``dist_spgemm.realization.*`` counters accumulate the choice
# per call (``obs/counters.py``).  These two names stay for existing
# tests/scripts; new code should read the span attrs instead.
LAST_B_REALIZATION: str = ""
LAST_B_PLAN: tuple = ()


@lru_cache(maxsize=128)
def _col_window_fn(mesh, la: _Layout):
    """Per-shard global-column min/max of A (the FAST_IMAGE_RANGE
    analog, ``fast_image_partition.cu:29-55``): one tiny jitted
    shard_map, host-fetched once per (A, B) structure pair.

    The per-shard scalars are ``all_gather``-replicated before leaving
    the shard_map (out_specs ``P(None)``) so the host fetch is legal in
    multi-controller runs — a ``P(ROW_AXIS)``-sharded output would span
    non-addressable devices there and refuse ``np.asarray``.
    """
    _obs.inc("jit_miss.dist_spgemm.col_window_fn")
    in_specs = _esc_specs(la)
    big = la.shape[1]

    def kern(*a_args):
        a_row, a_col, a_val, a_valid = _a_local_flat(la, *_local(a_args))
        mn = jnp.min(jnp.where(a_valid, a_col, big))
        mx = jnp.max(jnp.where(a_valid, a_col, -1))
        return (jax.lax.all_gather(mn, ROW_AXIS),
                jax.lax.all_gather(mx, ROW_AXIS))

    return jax.jit(shard_map(
        kern, mesh=mesh, in_specs=in_specs,
        out_specs=(P(None), P(None)), check_vma=False,
    ))


def _density_bucket(nnz: int, rows: int) -> int:
    """log2 bucket of nnz-per-row — the sparsity term of the
    window-decline key (ADVICE r5 low): two matrices sharing a layout
    but an order of magnitude apart in density get separate decline
    entries, so one wide-window matrix no longer pins every later
    same-layout matrix to the all_gather realization."""
    import math

    if nnz <= 0 or rows <= 0:
        return -1
    per_row = nnz / rows
    return math.floor(math.log2(per_row)) if per_row >= 1 else -1


def _decline_key(A: DistCSR, la: _Layout, lb: _Layout):
    """Cache key for a declined window: layout structure PLUS A's
    nnz-density bucket (the window width is a property of A's column
    sparsity, which the layout alone does not capture) PLUS the full
    mesh+layout fingerprint.  The fingerprint term matters now that
    one matrix shape can be sharded several ways: without it, a 1-D
    verdict (window too wide at R row blocks) would be replayed
    against a 2-d-block layout of the same shape — or against the
    same shapes on a different device set — and wrongly pin it to
    all_gather.  ``nnz_hint`` is set by every builder; an externally
    constructed DistCSR pays one counts fetch, memoized on the
    instance.  NOTE: ``_window_decline`` reads the density bucket at
    ``key[2]`` — keep its position stable."""
    from .dist_csr import mesh_fingerprint

    nnz = A.nnz_hint
    if nnz < 0:
        nnz = A.global_nnz
        A.nnz_hint = nnz
    return (la, lb, _density_bucket(nnz, la.shape[0]),
            mesh_fingerprint(A.mesh, layout=A.layout))


def _b_window_plan(A: DistCSR, la: _Layout, lb: _Layout, a_arrays):
    """Host-side B-realization window plan, or None for all_gather.

    Maps each shard's A-column range onto B's row blocks (block t of B
    lives on shard t).  Returns ``(first_blks, (nblk, d_fwd, d_bwd))``:
    per-shard first window block (an int32 host array — passed to the
    phase kernels as a TRACED operand so sparsity drift between calls
    never recompiles them), plus the static shape knobs: window width
    in blocks and the max forward/backward ring distances the rotation
    chain must cover.  None when B is precise-layout (compact cols
    don't rotate) or the worst-case window is too wide to beat
    all_gather.
    """
    if lb.has_ggl:
        return None
    R = la.num_shards
    if R <= 2:
        return None         # rotation chain degenerates to all_gather
    key = _decline_key(A, la, lb)
    with _STATE_LOCK:
        declined = key in _WINDOW_DECLINED
    if declined:
        # This structure+density pair already proved too wide for a
        # window: skip the min/max image probe (a blocking
        # device->host round trip) on every
        # later call.  The key carries A's nnz-density bucket, so only
        # comparably-dense matrices inherit the decline; a sparser
        # same-layout matrix re-probes (``reset_window_declines()``
        # still clears everything).  Correctness is unaffected.
        _obs.inc("dist_spgemm.window_decline_cached")
        return None
    _obs.inc("transfer.host_sync.spgemm_window_probe")
    from ..obs import comm as _comm

    # Probe cost in the ledger: two 1-element all_gathers (min/max).
    _comm.record("dist_spgemm.window_probe", {
        "all_gather": 2 * _comm.all_gather_bytes(
            1, np.dtype(index_dtype()).itemsize, R),
    }, calls={"all_gather": 2})
    mn, mx = _col_window_fn(A.mesh, la)(*a_arrays)
    mn = np.asarray(mn)
    mx = np.asarray(mx)
    rps_b = lb.rps
    first = np.clip(mn // rps_b, 0, R - 1).astype(np.int64)
    last = np.clip(mx // rps_b, 0, R - 1).astype(np.int64)
    s_ids = np.arange(R)
    empty = mx < 0          # shard with no valid A entries
    first[empty] = s_ids[empty]
    last[empty] = s_ids[empty]
    nblk = int(np.max(last - first) + 1)
    # Floor of 3 so a 2-block window (any band crossing one shard
    # boundary) is accepted on small rings: at R=3 the 0.75 fraction
    # alone would make the window UNREACHABLE (limit 2 declines
    # nblk=2), turning every banded product into an all_gather.
    limit = max(3, int(R * _B_WINDOW_DENSE_FRAC))
    if nblk <= 0 or nblk >= limit:
        _window_decline(key, la, lb)
        return None
    d_fwd = int(np.max(np.maximum(s_ids - first, 0)))
    d_bwd = int(np.max(np.maximum(last - s_ids, 0)))
    if d_fwd + d_bwd >= R:
        _window_decline(key, la, lb)
        return None         # would rotate the whole ring anyway
    return first.astype(np.int32), (nblk, d_fwd, d_bwd)


_WINDOW_DECLINED: set = set()
# Guards the module-level mutable state above (_WINDOW_DECLINED and
# the LAST_B_* introspection globals): the engine's request executor
# makes concurrent dist_spgemm callers a supported configuration, and
# an unguarded size-check-then-clear/add on the set (or a torn
# REALIZATION/PLAN pair) is a real race there.  Device launches still
# serialize (tests/test_obs_concurrency.py: concurrent collective
# launches deadlock the XLA CPU backend); this lock only covers the
# host-side bookkeeping.
_STATE_LOCK = threading.Lock()


def _window_decline(key, la: _Layout, lb: _Layout) -> None:
    with _STATE_LOCK:
        if len(_WINDOW_DECLINED) > 256:  # unbounded-session safety valve
            _WINDOW_DECLINED.clear()
        _WINDOW_DECLINED.add(key)
    _obs.inc("dist_spgemm.window_decline")
    _obs.event("dist_spgemm.window_decline",
               a_shape=la.shape, b_shape=lb.shape,
               shards=la.num_shards, density_bucket=key[2])


def last_b_realization() -> tuple:
    """Consistent snapshot of the legacy introspection pair
    ``(LAST_B_REALIZATION, LAST_B_PLAN)`` — both read under the state
    lock, so a concurrent ``dist_spgemm`` can never tear the pair
    (realization from one call, plan from another).  The SUPPORTED
    mechanism remains the obs span attrs; this accessor exists for the
    scripts that still read the globals."""
    with _STATE_LOCK:
        return LAST_B_REALIZATION, LAST_B_PLAN


def reset_window_declines() -> None:
    """Clear the window-decline cache.  Entries are keyed on layout
    structure PLUS A's nnz-density bucket (``_decline_key``), so a
    wide-window matrix only pins comparably-dense same-layout matrices
    — but a long-lived process retiring whole matrix families can
    still call this to force re-probing of the min/max column image."""
    with _STATE_LOCK:
        _WINDOW_DECLINED.clear()


def _b_window_flat(B: _Layout, plan, first_local, data, cols, counts,
                   row_ids, ggl=None, counts_only: bool = False):
    """Windowed analog of ``_b_global_flat``: realize only the B row
    blocks inside this shard's A-column window via ring ``ppermute``
    rotations (``d_fwd + d_bwd`` rounds), then expose the same flat
    per-row access over the (nblk, ...) buffers.

    ``plan`` carries only the STATIC shape knobs ``(nblk, d_fwd,
    d_bwd)``; ``first_local`` is the shard's first-window-block id as a
    traced (1,)-block operand — keeping the data-dependent part of the
    plan out of the jit key (window drift between calls re-runs, not
    recompiles).

    Returns ``(b_data_g, b_cols_g, b_start, b_counts, row_base)`` —
    identical contract to the global variant except row lookups must
    subtract the traced ``row_base`` (global B row of window slot 0).
    ``counts_only`` rotates just the per-row-count inputs (phase 1
    needs no values/cols) and returns None for the other slots.
    """
    nblk, d_fwd, d_bwd = plan
    R = B.num_shards
    rps = B.rps
    s = jax.lax.axis_index(ROW_AXIS)
    first = first_local.reshape(()).astype(jnp.int32)
    perm_fwd = [(i, (i + 1) % R) for i in range(R)]
    perm_bwd = [(i, (i - 1) % R) for i in range(R)]

    def place(buf, blk, blk_id):
        pos = blk_id.astype(jnp.int32) - first
        ok = (pos >= 0) & (pos < nblk)
        safe = jnp.clip(pos, 0, nblk - 1)
        cur = jax.lax.dynamic_index_in_dim(buf, safe, 0, keepdims=False)
        newv = jnp.where(ok, blk, cur)
        return jax.lax.dynamic_update_index_in_dim(buf, newv, safe, 0)

    def gather_win(*blks):
        bufs = [jnp.zeros((nblk,) + b.shape, b.dtype) for b in blks]
        bufs = [place(buf, b, s) for buf, b in zip(bufs, blks)]
        cur = blks
        for d in range(1, d_fwd + 1):
            cur = tuple(jax.lax.ppermute(c, ROW_AXIS, perm_fwd)
                        for c in cur)
            blk_id = (s - d) % R
            bufs = [place(buf, c, blk_id) for buf, c in zip(bufs, cur)]
        cur = blks
        for d in range(1, d_bwd + 1):
            cur = tuple(jax.lax.ppermute(c, ROW_AXIS, perm_bwd)
                        for c in cur)
            blk_id = (s + d) % R
            bufs = [place(buf, c, blk_id) for buf, c in zip(bufs, cur)]
        return bufs

    # Which global block each window slot holds (for col un-rebasing).
    slot_blk = first.astype(index_dtype()) + jnp.arange(
        nblk, dtype=index_dtype()
    )
    row_base = first.astype(index_dtype()) * rps

    if B.ell:
        W = cols.shape[-1]
        if counts_only:
            (counts_w,) = gather_win(counts)
            b_counts = counts_w.reshape(nblk * rps).astype(jnp.int32)
            return None, None, None, b_counts, row_base
        data_w, cols_w, counts_w = gather_win(data, cols, counts)
        b_data_g = data_w.reshape(-1)
        b_cols_g = cols_w.reshape(nblk, -1).astype(index_dtype())
        if B.halo >= 0:
            # local = global - (t*rps - halo) for source block t.
            b_cols_g = b_cols_g + (slot_blk * rps - B.halo)[:, None]
        b_cols_g = b_cols_g.reshape(-1)
        b_counts = counts_w.reshape(nblk * rps).astype(jnp.int32)
        b_start = jnp.arange(nblk * rps, dtype=index_dtype()) * W
    else:
        nnz_max = B.inner
        if counts_only:
            counts_w, rid_w = gather_win(counts, row_ids)
        else:
            data_w, cols_w, counts_w, rid_w = gather_win(
                data, cols, counts, row_ids
            )
        slot = jnp.arange(nnz_max, dtype=jnp.int32)
        valid = slot[None, :] < counts_w[:, None]          # (nblk, nnz_max)
        ids_2d = jnp.where(valid, rid_w, rps)
        one = jnp.ones_like(ids_2d, dtype=jnp.int32)
        percount = jax.vmap(
            lambda ids, on: jax.ops.segment_sum(on, ids,
                                                num_segments=rps + 1)
        )(ids_2d, one)[:, :rps]                            # (nblk, rps)
        b_counts = percount.reshape(nblk * rps)
        if counts_only:
            return None, None, None, b_counts, row_base
        b_data_g = data_w.reshape(-1)
        b_cols_g = cols_w.reshape(nblk, -1).astype(index_dtype())
        if B.halo >= 0:
            b_cols_g = b_cols_g + (slot_blk * rps - B.halo)[:, None]
        b_cols_g = b_cols_g.reshape(-1)
        starts_local = jnp.cumsum(percount, axis=1) - percount
        b_start = (
            starts_local.astype(index_dtype())
            + (jnp.arange(nblk, dtype=index_dtype()) * nnz_max)[:, None]
        ).reshape(nblk * rps)

    b_cols_g = jnp.clip(b_cols_g, 0, B.shape[1] - 1)
    return b_data_g, b_cols_g, b_start, b_counts, row_base


def _expand_sorted(A: _Layout, a_args, b_args, T_cap: int, n_cols: int,
                   row_base=0):
    """Shared expand + two-key sort producing (c_row, c_col, c_val,
    heads, local_nnz) for one shard — 1-D entry point (flattens the
    shard's A block first); the 2-d path feeds its gathered row-panel
    quad straight into ``_expand_sorted_flat``."""
    return _expand_sorted_flat(
        _a_local_flat(A, *a_args), b_args, T_cap, n_cols, A.rps,
        row_base=row_base,
    )


def _expand_sorted_flat(a_flat, b_args, T_cap: int, n_cols: int,
                        rps: int, row_base=0):
    """Expansion core over a flat (a_row, a_col, a_val, a_valid) quad.
    Invalid product slots carry the sentinel row ``rps`` (sorts after
    every valid row) and value 0.

    ``row_base``: global B row of the realized buffer's first row (0
    for the all_gather realization; the shard's window start — traced —
    for the windowed one).  Every valid A column lies inside the window
    by construction, so the clip only ever moves invalid slots.
    """
    a_row, a_col, a_val, a_valid = a_flat
    b_data_g, b_cols_g, b_start, b_counts = b_args

    b_row = jnp.clip(a_col - row_base, 0, b_counts.shape[0] - 1)
    counts_per_a = jnp.where(a_valid, b_counts[b_row], 0).astype(index_dtype())
    starts = jnp.concatenate(
        [jnp.zeros((1,), index_dtype()), jnp.cumsum(counts_per_a)]
    )
    T_local = starts[-1]

    t = jnp.arange(T_cap, dtype=index_dtype())
    e = jnp.clip(
        jnp.searchsorted(starts, t, side="right") - 1, 0, a_row.shape[0] - 1
    )
    valid_t = t < T_local
    within = t - starts[e]
    k = b_row[e]
    b_pos = jnp.clip(b_start[k] + within, 0, b_data_g.shape[0] - 1)

    c_row = jnp.where(valid_t, a_row[e], rps).astype(jnp.int32)
    c_col = jnp.where(valid_t, b_cols_g[b_pos], n_cols)
    c_val = jnp.where(valid_t, a_val[e] * b_data_g[b_pos],
                      jnp.zeros((), a_val.dtype))
    c_row, c_col, c_val = jax.lax.sort([c_row, c_col, c_val], num_keys=2)

    valid_s = c_row < rps
    if T_cap > 1:
        change = jnp.logical_or(c_row[1:] != c_row[:-1],
                                c_col[1:] != c_col[:-1])
        heads = jnp.concatenate([jnp.ones((1,), bool), change])
    else:
        heads = jnp.ones((T_cap,), bool)
    heads = jnp.logical_and(heads, valid_s)
    local_nnz = jnp.sum(heads.astype(jnp.int32))
    return c_row, c_col, c_val, heads, local_nnz


def _compress_tail(c_row, c_col, c_val, heads, val_mask, local_nnz,
                   nnz_cap: int, rps: int, col_dtype):
    """Shared ESC compression: scatter-add run values into the padded
    (nnz_cap,) output and gather run-head coordinates.  ``val_mask``
    selects the product slots whose values may contribute (invalid
    sentinel slots — and, on the 2-d path, any slot outside the
    device's output block — add 0 wherever their clipped segment id
    lands)."""
    seg = jnp.clip(jnp.cumsum(heads.astype(jnp.int32)) - 1, 0,
                   nnz_cap - 1)
    out_vals = jnp.zeros((nnz_cap,), c_val.dtype).at[seg].add(
        jnp.where(val_mask, c_val, jnp.zeros((), c_val.dtype))
    )
    head_idx = jnp.nonzero(heads, size=nnz_cap, fill_value=0)[0]
    slot = jnp.arange(nnz_cap, dtype=jnp.int32)
    pad = slot >= local_nnz
    out_cols = jnp.where(pad, 0, c_col[head_idx]).astype(col_dtype)
    out_rows = jnp.where(
        pad, max(rps - 1, 0), c_row[head_idx]
    ).astype(jnp.int32)
    out_vals = jnp.where(pad, jnp.zeros((), c_val.dtype), out_vals)
    return out_vals, out_cols, out_rows


def _dist_band_spgemm(A: DistCSR, B: DistCSR):
    """C = A @ B for exactly-banded square operands: nd_a*nd_b shifted
    multiplies on the row-indexed per-shard DIA blocks, with B's rows
    realized by a ``ppermute`` halo exchange — no all_gather, no
    expansion, no sort.  The distributed rendition of
    ``ops.dia_ops.dia_spgemm``.

    Returns a DIA-layout DistCSR (ELL blocks included, same assembly as
    ``dist_diags``), or None when the preconditions don't hold (not
    exact bands, band too wide for halo mode, pattern not provably
    equal to the structural product).
    """
    from ..ops.dia_ops import (
        band_cover, band_product_is_full, band_product_offsets,
    )
    from ..settings import settings

    if (
        A.dia_data is None or B.dia_data is None
        or A.dia_mask is not None or B.dia_mask is not None
        or A.shape[0] != A.shape[1] or B.shape[0] != B.shape[1]
        or A.rows_per_shard != B.rows_per_shard
    ):
        return None
    n = A.shape[0]
    rps = A.rows_per_shard
    offs_a, offs_b = A.dia_offsets, B.dia_offsets
    offs_c = band_product_offsets(offs_a, offs_b)
    nnz_c = band_cover(offs_c, (n, n), n)
    h = max(abs(o) for o in offs_a)          # B-row reach of the product
    halo_c = max(abs(o) for o in offs_c)     # halo of the result matrix
    if (
        h > rps or halo_c > rps
        or len(offs_c) > settings.dia_max_diags
        or len(offs_c) * n > settings.dia_max_expand * max(nnz_c, 1)
        or not band_product_is_full(offs_a, offs_b, offs_c,
                                    A.shape, B.shape)
    ):
        return None

    fn = _band_spgemm_fn(A.mesh, offs_a, offs_b, offs_c, n, rps, h,
                         halo_c)
    data, cols_b, counts, dia_data = fn(A.dia_data, B.dia_data)
    from .dist_csr import attach_dia_prepack

    return attach_dia_prepack(DistCSR(
        data=data, cols=cols_b, counts=counts, row_ids=None,
        shape=(n, n), rows_per_shard=rps, halo=halo_c, ell=True,
        mesh=A.mesh, dia_data=dia_data, dia_offsets=offs_c,
        nnz_hint=nnz_c,
    ))


@lru_cache(maxsize=128)
def _band_spgemm_fn(mesh, offs_a, offs_b, offs_c, n, rps, h, halo_c):
    """Cached shard_map callable for the banded product (fresh closures
    would re-trace/recompile on every call — same reasoning as
    ``dist_csr._dia_spmv_fn``)."""
    _obs.inc("jit_miss.dist_spgemm.band_spgemm_fn")
    nd_c = len(offs_c)
    idx_c = {o: i for i, o in enumerate(offs_c)}
    offs_c_dev = jnp.asarray(offs_c, dtype=index_dtype())

    def kernel(a_blk, b_blk):
        a = a_blk[0]                               # (nd_a, rps)
        b = b_blk[0]                               # (nd_b, rps)
        # Halo-extend B's rows (axis 1) from ring neighbors.  Ring wrap
        # at the global edges multiplies against A's out-of-range zeros
        # (exact-band blocks are 0 there by construction), so wrapped
        # values never reach the result.
        from .dist_csr import _extend_x

        b_ext = _extend_x(b, h, axis=1)
        C = jnp.zeros((nd_c, rps), dtype=jnp.result_type(a.dtype, b.dtype))
        for a_i, oa in enumerate(offs_a):
            for b_i, ob in enumerate(offs_b):
                seg = jax.lax.slice_in_dim(
                    b_ext[b_i], h + oa, h + oa + rps
                )
                C = C.at[idx_c[oa + ob]].add(a[a_i] * seg)
        # ELL assembly: the product band is full, so per-row counts and
        # cols follow from the offsets alone (shared helper with
        # dist_diags — one source of truth for the slot conventions).
        from .dist_build import band_ell_local

        shard = jax.lax.axis_index(ROW_AXIS)
        start = shard.astype(index_dtype()) * rps
        r_l = jnp.arange(rps, dtype=index_dtype())
        r = start + r_l
        ell_data, ell_cols, cnt = band_ell_local(
            C, offs_c_dev, n, rps, halo_c, start, r, r_l
        )
        return ell_data[None], ell_cols[None], cnt[None], C[None]

    out_specs = (P(ROW_AXIS, None, None), P(ROW_AXIS, None, None),
                 P(ROW_AXIS, None), P(ROW_AXIS, None, None))
    return jax.jit(shard_map(
        kernel, mesh=mesh,
        in_specs=(P(ROW_AXIS, None, None), P(ROW_AXIS, None, None)),
        out_specs=out_specs, check_vma=False,
    ))


def _b_realization_volumes(B: DistCSR, lb: _Layout, plan):
    """Predicted interconnect volumes for realizing B across the three
    ESC phases, for BOTH candidate realizations — the evidence behind
    the window-vs-all_gather choice.

    Returns ``(ag_vols, ag_calls, win_vols, win_calls)``: per-
    collective byte dicts + collective-op counts, with the window pair
    None when no accepted plan exists (declined / precise layout /
    R <= 2).  Per-phase arrays mirror ``_esc_t_fn`` (phase 1 rotates
    or gathers only counts/row_ids) and ``_b_global_flat`` /
    ``_b_window_flat`` (phases 2-3 move the full operand set)."""
    from ..obs import comm as _comm

    R = lb.num_shards
    item_d = np.dtype(B.data.dtype).itemsize
    item_c = np.dtype(B.cols.dtype).itemsize
    if lb.ell:
        data_b = lb.rps * lb.inner * item_d
        cols_b = lb.rps * lb.inner * item_c
        cnt_b = lb.rps * 4
        rid_b = 0
    else:
        data_b = lb.inner * item_d
        cols_b = lb.inner * item_c
        cnt_b = 4                       # (R,) per-shard totals
        rid_b = lb.inner * 4
    ggl_b = 0
    if lb.has_ggl:
        g = B.gather_globals
        ggl_b = (int(g.shape[1]) * int(g.shape[2])
                 * np.dtype(g.dtype).itemsize)

    phase1_b = cnt_b + rid_b
    phase1_n = 1 if lb.ell else 2
    phase23_b = data_b + cols_b + cnt_b + rid_b + ggl_b
    phase23_n = (3 if lb.ell else 4) + (1 if lb.has_ggl else 0)

    ag_vols = {"all_gather": _comm.all_gather_bytes(
        phase1_b + 2 * phase23_b, 1, R)}
    ag_calls = {"all_gather": phase1_n + 2 * phase23_n}

    win_vols = win_calls = None
    if plan is not None:
        _, d_fwd, d_bwd = plan
        rounds = d_fwd + d_bwd
        # Window phases never move ggl (precise layouts decline the
        # window) and phase-1 csr rotations carry row_ids too.
        w_phase1_b = cnt_b + rid_b
        w_phase23_b = data_b + cols_b + cnt_b + rid_b
        win_vols = {"ppermute": _comm.ppermute_bytes(
            w_phase1_b + 2 * w_phase23_b, 1, R, rounds=rounds)}
        win_calls = {"ppermute": rounds * (phase1_n + 2 * phase23_n)}
    return ag_vols, ag_calls, win_vols, win_calls


# ------------------------------------------------------------------ 2-D --
# SUMMA-style SpGEMM over 2-d-block operands (docs/DIST.md): device
# (i, j) owns C block (i, j) = sum_k A(i, k) @ B(k, j), so it realizes
# its A ROW panel (all_gather along the mesh COLUMN axis — each A
# element reaches Rc-1 receivers) and its B COLUMN panel (staged along
# the mesh ROW axis — each B element reaches Rr-1 receivers, ledgered
# as the ``bcast`` kind), then runs the SAME local ESC as the 1-D
# kernel.  No product triple ever crosses the interconnect: every
# partial product lands in the block that owns it, which is what makes
# the 2-d layout communication-avoiding for SpGEMM (vs the 1-D path's
# N-1-receiver all_gather of all of B).


def _a_row_panel_flat(cps_a: int, data, cols, row_ids, counts):
    """Gather this device's A row panel along the mesh column axis and
    expose it as one flat (a_row, a_col, a_val, a_valid) quad: rows
    stay BLOCK-local (every block of the row group shares the row
    range), columns rebase to the global [0, cols_padded) domain via
    each source block's column offset."""
    data_g = jax.lax.all_gather(data, COL_AXIS)       # (Rc, capA)
    cols_g = jax.lax.all_gather(cols, COL_AXIS)
    rids_g = jax.lax.all_gather(row_ids, COL_AXIS)
    counts_g = jax.lax.all_gather(counts, COL_AXIS)   # (Rc,)
    cap = data.shape[-1]
    slot = jnp.arange(cap, dtype=jnp.int32)
    a_valid = (slot[None, :] < counts_g[:, None]).reshape(-1)
    off = jnp.arange(cols_g.shape[0], dtype=index_dtype()) * cps_a
    a_col = (cols_g.astype(index_dtype()) + off[:, None]).reshape(-1)
    a_row = rids_g.reshape(-1)
    a_val = data_g.reshape(-1)
    return a_row, a_col, a_val, a_valid


_GRID_SPECS = (P(ROW_AXIS, COL_AXIS, None), P(ROW_AXIS, COL_AXIS, None),
               P(ROW_AXIS, COL_AXIS, None), P(ROW_AXIS, COL_AXIS))


@lru_cache(maxsize=128)
def _esc2d_t_fn(mesh, cps_a: int, rps_b: int):
    """Cached 2-d phase-1 (product count) shard_map: realizes only the
    structural halves of both panels (A cols+counts along mesh cols,
    B row_ids+counts along mesh rows)."""
    _obs.inc("jit_miss.dist_spgemm.esc2d_t_fn")
    in_specs = (P(ROW_AXIS, COL_AXIS, None), P(ROW_AXIS, COL_AXIS),
                P(ROW_AXIS, COL_AXIS, None), P(ROW_AXIS, COL_AXIS))

    def t_kernel(a_cols, a_counts, b_rids, b_counts):
        ac, act = a_cols[0, 0], a_counts[0, 0]
        cols_g = jax.lax.all_gather(ac, COL_AXIS)
        cnts_g = jax.lax.all_gather(act, COL_AXIS)
        slot = jnp.arange(ac.shape[-1], dtype=jnp.int32)
        a_valid = (slot[None, :] < cnts_g[:, None]).reshape(-1)
        off = jnp.arange(cols_g.shape[0], dtype=index_dtype()) * cps_a
        a_col = (cols_g.astype(index_dtype()) + off[:, None]).reshape(-1)

        br, bct = b_rids[0, 0], b_counts[0, 0]
        rid_g = jax.lax.all_gather(br, ROW_AXIS)      # (Rr, capB)
        cnt_g = jax.lax.all_gather(bct, ROW_AXIS)     # (Rr,)
        slotb = jnp.arange(br.shape[-1], dtype=jnp.int32)
        validb = slotb[None, :] < cnt_g[:, None]
        ids_2d = jnp.where(validb, rid_g, rps_b)
        one = jnp.ones_like(ids_2d, dtype=index_dtype())
        percount = jax.vmap(
            lambda ids, on: jax.ops.segment_sum(
                on, ids, num_segments=rps_b + 1
            )
        )(ids_2d, one)[:, :rps_b]
        b_cnt = percount.reshape(-1)                  # (rows_padded(B),)
        b_row = jnp.clip(a_col, 0, b_cnt.shape[0] - 1)
        t_local = jnp.sum(
            jnp.where(a_valid, b_cnt[b_row], 0), dtype=index_dtype()
        )
        return t_local[None, None]

    return jax.jit(shard_map(
        t_kernel, mesh=mesh, in_specs=in_specs,
        out_specs=P(ROW_AXIS, COL_AXIS), check_vma=False,
    ))


@lru_cache(maxsize=128)
def _esc2d_nnz_fn(mesh, lb2: _Layout, cps_a: int, rps_a: int,
                  T_cap: int):
    """Cached 2-d phase-2 (output nnz) shard_map."""
    _obs.inc("jit_miss.dist_spgemm.esc2d_nnz_fn")
    in_specs = _GRID_SPECS + _GRID_SPECS
    n_cols = lb2.shape[1]

    def nnz_kernel(ad, ac, ar, act, bd, bc, br, bct):
        a_flat = _a_row_panel_flat(
            cps_a, ad[0, 0], ac[0, 0], ar[0, 0], act[0, 0]
        )
        b_args = _b_global_flat(lb2, bd[0, 0], bc[0, 0], bct[0, 0],
                                br[0, 0])
        *_, local_nnz = _expand_sorted_flat(
            a_flat, b_args, T_cap, n_cols, rps_a
        )
        return local_nnz[None, None]

    return jax.jit(shard_map(
        nnz_kernel, mesh=mesh, in_specs=in_specs,
        out_specs=P(ROW_AXIS, COL_AXIS), check_vma=False,
    ))


@lru_cache(maxsize=128)
def _esc2d_numeric_fn(mesh, lb2: _Layout, cps_a: int, rps_a: int,
                      T_cap: int, nnz_cap: int):
    """Cached 2-d phase-3 (numeric) shard_map.  Output cols stay
    BLOCK-local: the realized B panel carries block-local columns and
    C block (i, j) inherits B block j's column range exactly."""
    from ..types import coord_dtype_for

    _obs.inc("jit_miss.dist_spgemm.esc2d_numeric_fn")
    in_specs = _GRID_SPECS + _GRID_SPECS
    n_cols = lb2.shape[1]
    col_dtype = coord_dtype_for(n_cols)

    def numeric_kernel(ad, ac, ar, act, bd, bc, br, bct):
        a_flat = _a_row_panel_flat(
            cps_a, ad[0, 0], ac[0, 0], ar[0, 0], act[0, 0]
        )
        b_args = _b_global_flat(lb2, bd[0, 0], bc[0, 0], bct[0, 0],
                                br[0, 0])
        c_row, c_col, c_val, heads, local_nnz = _expand_sorted_flat(
            a_flat, b_args, T_cap, n_cols, rps_a
        )
        out_vals, out_cols, out_rows = _compress_tail(
            c_row, c_col, c_val, heads, c_row < rps_a, local_nnz,
            nnz_cap, rps_a, col_dtype,
        )
        return (out_vals[None, None], out_cols[None, None],
                out_rows[None, None], local_nnz[None, None])

    out_specs = (P(ROW_AXIS, COL_AXIS, None),
                 P(ROW_AXIS, COL_AXIS, None),
                 P(ROW_AXIS, COL_AXIS, None), P(ROW_AXIS, COL_AXIS))
    return jax.jit(shard_map(
        numeric_kernel, mesh=mesh, in_specs=in_specs,
        out_specs=out_specs, check_vma=False,
    ))


def _summa_volumes_2d(A: DistCSR, B: DistCSR, grid):
    """Predicted interconnect volumes of the three 2-d ESC phases from
    the static block shapes: A row panels (``all_gather`` along mesh
    columns — Rr groups of Rc) and B column panels (``bcast`` staging
    along mesh rows — Rc groups of Rr).  Phase 1 moves only the
    structural halves; phases 2-3 move the full operand sets."""
    from ..obs import comm as _comm

    Rr, Rc = grid
    capA = int(A.data.shape[-1])
    capB = int(B.data.shape[-1])
    ia_d = np.dtype(A.data.dtype).itemsize
    ia_c = np.dtype(A.cols.dtype).itemsize
    ib_d = np.dtype(B.data.dtype).itemsize
    ib_c = np.dtype(B.cols.dtype).itemsize
    a1 = Rr * _comm.all_gather_bytes(capA * ia_c + 4, 1, Rc)
    a23 = Rr * _comm.all_gather_bytes(
        capA * (ia_d + ia_c + 4) + 4, 1, Rc)
    b1 = Rc * _comm.all_gather_bytes(capB * 4 + 4, 1, Rr)
    b23 = Rc * _comm.all_gather_bytes(
        capB * (ib_d + ib_c + 4) + 4, 1, Rr)
    vols = {"all_gather": a1 + 2 * a23, "bcast": b1 + 2 * b23}
    calls = {"all_gather": 2 + 2 * 4, "bcast": 2 + 2 * 4}
    vols = {k: v for k, v in vols.items() if v > 0}
    return vols, {k: calls[k] for k in vols}


def _dist_spgemm_2d(A: DistCSR, B: DistCSR) -> DistCSR:
    """C = A @ B for 2-d-block operands on a shared grid; returns a
    2-d-block C on the same grid (rows from A's row blocks, columns
    from B's column blocks — directly consumable by the 2-d SpMV or a
    further SUMMA product)."""
    from ..obs import comm as _comm
    from ..obs import memory as _mem
    from ..types import coord_dtype_for
    from .dist_csr import _device_put_sharded

    mesh = A.mesh
    Rr, Rc = A.grid
    N = Rr * Rc
    rps = A.rows_per_shard
    m, n_cols = A.shape[0], B.shape[1]
    col_dtype = coord_dtype_for(n_cols)
    # The gathered B panel has exactly the ``_b_global_flat`` shape
    # contract over the mesh-row group: Rr source blocks of rps_b rows
    # each, scalar per-block counts, block-local row ids — so the 1-D
    # realization helper is reused verbatim with this synthetic layout.
    lb2 = _Layout(
        ell=False, rps=B.rows_per_shard, halo=-1, cps=0, has_ggl=False,
        shape=B.shape, rows_padded=Rr * B.rows_per_shard,
        num_shards=Rr, inner=int(B.data.shape[-1]),
    )
    _obs.inc("dist_spgemm.realization.2d_panel")
    vols, calls = _summa_volumes_2d(A, B, A.grid)
    comm_bytes = _comm.record("dist_spgemm", vols, calls,
                              layout=A.layout)
    # Evidence: the 1-D counterfactual at the same device count — a
    # perfectly balanced all_gather realization of B over N row shards
    # (inner = ceil(nnz/N)), priced by the same per-phase formula as
    # ``_b_realization_volumes``.
    nnzb = B.nnz_hint
    if nnzb < 0:
        nnzb = B.global_nnz
        B.nnz_hint = nnzb
    inner1 = max(-(-nnzb // N), 1)
    ag1d = _comm.all_gather_bytes(
        (4 + inner1 * 4)
        + 2 * (inner1 * (np.dtype(B.data.dtype).itemsize
                         + np.dtype(B.cols.dtype).itemsize + 4) + 4),
        1, N)
    _obs.event(
        "dist_spgemm.realization", choice="2d-panel", shards=N,
        grid=A.grid, predicted_bytes=comm_bytes,
        predicted_all_gather_bytes=ag1d, predicted_window_bytes=None,
    )
    a_arrays = (A.data, A.cols, A.row_ids, A.counts)
    b_arrays = (B.data, B.cols, B.row_ids, B.counts)
    with _lat.timer("lat.dist_spgemm." + _lat.shape_bucket(m)), \
            _obs.span("dist_spgemm", shards=N, m=m, n=n_cols,
                      b_realization="2d-panel", b_plan=(),
                      comm_bytes=comm_bytes,
                      comm_calls=sum(calls.values())) as sp:
        t_locals = _esc2d_t_fn(mesh, A.cols_per_shard,
                               B.rows_per_shard)(
            A.cols, A.counts, B.row_ids, B.counts)
        _obs.inc("transfer.host_sync.dist_spgemm_T")
        T_cap = int(jnp.max(t_locals))
        val_dtype = jnp.result_type(A.data.dtype, B.data.dtype)
        if T_cap == 0:
            from jax.sharding import NamedSharding

            z3 = NamedSharding(mesh, P(ROW_AXIS, COL_AXIS, None))
            z2 = NamedSharding(mesh, P(ROW_AXIS, COL_AXIS))
            return DistCSR(
                data=_device_put_sharded(
                    jnp.zeros((Rr, Rc, 1), val_dtype), z3),
                cols=_device_put_sharded(
                    jnp.zeros((Rr, Rc, 1), col_dtype), z3),
                counts=_device_put_sharded(
                    jnp.zeros((Rr, Rc), jnp.int32), z2),
                row_ids=_device_put_sharded(
                    jnp.full((Rr, Rc, 1), max(rps - 1, 0), jnp.int32),
                    z3),
                shape=(m, n_cols), rows_per_shard=rps, halo=-1,
                ell=False, mesh=mesh,
                cols_per_shard=B.cols_per_shard, nnz_hint=0,
                layout=A.layout, grid=A.grid,
            )

        nnz_locals = _esc2d_nnz_fn(
            mesh, lb2, A.cols_per_shard, rps, T_cap
        )(*a_arrays, *b_arrays)
        _obs.inc("transfer.host_sync.dist_spgemm_nnz")
        nnz_cap = max(int(jnp.max(nnz_locals)), 1)
        nnz_total = int(jnp.sum(nnz_locals)) if _obs.enabled() else -1
        if sp is not None:
            sp.set(T_cap=T_cap, nnz_cap=nnz_cap, nnz=nnz_total)

        item_d = np.dtype(val_dtype).itemsize
        out_mb = N * nnz_cap * (item_d + np.dtype(col_dtype).itemsize
                                + 4) / 2**20
        expand_mb = N * T_cap * (item_d + 2 * np.dtype(
            index_dtype()).itemsize) / 2**20
        with _mem.watermark("dist_spgemm", T_cap=T_cap,
                            nnz_cap=nnz_cap, nnz=nnz_total,
                            out_mb=round(out_mb, 2),
                            expand_mb=round(expand_mb, 2)):
            vals_b, cols_b, rids_b, counts_b = _esc2d_numeric_fn(
                mesh, lb2, A.cols_per_shard, rps, T_cap, nnz_cap
            )(*a_arrays, *b_arrays)

    # cols_padded(C) == cols_padded(B): same global width, same
    # multiple-of-N padding convention — so C inherits B's column
    # blocking and stays a first-class 2-d operand.
    return DistCSR(
        data=vals_b, cols=cols_b, counts=counts_b.astype(jnp.int32),
        row_ids=rids_b, shape=(m, n_cols), rows_per_shard=rps,
        halo=-1, ell=False, mesh=mesh,
        cols_per_shard=B.cols_per_shard, nnz_hint=nnz_total,
        layout=A.layout, grid=A.grid,
    )


# Static (entry point, layout, realization) catalog of this module's
# contract-bearing lowered program families — the SpGEMM counterpart
# of ``dist_csr.DIST_PLAN_SHAPES`` (same consumers: ``tools/verify``
# and the sparselint plan-contract rule; same rule: a new dispatch
# branch grows this tuple and must commit a contract).  The contracted
# program per triple is the phase-1 product-count shard_map — the
# phase whose collective realization choice (window ppermute vs B
# all_gather vs 2-d panel staging) the later phases inherit.
SPGEMM_PLAN_SHAPES = (
    ("dist_spgemm", "1d-row", "all_gather"),
    ("dist_spgemm", "2d-block", "panel"),
)


def dist_spgemm(A: DistCSR, B: DistCSR) -> DistCSR:
    """C = A @ B, both row-block distributed; returns a row-block C.

    Exactly-banded square operands take the gather-free banded fast
    path (``_dist_band_spgemm``: shifted multiplies + ppermute halo —
    no all_gather of B); everything else runs the general collective
    ESC.  Differentially tested against scipy on the 8-device CPU mesh
    (``tests/test_dist_spgemm.py``), including the GMG Galerkin
    triple product R @ A @ P.

    Resilience (``LEGATE_SPARSE_TPU_RESIL``, docs/RESILIENCE.md): the
    whole multiply is the ``dist.spgemm`` site — SpGEMM is a driver of
    eager collective phases with host syncs between them, so a
    transient failure in any phase retries the multiply from its
    immutable inputs (bit-identical on success).
    """
    from ..resilience import guarded_call as _resil_guarded
    from ..settings import settings as _rsettings

    if _rsettings.resil:
        return _resil_guarded("dist.spgemm",
                              lambda: _dist_spgemm_impl(A, B))
    return _dist_spgemm_impl(A, B)


def _dist_spgemm_impl(A: DistCSR, B: DistCSR) -> DistCSR:
    if A.shape[1] != B.shape[0]:
        raise ValueError(f"dimension mismatch: {A.shape} @ {B.shape}")
    if A.mesh is not B.mesh and A.mesh != B.mesh:
        raise ValueError("operands must share a mesh")
    _obs.inc("op.dist_spgemm")
    from ..obs import comm as _comm

    if A.grid is not None or B.grid is not None:
        if A.grid is None or B.grid is None or A.grid != B.grid:
            raise ValueError(
                f"dist_spgemm: operands must share one 2-d grid "
                f"(got {A.grid} and {B.grid}); reshard with the same "
                f"layout"
            )
        return _dist_spgemm_2d(A, B)

    with _obs.span("dist_spgemm.band_probe"):
        C_band = _dist_band_spgemm(A, B)
    if C_band is not None:
        _obs.inc("dist_spgemm.realization.band")
        # Band realization moves only B's halo-extended DIA rows: one
        # two-sided exchange of (nd_b, h) slices — no all_gather, no
        # expansion.  That byte count IS the evidence for taking the
        # banded path.
        h = max(abs(int(o)) for o in A.dia_offsets)
        nd_b = len(B.dia_offsets)
        band_vols = {"ppermute": _comm.halo_exchange_bytes(
            nd_b * h, np.dtype(B.dtype).itemsize, A.num_shards)}
        band_bytes = _comm.record("dist_spgemm", band_vols,
                                  layout=A.layout)
        _obs.event("dist_spgemm.realization", choice="band",
                   shards=A.num_shards, predicted_bytes=band_bytes)
        return C_band
    A._require_blocks("dist_spgemm")
    B._require_blocks("dist_spgemm")
    if A.rows_padded < A.shape[0] or B.rows_padded < B.shape[0]:
        raise AssertionError("padded row invariant violated")
    # Padded B rows have count 0 everywhere (shard_csr invariant), so
    # they contribute no products even though A cols never index them.

    from ..types import coord_dtype_for

    mesh = A.mesh
    rps = A.rows_per_shard
    m, n_cols = A.shape[0], B.shape[1]
    col_dtype = coord_dtype_for(n_cols)
    la, lb = _layout_of(A), _layout_of(B)

    # Absent layout fields (ELL has no row_ids; only precise layouts
    # carry gather_globals) ride along as (R, 1) zero blocks so every
    # kernel arg shards uniformly on the row axis.
    R = A.num_shards
    placeholder = jnp.zeros((R, 1), dtype=jnp.int32)

    def arrays_of(M):
        return (
            M.data, M.cols,
            M.counts if M.counts is not None else placeholder,
            M.row_ids if M.row_ids is not None else placeholder,
            M.gather_globals if M.gather_globals is not None
            else placeholder,
        )

    a_arrays = arrays_of(A)
    b_arrays = arrays_of(B)

    # B-realization window plan (the reference's min/max column image of
    # A, ``csr.py:640-666``): gather only the B row blocks each shard's
    # A columns reach, via ring ppermute — None falls back to the full
    # all_gather when the window is dense or B is precise-layout.  Only
    # the static shape triple enters the phase-fn cache keys; the
    # per-shard window starts ride as a traced operand.
    global LAST_B_REALIZATION, LAST_B_PLAN
    win = _b_window_plan(A, la, lb, a_arrays)
    if win is not None:
        first_blks, plan = win
        first_dev = (_put_blocks(jnp.asarray(first_blks), mesh),)
        realization = "window"
        b_plan = (tuple(int(f) for f in first_blks), *plan)
    else:
        plan = None
        first_dev = ()
        realization = "all_gather"
        b_plan = ()
    with _STATE_LOCK:
        # Written as a pair under the lock.  Concurrent readers who
        # need the pair to be mutually consistent must read through
        # ``last_b_realization()`` (which takes the same lock); bare
        # reads of either global alone stay safe (single attribute).
        LAST_B_REALIZATION = realization
        LAST_B_PLAN = b_plan
    _obs.inc("dist_spgemm.realization." + realization)
    # Evidence for the realization choice: predicted interconnect
    # bytes of BOTH candidates from the static shard shapes, the
    # chosen one entering the comm ledger.  (The window prediction
    # exists only when a plan was accepted — a declined probe never
    # computed ring distances.)
    ag_vols, ag_calls, win_vols, win_calls = _b_realization_volumes(
        B, lb, plan)
    if win is not None:
        comm_bytes = _comm.record("dist_spgemm", win_vols, win_calls,
                                  layout=A.layout)
        comm_calls = sum(win_calls.values())
    else:
        comm_bytes = _comm.record("dist_spgemm", ag_vols, ag_calls,
                                  layout=A.layout)
        comm_calls = sum(ag_calls.values())
    _obs.event(
        "dist_spgemm.realization", choice=realization,
        shards=R, predicted_bytes=comm_bytes,
        predicted_all_gather_bytes=_comm.total(ag_vols),
        predicted_window_bytes=(_comm.total(win_vols)
                                if win_vols is not None else None),
    )
    with _lat.timer("lat.dist_spgemm." + _lat.shape_bucket(m)), \
            _obs.span("dist_spgemm", shards=R, m=m, n=n_cols,
                      b_realization=realization,
                      b_plan=b_plan, comm_bytes=comm_bytes,
                      comm_calls=comm_calls) as sp:
        return _dist_spgemm_phases(
            A, B, mesh, la, lb, plan, a_arrays, b_arrays, first_dev,
            rps, m, n_cols, col_dtype, R, sp,
        )


def _dist_spgemm_phases(A, B, mesh, la, lb, plan, a_arrays, b_arrays,
                        first_dev, rps, m, n_cols, col_dtype, R, sp):
    """The three collective ESC phases (split out so the realization
    span covers them; ``sp`` is the live span, or None when tracing
    is disabled)."""
    # ---- phase 1: T_local ------------------------------------------------
    t_locals = _esc_t_fn(mesh, la, lb, plan)(
        *a_arrays, *b_arrays, *first_dev
    )
    _obs.inc("transfer.host_sync.dist_spgemm_T")
    T_cap = int(jnp.max(t_locals))

    val_dtype = jnp.result_type(A.data.dtype, B.data.dtype)
    if T_cap == 0:
        return DistCSR(
            data=_put_blocks(jnp.zeros((R, 1), val_dtype), mesh),
            cols=_put_blocks(jnp.zeros((R, 1), col_dtype), mesh),
            counts=_put_blocks(jnp.zeros((R,), jnp.int32), mesh),
            row_ids=_put_blocks(
                jnp.full((R, 1), max(rps - 1, 0), jnp.int32), mesh
            ),
            shape=(m, n_cols), rows_per_shard=rps, halo=-1, ell=False,
            mesh=mesh, nnz_hint=0,
        )

    # ---- phase 2: nnz_local ---------------------------------------------
    nnz_locals = _esc_nnz_fn(mesh, la, lb, T_cap, plan)(
        *a_arrays, *b_arrays, *first_dev
    )
    _obs.inc("transfer.host_sync.dist_spgemm_nnz")
    # Device-side reductions only: fetching the P(ROW_AXIS)-sharded
    # nnz_locals itself (np.asarray) is illegal in multi-controller
    # runs — same pitfall documented at _col_window_fn.  The reduced
    # scalars are replicated and always fetchable.
    nnz_cap = max(int(jnp.max(nnz_locals)), 1)
    # The exact output nnz costs one more blocking scalar fetch —
    # tracing mode only (the default path must not grow a host
    # sync).  Without it the result's
    # nnz_hint stays -1 and the decline key's lazy ``global_nnz``
    # fallback pays once, memoized on the instance.
    nnz_total = int(jnp.sum(nnz_locals)) if _obs.enabled() else -1
    if sp is not None:
        sp.set(T_cap=T_cap, nnz_cap=nnz_cap, nnz=nnz_total)

    # ---- phase 3: numeric ------------------------------------------------
    # Output-nnz blowup becomes a recorded number, not an OOM: the
    # watermark event carries the predicted padded allocation next to
    # the realized RSS delta.
    from ..obs import memory as _mem

    item_d = np.dtype(jnp.result_type(A.data.dtype,
                                      B.data.dtype)).itemsize
    out_mb = R * nnz_cap * (item_d + np.dtype(col_dtype).itemsize
                            + 4) / 2**20
    expand_mb = R * T_cap * (item_d + 2 * np.dtype(
        index_dtype()).itemsize) / 2**20
    with _mem.watermark("dist_spgemm", T_cap=T_cap, nnz_cap=nnz_cap,
                        nnz=nnz_total, out_mb=round(out_mb, 2),
                        expand_mb=round(expand_mb, 2)):
        vals_b, cols_b, rids_b, counts_b = _esc_numeric_fn(
            mesh, la, lb, T_cap, nnz_cap, plan
        )(*a_arrays, *b_arrays, *first_dev)

    return DistCSR(
        data=vals_b, cols=cols_b, counts=counts_b.astype(jnp.int32),
        row_ids=rids_b, shape=(m, n_cols), rows_per_shard=rps,
        halo=-1, ell=False, mesh=mesh, nnz_hint=nnz_total,
    )


def _esc_specs(L: _Layout):
    """in_specs ndims for (data, cols, counts, row_ids, ggl) blocks of a
    layout (placeholders are (R, 1), i.e. 2-D)."""
    data_nd = 3 if L.ell else 2
    counts_nd = 2 if L.ell else 1
    ggl_nd = 3 if L.has_ggl else 2
    return tuple(
        P(ROW_AXIS, *([None] * (k - 1)))
        for k in (data_nd, data_nd, counts_nd, 2, ggl_nd)
    )


def _local(args):
    # Inside shard_map each (R, ...) axis-0-sharded block arrives as a
    # (1, ...) slice — index [0] for the local block (same convention as
    # dist_spmv).
    return tuple(x[0] for x in args)


@lru_cache(maxsize=128)
def _esc_t_fn(mesh, la: _Layout, lb: _Layout, plan=None):
    """Cached phase-1 (product count) shard_map (structure-keyed, see
    ``_Layout``; fresh closures per call would recompile every time).
    ``plan`` is the static window-shape triple or None — the per-shard
    window starts ride as a traced trailing operand, not a cache key."""
    _obs.inc("jit_miss.dist_spgemm.esc_t_fn")
    in_specs = _esc_specs(la) + _esc_specs(lb)
    if plan is not None:
        in_specs = in_specs + (P(ROW_AXIS),)

    def t_kernel(*args):
        if plan is not None:
            a_args, b_args_raw, first = args[:5], args[5:10], args[10]
        else:
            a_args, b_args_raw = args[:5], args[5:]
        a_row, a_col, a_val, a_valid = _a_local_flat(la, *_local(a_args))
        if plan is not None:
            *_, b_counts, row_base = _b_window_flat(
                lb, plan, first[0], *_local(b_args_raw),
                counts_only=True
            )
            b_row = jnp.clip(a_col - row_base, 0,
                             b_counts.shape[0] - 1)
            t_local = jnp.sum(
                jnp.where(a_valid, b_counts[b_row], 0),
                dtype=index_dtype(),
            )
            return t_local[None]
        counts = _local(b_args_raw)[2]
        rid = _local(b_args_raw)[3]
        counts_g = jax.lax.all_gather(counts, ROW_AXIS)
        if lb.ell:
            b_counts = counts_g.reshape(lb.rows_padded).astype(index_dtype())
        else:
            rid_g = jax.lax.all_gather(rid, ROW_AXIS)
            nnz_max = lb.inner
            slot = jnp.arange(nnz_max, dtype=jnp.int32)
            valid = slot[None, :] < counts_g[:, None]
            ids_2d = jnp.where(valid, rid_g, lb.rps)
            one = jnp.ones_like(ids_2d, dtype=index_dtype())
            percount = jax.vmap(
                lambda ids, on: jax.ops.segment_sum(
                    on, ids, num_segments=lb.rps + 1
                )
            )(ids_2d, one)[:, : lb.rps]
            b_counts = percount.reshape(lb.rows_padded)
        t_local = jnp.sum(
            jnp.where(a_valid, b_counts[a_col], 0), dtype=index_dtype()
        )
        return t_local[None]

    return jax.jit(shard_map(
        t_kernel, mesh=mesh, in_specs=in_specs, out_specs=P(ROW_AXIS),
        check_vma=False,
    ))


@lru_cache(maxsize=128)
def _esc_nnz_fn(mesh, la: _Layout, lb: _Layout, T_cap: int,
                plan=None):
    """Cached phase-2 (output nnz) shard_map."""
    _obs.inc("jit_miss.dist_spgemm.esc_nnz_fn")
    in_specs = _esc_specs(la) + _esc_specs(lb)
    if plan is not None:
        in_specs = in_specs + (P(ROW_AXIS),)
    n_cols = lb.shape[1]

    def nnz_kernel(*args):
        if plan is None:
            a_args, b_args_raw = args[:5], args[5:]
            b_args = _b_global_flat(lb, *_local(b_args_raw))
            row_base = 0
        else:
            a_args, b_args_raw, first = args[:5], args[5:10], args[10]
            *b_args, row_base = _b_window_flat(
                lb, plan, first[0], *_local(b_args_raw)
            )
        *_, local_nnz = _expand_sorted(
            la, _local(a_args), tuple(b_args), T_cap, n_cols,
            row_base=row_base,
        )
        return local_nnz[None]

    return jax.jit(shard_map(
        nnz_kernel, mesh=mesh, in_specs=in_specs, out_specs=P(ROW_AXIS),
        check_vma=False,
    ))


@lru_cache(maxsize=128)
def _esc_numeric_fn(mesh, la: _Layout, lb: _Layout, T_cap: int,
                    nnz_cap: int, plan=None):
    """Cached phase-3 (numeric) shard_map."""
    from ..types import coord_dtype_for

    _obs.inc("jit_miss.dist_spgemm.esc_numeric_fn")
    in_specs = _esc_specs(la) + _esc_specs(lb)
    if plan is not None:
        in_specs = in_specs + (P(ROW_AXIS),)
    n_cols = lb.shape[1]
    col_dtype = coord_dtype_for(n_cols)
    rps = la.rps

    def numeric_kernel(*args):
        if plan is None:
            a_args, b_args_raw = args[:5], args[5:]
            b_args = _b_global_flat(lb, *_local(b_args_raw))
            row_base = 0
        else:
            a_args, b_args_raw, first = args[:5], args[5:10], args[10]
            *b_args, row_base = _b_window_flat(
                lb, plan, first[0], *_local(b_args_raw)
            )
        c_row, c_col, c_val, heads, local_nnz = _expand_sorted(
            la, _local(a_args), tuple(b_args), T_cap, n_cols,
            row_base=row_base,
        )
        out_vals, out_cols, out_rows = _compress_tail(
            c_row, c_col, c_val, heads, c_row < rps, local_nnz,
            nnz_cap, rps, col_dtype,
        )
        return (out_vals[None], out_cols[None], out_rows[None],
                local_nnz[None])

    out_specs = (P(ROW_AXIS, None), P(ROW_AXIS, None), P(ROW_AXIS, None),
                 P(ROW_AXIS))
    return jax.jit(shard_map(
        numeric_kernel, mesh=mesh, in_specs=in_specs,
        out_specs=out_specs, check_vma=False,
    ))


def _put_blocks(arr, mesh):
    from jax.sharding import NamedSharding

    from .dist_csr import _device_put_sharded

    return _device_put_sharded(arr, NamedSharding(mesh, P(ROW_AXIS)))
