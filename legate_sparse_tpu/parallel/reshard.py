# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Resharding: move distributed operands between meshes and layouts.

ROADMAP item 3's missing primitive, and the middle rung of the
recovery ladder (docs/RESILIENCE.md): after a device loss the solver
needs its operands on the survivor mesh; after a layout decision
changes (autotune, a 2-d-block SpGEMM feeding a 1d-row solve) the
same matrix needs a different partition.  Two entry points:

- :func:`reshard_vector` — THE cached chunk-permute program.  A
  sharded padded vector is, under every layout ``shard_vector``
  produces, one contiguous chunk per device in flat mesh order; a
  placement change over the same device set is therefore exactly one
  ``ppermute`` over the flat mesh whose pairs send chunk ``c`` from
  its source device to the device that owns chunk ``c`` under the
  destination mesh.  One shard_map program per (src, dst) mesh
  fingerprint pair, cached and contracted (``tools/verify``:
  ``dist/reshard/1d-row/chunk-permute/f32``), priced exactly by
  ``obs.comm.reshard_volumes`` — identity pairs move zero bytes, so
  resharding onto the same placement ledgers nothing.

- :func:`reshard` — the matrix path.  Block representations are
  layout-specific (halo-rebased ELL windows vs block-local 2-d
  panels), so a layout or mesh-shape change is a *repartition*, not a
  permute: ``shard_csr`` re-runs on the retained source ``csr_array``
  (``DistCSR._src_csr``) over the destination mesh, with upload bytes
  ledgered by the existing ``transfer.shard_upload*`` counters.  A
  destination whose ``mesh_fingerprint(mesh, layout)`` equals the
  source's returns ``A`` unchanged.

Plan-cache non-aliasing: ``dist_plan_fingerprint`` already folds
``mesh_fingerprint(mesh, layout)`` into every dist-plan identity, so
a resharded matrix can never alias its pre-reshard compiled programs
in the engine's ledger — pinned by test_reshard.py.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .. import obs as _obs
from ..obs import comm as _comm
from jax import shard_map
from .mesh import (
    LAYOUT_1D_COL, LAYOUT_1D_ROW, LAYOUT_2D_BLOCK,
    make_grid_mesh, make_row_mesh, resolve_layout,
)

__all__ = ["reshard", "reshard_vector", "chunk_permute_plan"]


def _flat_devices(mesh: Mesh) -> list:
    return list(np.asarray(mesh.devices).reshape(-1))


def _vector_spec(mesh: Mesh) -> P:
    """The dim-0 spec ``shard_vector`` uses: every mesh axis, grouped
    — one contiguous chunk per device in flat (row-major) order."""
    names = tuple(mesh.axis_names)
    return P(names if len(names) > 1 else names[0])


def chunk_permute_plan(src_mesh: Mesh,
                       dst_mesh: Mesh) -> Tuple[Tuple[Tuple[int, int],
                                                      ...], int]:
    """The ppermute pairs of the (src, dst) placement change and how
    many of them actually move a chunk.

    Chunk ``c`` lives on flat device ``src[c]`` and must end on flat
    device ``dst[c]``; device ``dst[c]`` is flat ordinal
    ``src.index(dst[c])`` of the source mesh, so the pair is
    ``(c, src.index(dst[c]))``.  Identity pairs are kept (every
    device must receive or ``ppermute`` zeros its output) but priced
    at zero bytes."""
    src = _flat_devices(src_mesh)
    dst = _flat_devices(dst_mesh)
    if len(src) != len(dst) or set(src) != set(dst):
        raise ValueError(
            "chunk_permute_plan: src and dst meshes must cover the "
            "same device set (a shrink/grow is a repartition — use "
            "reshard / shard_vector from host state)")
    pairs = tuple(
        (c, src.index(dst[c])) for c in range(len(src)))
    moved = sum(1 for s, t in pairs if s != t)
    return pairs, moved


# One compiled chunk-permute program per (src, dst) mesh fingerprint
# pair — the tentpole cache.  jit handles chunk shape/dtype retraces
# within an entry; the fingerprint key (not Mesh object identity)
# means two equal meshes built independently share one program.
_PERMUTE_PROGRAMS: Dict[Tuple[str, str], tuple] = {}


def _chunk_permute_program(src_mesh: Mesh, dst_mesh: Mesh):
    from .dist_csr import mesh_fingerprint

    key = (mesh_fingerprint(src_mesh), mesh_fingerprint(dst_mesh))
    hit = _PERMUTE_PROGRAMS.get(key)
    if hit is not None:
        return hit
    pairs, moved = chunk_permute_plan(src_mesh, dst_mesh)
    axes = (tuple(src_mesh.axis_names)
            if len(src_mesh.axis_names) > 1 else src_mesh.axis_names[0])
    spec = _vector_spec(src_mesh)

    def kernel(chunk):
        return jax.lax.ppermute(chunk, axes, pairs)

    fn = jax.jit(shard_map(
        kernel, mesh=src_mesh, in_specs=(spec,), out_specs=spec,
        check_vma=False,
    ))
    built = (fn, pairs, moved)
    _PERMUTE_PROGRAMS[key] = built
    return built


def reshard_vector(x: jax.Array, mesh: Mesh,
                   layout: str = LAYOUT_1D_ROW) -> jax.Array:
    """Move a sharded padded vector onto ``mesh``'s placement via the
    cached chunk-permute program (same device set; eager only — the
    rewrap below assembles per-device buffers, which has no traced
    equivalent).  The result is the SAME global vector sharded as
    ``shard_vector`` would shard it over ``mesh``/``layout``; chunks
    whose source and destination device coincide never cross the
    interconnect."""
    src_mesh = x.sharding.mesh
    G = int(np.asarray(src_mesh.devices).size)
    L = int(x.shape[0])
    if int(np.asarray(mesh.devices).size) != G:
        from .dist_csr import mesh_fingerprint

        # Name BOTH endpoint fingerprints: the placement controller
        # debugs failed migrations by the same mesh_fingerprint keys
        # its plans and the permute-program cache are ledgered under.
        raise ValueError(
            f"reshard_vector: device count changed ({G} -> "
            f"{int(np.asarray(mesh.devices).size)}; src mesh "
            f"{mesh_fingerprint(src_mesh)} -> dst mesh "
            f"{mesh_fingerprint(mesh)}); a mesh "
            "shrink/grow is a repartition — re-shard from host state "
            "(shard_vector / checkpoint restore)")
    if L % G:
        raise ValueError(
            f"reshard_vector: length {L} not divisible by {G} chunks")
    fn, pairs, moved = _chunk_permute_program(src_mesh, mesh)
    item = jnp.dtype(x.dtype).itemsize
    vols = _comm.reshard_volumes(moved_chunks=moved,
                                 chunk_elems=L // G, itemsize=item,
                                 shards=G)
    comm_bytes = _comm.record("dist_reshard", vols,
                              calls={"ppermute": 1}, layout=layout)
    with _obs.span("dist_reshard", shards=G, moved=moved,
                   comm_bytes=comm_bytes):
        out = fn(x)
        if moved == 0 and src_mesh is mesh:
            return out
        # The program leaves chunk c's bytes ON its destination
        # device; re-wrap those buffers under the destination mesh's
        # sharding without another copy.
        per_dev = {s.device: s.data for s in out.addressable_shards}
        dst_sh = NamedSharding(mesh, _vector_spec(mesh))
        arrays = [per_dev[d] for d in _flat_devices(mesh)]
        return jax.make_array_from_single_device_arrays(
            x.shape, dst_sh, arrays)


def _default_mesh(A, layout: str) -> Mesh:
    """Destination mesh over the source matrix's own devices when the
    caller only names a layout."""
    devs = _flat_devices(A.mesh)
    if layout == LAYOUT_2D_BLOCK:
        return make_grid_mesh(devs)
    if layout == LAYOUT_1D_COL:
        return make_grid_mesh(devs, (1, len(devs)))
    return make_row_mesh(devs)


def reshard(A, mesh: Optional[Mesh] = None,
            layout: Optional[str] = None):
    """Repartition a :class:`~.dist_csr.DistCSR` onto ``mesh`` /
    ``layout`` (each defaulting to the source's).  Returns ``A``
    itself when the destination ``mesh_fingerprint(mesh, layout)``
    already matches — the zero-byte fast path the recovery ladder
    relies on for no-op rungs.

    The repartition runs ``shard_csr`` on the retained source
    ``csr_array`` — correct for ANY (src, dst) pair including mesh
    shrinks, with host->device bytes ledgered by the existing
    ``transfer.shard_upload*`` counters.  Matrices that did not come
    from ``shard_csr`` (no ``_src_csr``) raise a typed error telling
    the caller to reshard from their own source."""
    from .dist_csr import mesh_fingerprint, shard_csr

    # Delta wrappers (delta/dist.py) carry their pending update
    # buffer across the repartition — resharding must never silently
    # drop buffered mutations (pinned by test_delta.py).
    carry = getattr(A, "_delta_reshard_carry", None)
    if carry is not None:
        return carry(mesh, layout)

    lay = A.layout if layout is None else resolve_layout(layout)
    dst_mesh = _default_mesh(A, lay) if mesh is None else mesh
    _obs.inc("op.reshard")
    if (mesh_fingerprint(dst_mesh, lay)
            == mesh_fingerprint(A.mesh, A.layout)):
        _obs.event("reshard.matrix", moved=False, layout=lay,
                   shards=A.num_shards)
        return A
    src = getattr(A, "_src_csr", None)
    if src is None:
        raise ValueError(
            "reshard: this DistCSR carries no retained source matrix "
            "(_src_csr); shard_csr retains one — rebuild via "
            "shard_csr, or repartition your own source explicitly")
    with _obs.span("dist_reshard_matrix", layout=lay,
                   shards=int(np.asarray(dst_mesh.devices).size)):
        B = shard_csr(src, mesh=dst_mesh, layout=lay)
    _obs.event("reshard.matrix", moved=True, layout=lay,
               src_layout=A.layout, shards=B.num_shards)
    return B
