# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Shared benchmark harness for the examples.

Parity target: the reference's ``examples/common.py`` (triple-backend
``--package`` switch ``common.py:162-199``, Timer protocol
``common.py:52-101``, matrix generators ``common.py:206-347``).

TPU-first re-design:

- Backends are ``tpu`` (this framework: jax-backed sparse + jitted
  solvers) and ``scipy`` (host differential baseline).  The reference's
  third backend (cupy) has no TPU analog.
- ``JaxTimer`` brackets timed regions with a host round-trip fetch —
  the XLA analog of ``legate.timing.time``'s implicit execution fence
  (reference ``common.py:52-66``), and the only sync that holds on
  detached-dispatch backends (see the class docstring).
- Phase scoping (reference ``Machine.only`` CPU-build/GPU-solve,
  ``common.py:128-159``) is a no-op scope: on TPU the build phase runs
  on host numpy and the solve phase under jit — the split is structural
  rather than machine-scoped.
- Matrix generators build with vectorized host numpy, then hand off to
  the sparse package; every generator matches the reference's output
  pattern exactly (checked by tests/test_examples.py).
"""

import argparse
import importlib
import importlib.util
import os
import sys

import numpy

# Make the repo checkout importable when examples run uninstalled
# (`python examples/pde.py` puts examples/ on sys.path, not the root).
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.isdir(os.path.join(_ROOT, "legate_sparse_tpu")):
    # find_spec tests importability without executing the package (the
    # scipy baseline path must stay JAX-free, and importing the package
    # pulls in jax).
    if importlib.util.find_spec("legate_sparse_tpu") is None:
        sys.path.insert(0, _ROOT)


def harness_float():
    """Value dtype for the matrix generators.

    tpu package runs follow the platform policy (f32 on TPU where f64
    is emulated, scipy-parity f64 on CPU — ``settings.x64`` auto mode),
    avoiding f64 host arrays that would be silently downcast at device
    put.  ``--package scipy`` runs always get float64: the host
    differential baseline keeps its independent f64 precision and stays
    JAX-free."""
    sparse_mod = globals().get("sparse")
    if sparse_mod is not None and sparse_mod.__name__.startswith("scipy"):
        return numpy.float64
    try:
        from legate_sparse_tpu.runtime import runtime

        return runtime.default_float
    except Exception:
        return numpy.float64


def get_arg_number(arg: str) -> int:
    """Parse '4k' / '2m' / '1g' style sizes (reference ``common.py:22-37``)."""
    arg = arg.lower()
    if not arg:
        return 1
    mult = 1
    if arg[-1] == "k":
        mult, arg = 1024, arg[:-1]
    elif arg[-1] == "m":
        mult, arg = 1024 * 1024, arg[:-1]
    elif arg[-1] == "g":
        mult, arg = 1024 * 1024 * 1024, arg[:-1]
    return int(arg) * mult


class JaxTimer:
    """Wall-clock timer with device synchronization at both ends.

    Synchronization is a host ROUND TRIP (fetch a scalar computed from
    a device buffer), not ``block_until_ready``: on a detached-dispatch
    backend ``block_until_ready`` can return at dispatch-ack, before the
    device finishes, and a barrier-timed region then measures nothing
    (see ``legate_sparse_tpu/bench_timing.py``).
    Execution is in-order per device, so fetching a freshly dispatched
    scalar waits for all previously dispatched work.
    """

    def __init__(self):
        self._start = None
        self._token = None

    def _sync(self):
        import jax
        import jax.numpy as jnp

        jax.effects_barrier()
        if self._token is None:
            self._token = jnp.zeros((1,), jnp.float32)
        # Device-dependent fetch: queued behind all prior dispatches.
        float((self._token + 1.0)[0])

    def start(self):
        import time

        # Drain everything already dispatched so it is not charged to
        # the timed region (the reference's implicit fence).
        self._sync()
        self._start = time.perf_counter_ns()

    def stop(self, result=None):
        """Milliseconds since start(); round-trip syncs (on ``result``'s
        first element if given — the cheapest true completion proof)."""
        import time
        import numpy as _np

        if result is not None:
            import jax

            leaves = jax.tree_util.tree_leaves(result)
            for leaf in leaves:
                if hasattr(leaf, "ravel") and getattr(leaf, "size", 0):
                    float(_np.asarray(leaf.ravel()[0]))
                    break
            else:
                self._sync()
        else:
            self._sync()
        return (time.perf_counter_ns() - self._start) / 1e6


class NumPyTimer:
    def __init__(self):
        self._start = None

    def start(self):
        import time

        self._start = time.perf_counter_ns()

    def stop(self, result=None):
        import time

        return (time.perf_counter_ns() - self._start) / 1e6


class DummyScope:
    """No-op context manager standing in for the reference's
    phase-scoped Machine contexts (``common.py:104-159``)."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def get_phase_procs(use_tpu: bool):
    """Build/solve phase scopes.  On TPU both phases are the whole
    device set; XLA owns placement (reference ``common.py:128-159``)."""
    return DummyScope(), DummyScope()


def parse_common_args():
    parser = argparse.ArgumentParser(add_help=False)
    parser.add_argument(
        "--package",
        type=str,
        default="tpu",
        choices=["tpu", "legate", "scipy"],
        help="'tpu' (alias 'legate') = this framework; 'scipy' = host baseline",
    )
    parser.add_argument(
        "--profile",
        type=str,
        default=None,
        metavar="DIR",
        help="capture a jax.profiler trace of the run into DIR "
             "(the Legion Prof analog: named_scope provenance from the "
             "coverage layer shows up as trace annotations)",
    )
    args, _ = parser.parse_known_args()

    if args.package in ("tpu", "legate"):
        # JAX's own platform choice; the compile cache is placed
        # before the first compile.
        from legate_sparse_tpu import _platform

        _platform.use_compile_cache()

    if args.profile and args.package in ("tpu", "legate"):
        # tpu path only: the scipy baseline must stay JAX-free (and its
        # trace would carry none of the named_scope annotations anyway).
        import atexit

        import jax

        jax.profiler.start_trace(args.profile)
        atexit.register(jax.profiler.stop_trace)
        print(f"profiling -> {args.profile} (view with TensorBoard)")
    elif args.profile:
        print("--profile ignored for --package scipy (JAX-free baseline)")

    if args.package in ("tpu", "legate"):
        timer = JaxTimer()
        np_mod = numpy
        sparse = importlib.import_module("legate_sparse_tpu")
        linalg = importlib.import_module("legate_sparse_tpu.linalg")
        use_tpu = True
    else:
        timer = NumPyTimer()
        np_mod = numpy
        sparse = importlib.import_module("scipy.sparse")
        linalg = importlib.import_module("scipy.sparse.linalg")
        use_tpu = False

    globals()["np"] = np_mod
    globals()["sparse"] = sparse
    globals()["linalg"] = linalg
    return args.package, timer, np_mod, sparse, linalg, use_tpu


def banded_matrix(N: int, nnz_per_row: int, from_diags: bool = False):
    """Banded CSR with 1.0 values (reference ``common.py:206-249``).

    ``from_diags=False`` builds (data, indices, indptr) directly with
    vectorized numpy — same construction the reference uses, minus its
    per-backend branching.
    """
    if from_diags:
        return sparse.diags(
            [1.0] * nnz_per_row,
            [d - nnz_per_row // 2 for d in range(nnz_per_row)],
            shape=(N, N),
            format="csr",
            dtype=harness_float(),
        )
    assert N > nnz_per_row and nnz_per_row % 2 == 1
    half = nnz_per_row // 2
    cols = numpy.tile(
        numpy.arange(-half, nnz_per_row - half), N
    ) + numpy.repeat(numpy.arange(N), nnz_per_row)
    mask = (cols >= 0) & (cols < N)
    cols = cols[mask]
    data = numpy.ones(cols.shape[0], dtype=harness_float())
    counts = mask.reshape(N, nnz_per_row).sum(axis=1)
    indptr = numpy.zeros(N + 1, dtype=numpy.int64)
    numpy.cumsum(counts, out=indptr[1:])
    return sparse.csr_array(
        (data, cols.astype(numpy.int64), indptr), shape=(N, N)
    )


def stencil_grid(S, grid, dtype=None):
    """CSR operator applying stencil ``S`` over an N-D ``grid`` with
    zero (Dirichlet) boundaries (reference ``common.py:252-310``).

    Vectorized: one DIA band per nonzero stencil entry, boundary
    connections zeroed by index arithmetic instead of slice loops.
    """
    dtype = harness_float() if dtype is None else dtype
    S = numpy.asarray(S, dtype=dtype)
    grid = tuple(int(g) for g in grid)
    N_v = int(numpy.prod(grid))
    strides = numpy.cumprod([1] + list(reversed(grid)))[:-1][::-1]

    offsets = []
    bands = []
    centered = [idx - (s // 2) for idx, s in zip(numpy.nonzero(S), S.shape)]
    coords_nd = numpy.unravel_index(numpy.arange(N_v), grid)
    for entry in range(centered[0].shape[0]):
        off_nd = [int(c[entry]) for c in centered]
        diag = int(sum(o * st for o, st in zip(off_nd, strides)))
        if abs(diag) >= N_v:
            continue
        val = S[tuple(idx[entry] for idx in numpy.nonzero(S))]
        band = numpy.full(N_v, val, dtype=dtype)
        # Zero connections that would wrap across the grid boundary:
        # position p connects to p+diag only if every coordinate stays
        # in range after the per-axis offset.
        ok = numpy.ones(N_v, dtype=bool)
        for axis, o in enumerate(off_nd):
            c = coords_nd[axis]
            ok &= (c + o >= 0) & (c + o < grid[axis])
        band[~ok] = 0.0
        # DIA convention: band value for column j lives at band[j].
        shifted = numpy.zeros(N_v, dtype=dtype)
        src = numpy.arange(N_v)
        dst = src + diag
        sel = (dst >= 0) & (dst < N_v)
        shifted[dst[sel]] = band[src[sel]]
        offsets.append(diag)
        bands.append(shifted)

    offsets_a = numpy.array(offsets)
    order = numpy.argsort(offsets_a)
    uniq, inv = numpy.unique(offsets_a[order], return_inverse=True)
    data = numpy.zeros((uniq.shape[0], N_v), dtype=dtype)
    for k, band in enumerate(numpy.asarray(bands)[order]):
        data[inv[k]] += band
    return sparse.dia_array((data, uniq), shape=(N_v, N_v)).tocsr()


def poisson2D(N: int):
    """5-point 2-D Poisson operator, N*N unknowns (reference
    ``common.py:313-327``)."""
    first = numpy.full(N - 1, -1.0)
    chunks = numpy.concatenate([numpy.zeros(1), first])
    diag_size = N * N - 1
    diag_a = numpy.concatenate(
        [first, numpy.tile(chunks, (diag_size - (N - 1)) // N)]
    )
    diag_g = -1.0 * numpy.ones(N * (N - 1))
    diag_c = 4.0 * numpy.ones(N * N)
    return sparse.diags(
        [diag_g, diag_a, diag_c, diag_a, diag_g],
        [-N, -1, 0, 1, N],
        dtype=harness_float(),
    ).tocsr()


def diffusion2D(N: int, epsilon: float = 1.0, theta: float = 0.0):
    """9-point rotated-anisotropy diffusion operator (reference
    ``common.py:330-347``)."""
    eps = float(epsilon)
    C = numpy.cos(float(theta))
    S = numpy.sin(float(theta))
    CS, CC, SS = C * S, C * C, S * S
    a = (-1 * eps - 1) * CC + (-1 * eps - 1) * SS + (3 * eps - 3) * CS
    b = (2 * eps - 4) * CC + (-4 * eps + 2) * SS
    c = (-1 * eps - 1) * CC + (-1 * eps - 1) * SS + (-3 * eps + 3) * CS
    d = (-4 * eps + 2) * CC + (2 * eps - 4) * SS
    e = (8 * eps + 8) * CC + (8 * eps + 8) * SS
    stencil = numpy.array([[a, b, c], [d, e, d], [c, b, a]]) / 6.0
    return stencil_grid(stencil, (N, N))
