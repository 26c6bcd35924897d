# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Iterative solvers: CG, GMRES, LinearOperator.

Parity target: the reference's solver layer (reference:
``legate_sparse/linalg.py:85-668`` — ``LinearOperator`` family,
``cg_axpby`` fused update, ``cg`` with deferred convergence checks,
restarted ``gmres``).

TPU-first re-design: the reference hides latency by keeping scalars as
Legion futures and testing convergence every ``conv_test_iters``
iterations (``linalg.py:507-533``).  The XLA-native equivalent is
stronger: the *entire* CG iteration runs inside ``lax.while_loop`` under
one ``jit`` — zero host round-trips until the solve finishes; the
convergence cadence is preserved for iteration-count parity.  The fused
``cg_axpby`` kernel (reference ``axpby_template.inl:27-71``) exists for
API parity but fuses automatically when used inside jit.
"""

from __future__ import annotations

import warnings
from functools import partial
from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp

from . import obs as _obs
from .obs import latency as _lat
from .resilience import checkpoint as _rckpt
from .resilience import deadline as _rdeadline
from .resilience import faults as _rfaults
from .resilience import health as _rhealth
from .resilience import policy as _rpolicy
from .settings import settings as _rsettings
from .types import index_dtype

from .csr import csr_array
from .utils import fill_out as _fill_out, is_sparse_matrix


# --------------------------------------------------------------------------
# LinearOperator family (reference ``linalg.py:85-421``)
# --------------------------------------------------------------------------
class LinearOperator:
    """Common interface for matrix-vector products.

    Iterative solvers only need ``A @ v``; this class abstracts matrices,
    callables, and compositions behind ``matvec``/``rmatvec`` the same
    way the reference (and scipy) do.  Matvec implementations must be
    jax-traceable to participate in jitted solver loops.
    """

    ndim = 2

    def __new__(cls, *args, **kwargs):
        if cls is LinearOperator:
            return super().__new__(_CustomLinearOperator)
        obj = super().__new__(cls)
        if (
            type(obj)._matvec == LinearOperator._matvec
            and type(obj)._matmat == LinearOperator._matmat
        ):
            warnings.warn(
                "LinearOperator subclass should implement"
                " at least one of _matvec and _matmat.",
                category=RuntimeWarning,
                stacklevel=2,
            )
        return obj

    def __init__(self, dtype, shape):
        if dtype is not None:
            dtype = np.dtype(dtype)
        self.dtype = dtype
        self.shape = tuple(int(s) for s in shape)

    def _init_dtype(self):
        if self.dtype is None:
            v = jnp.zeros(self.shape[-1])
            self.dtype = np.dtype(self.matvec(v).dtype)

    # -- default implementations, each in terms of the other --
    def _matvec(self, x, out=None):
        return self._matmat(x.reshape(-1, 1), out=out).reshape(-1)

    def _matmat(self, X, out=None):
        cols = [self._matvec(X[:, j]) for j in range(X.shape[1])]
        result = jnp.stack(cols, axis=1)
        return result

    def _rmatvec(self, x, out=None):
        raise NotImplementedError("rmatvec is not defined")

    def matvec(self, x, out=None):
        M, N = self.shape
        if x.shape != (N,) and x.shape != (N, 1):
            raise ValueError("dimension mismatch")
        return self._matvec(x, out=out)

    def rmatvec(self, x, out=None):
        M, N = self.shape
        if x.shape != (M,) and x.shape != (M, 1):
            raise ValueError("dimension mismatch")
        return self._rmatvec(x, out=out)

    def matmat(self, X, out=None):
        if X.ndim != 2:
            raise ValueError("expected 2-d array")
        M, N = self.shape
        if X.shape[0] != N:
            raise ValueError("dimension mismatch")
        return self._matmat(X, out=out)

    def __matmul__(self, x):
        if x.ndim == 1:
            return self.matvec(x)
        return self.matmat(x)


class _CustomLinearOperator(LinearOperator):
    """LinearOperator from user callables (reference ``linalg.py:312-372``)."""

    def __init__(
        self, shape, matvec, rmatvec=None, matmat=None, dtype=None,
        rmatmat=None,
    ):
        super().__init__(dtype, shape)
        self.__matvec_impl = matvec
        self.__rmatvec_impl = rmatvec
        self.__matmat_impl = matmat
        self.__rmatmat_impl = rmatmat
        self._init_dtype()

    def _matvec(self, x, out=None):
        result = self.__matvec_impl(x)
        return _fill_out(result, out)

    def _rmatvec(self, x, out=None):
        if self.__rmatvec_impl is None:
            raise NotImplementedError("rmatvec is not defined")
        return _fill_out(self.__rmatvec_impl(x), out=out)

    def _matmat(self, X, out=None):
        if self.__matmat_impl is not None:
            return _fill_out(self.__matmat_impl(X), out)
        return super()._matmat(X, out=out)


class _SparseMatrixLinearOperator(LinearOperator):
    """Wraps a csr_array; caches the conjugate transpose for rmatvec
    (reference ``linalg.py:375-390``).

    Engine routing (``settings.engine``): construction — always a
    concrete context — eagerly builds the engine's bucketed traceable
    matvec for eligible matrices, so solver loops (cg/gmres/...) run
    their in-trace matvecs through the same cached plan the eager
    ``A @ x`` dispatch uses.  The closure slices back to ``n`` before
    returning, so solver reductions — and results — are bit-for-bit
    the unpadded kernel's (``docs/ENGINE.md``)."""

    def __init__(self, A: csr_array):
        self.A = A
        self.AT = None
        self._engine_mv = None
        from .settings import settings as _settings

        if _settings.engine:
            from . import obs as _obs
            from .engine import get_engine

            # Same "engine on is always safe" contract as
            # route_matvec: a plan-build failure (including the
            # cached-failure fast path) must not make a solve raise
            # where the normal dispatch would succeed.
            try:
                self._engine_mv = get_engine().traceable_matvec(A)
            except Exception as e:
                _obs.inc("engine.route.error")
                _obs.event("engine.route.error", op="solver_matvec",
                           error=repr(e)[:200])
        super().__init__(A.dtype, A.shape)

    def _matvec(self, x, out=None):
        if (self._engine_mv is not None
                and isinstance(x, jax.core.Tracer)
                and np.result_type(self.A.dtype, x.dtype)
                == np.dtype(self.A.dtype)
                and self._engine_fresh()):
            # Inside a solver trace the AOT route declines; the
            # traceable closure keeps the loop on the bucketed kernel.
            # The dtype gate mirrors engine eligibility: a PROMOTED
            # iterate (f64 rhs over an f32 matrix, complex over real —
            # what _promote_rhs arranges) must not be downcast by the
            # closure's astype; those solves keep the normal dispatch.
            return _fill_out(self._engine_mv(x), out)
        return self.A.dot(x, out=out)

    def _engine_fresh(self) -> bool:
        """The construction-time closure captured padded COPIES of the
        operands; an in-place mutation of ``A`` since then (which
        clears ``A._engine_pack``) would make it a silent solve of the
        OLD matrix — fall back to the live dispatch instead."""
        cached = getattr(self.A, "_engine_pack", None)
        return (cached is not None
                and cached[1] is getattr(self._engine_mv, "pack", None))

    def _rmatvec(self, x, out=None):
        if self.AT is None:
            self.AT = self.A.T.conj(copy=False)
        return self.AT.dot(x, out=out)


class _DenseMatrixLinearOperator(LinearOperator):
    def __init__(self, A):
        self.A = jnp.asarray(A)
        super().__init__(self.A.dtype, self.A.shape)

    def _matvec(self, x, out=None):
        return _fill_out(self.A @ x, out)

    def _rmatvec(self, x, out=None):
        return _fill_out(self.A.conj().T @ x, out)


class IdentityOperator(LinearOperator):
    """No-op operator (reference ``linalg.py:392-414``)."""

    def __init__(self, shape, dtype=None):
        super().__init__(dtype, shape)

    def _matvec(self, x, out=None):
        return _fill_out(x, out)

    def _rmatvec(self, x, out=None):
        return _fill_out(x, out)


def maybe_jit(fun, **jit_kwargs):
    """``jax.jit(fun)`` in single-controller runs; the bare function in
    multi-process runs.

    Explicit jit embeds closure-captured arrays as trace CONSTANTS,
    and a multi-controller run forbids constants that span processes
    ("Closing over jax.Array that spans non-addressable devices").
    Eagerly-executed ``lax`` control flow lifts those captures to
    arguments instead, so dropping the wrapper keeps the heavy inner
    scans/loops compiled while making the composition legal on a
    process-spanning mesh.  Single-controller behavior is unchanged.
    """
    if jax.process_count() == 1:
        return jax.jit(fun, **jit_kwargs)
    return fun


def _promote_rhs(b, A_op):
    """Solve in ``result_type(A, b)`` (scipy parity): a real rhs on a
    complex operator — or f32 rhs on an f64 operator — must not build
    mixed-dtype while_loop carries (loud TypeError) or silently cast
    complex iterates down to real (silent wrong answers in gmres)."""
    if A_op.dtype is None:
        return b
    dt = jnp.result_type(A_op.dtype, b.dtype)
    return b.astype(dt) if b.dtype != dt else b


def make_linear_operator(A) -> LinearOperator:
    """Promote matrices/callables to LinearOperator (reference
    ``linalg.py:417-431``).  scipy sparse operands convert to the
    package's csr so every native solver accepts them directly."""
    from .csr import _is_scipy_sparse

    if isinstance(A, LinearOperator):
        return A
    if _is_scipy_sparse(A):
        A = csr_array(A)
    if is_sparse_matrix(A):
        if not isinstance(A, csr_array):
            A = A.tocsr()
        return _SparseMatrixLinearOperator(A)
    return _DenseMatrixLinearOperator(A)


# --------------------------------------------------------------------------
# Fused vector updates (reference ``linalg.py:424-451`` + AXPBY task)
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("isalpha", "negate"))
def _cg_axpby_impl(y, x, a, b, isalpha: bool, negate: bool):
    coef = a / b
    if negate:
        coef = -coef
    if isalpha:
        return coef * x + y  # y = (±a/b)·x + y
    return x + coef * y      # y = x + (±a/b)·y


def cg_axpby(y, x, a, b, isalpha: bool = True, negate: bool = False):
    """y = alpha*x + beta*y with the alpha/beta division fused in-kernel.

    API parity with the reference (``linalg.py:434-451``), which passes
    ``a``/``b`` as futures so alpha = a/b is computed inside the task.
    Under jit the division and AXPBY fuse into one VPU pass anyway; numpy
    ``y`` is updated in place to preserve the reference's mutation
    contract.
    """
    result = _cg_axpby_impl(
        jnp.asarray(y), jnp.asarray(x), jnp.asarray(a), jnp.asarray(b),
        bool(isalpha), bool(negate),
    )
    if isinstance(y, np.ndarray):
        np.copyto(y, np.asarray(result, dtype=y.dtype))
        return y
    return result


def _get_atol_rtol(b_norm, tol=None, atol=0.0, rtol=1e-5):
    """scipy-compatible tolerance resolution (reference ``linalg.py:454-462``)."""
    rtol = float(tol) if tol is not None else rtol
    if atol is None:
        atol = rtol
    atol = max(float(atol), float(rtol) * float(b_norm))
    return atol, rtol


# --------------------------------------------------------------------------
# Mixed-precision iterative refinement (compressed-storage inner solves)
# --------------------------------------------------------------------------

_REFINE_AUTO_CYCLES = 12   # "auto": outer-correction cycle budget
_REFINE_INNER_RTOL = 1e-2  # per-cycle inner residual-reduction target


def _refine_inner_operator(A) -> csr_array:
    """The compressed-storage inner operator behind ``refine=``: one
    precision rung below the system dtype — f64 values drop to f32,
    f32 values to bf16 — with int16 column indices whenever the width
    fits, built through :meth:`csr_array.compress`.  The inner Krylov
    sweep streams the narrow bytes (that is the roofline win); the
    outer full-precision residual correction restores the grade.
    Raises for operands refinement cannot serve: the knob is an
    explicit request, and silently solving unrefined would lie."""
    if is_sparse_matrix(A) and not isinstance(A, csr_array):
        A = A.tocsr()
    if not isinstance(A, csr_array):
        raise ValueError(
            "refine= needs a sparse-matrix operand (the inner solve "
            "runs over compressed csr_array storage); got "
            f"{type(A).__name__}")
    dt = np.dtype(A.dtype)
    if dt == np.float64:
        return A.compress(values="float32")
    if dt == np.float32:
        return A.compress()
    raise ValueError(
        f"refine= serves float32/float64 systems (got {dt.name}: "
        "storage is already low-precision — solve it directly)")


def _refine_cycles(refine) -> int:
    if refine == "auto":
        return _REFINE_AUTO_CYCLES
    cycles = int(refine)
    if cycles <= 0:
        raise ValueError(
            f"refine= must be 'auto' or a positive cycle count, "
            f"got {refine!r}")
    return cycles


def _refined_solve(solver: str, inner_solve: Callable, A_op, A_in,
                   b, x, atol: float, maxiter: int, cycles: int):
    """The shared iterative-refinement driver behind ``cg``/``gmres``
    ``refine=``.

    Classic mixed-precision IR: a full-precision residual
    ``r = b - A x`` against the matrix the caller handed us, an inner
    Krylov correction solve over the compressed-storage operator
    ``A_in`` in f32 vectors (to :data:`_REFINE_INNER_RTOL` relative —
    the grade low-precision storage can actually deliver), then a
    full-precision update ``x += d``.  Convergence is judged on the
    TRUE residual, so the refined solve meets the same ``atol`` the
    unrefined f32/f64 solve would.

    Host-sync cadence contract: ONE stacked fetch per refinement
    cycle — the residual-norm convergence decision (counted as
    ``transfer.host_sync.<solver>_refine``), matching the solvers'
    existing one-fetch-per-cycle discipline; the opt-in health monitor
    (docs/RESILIENCE.md) rides that same fetch.
    """
    site = f"solver.{solver}.refine"
    monitor = _rhealth.Monitor(site) if _rsettings.resil else None
    inner_dt = (jnp.float32 if np.dtype(b.dtype) == np.float64
                else b.dtype)
    total = 0
    rn = None
    with _obs.span(solver + ".refine", n=int(b.shape[0]),
                   cycles=cycles,
                   inner_dtype=np.dtype(A_in.dtype).name) as sp:
        for cycle in range(cycles):
            r = b - A_op.matvec(x)
            rn = float(jnp.linalg.norm(r))     # the per-cycle fetch
            _obs.inc(f"transfer.host_sync.{solver}_refine")
            if monitor is not None:
                monitor.observe(rn, total, partial=x)
            if _rsettings.resil:
                # The refinement fetch IS a cadence point: enforce the
                # request deadline here too, or a refined solve could
                # outlive its budget unnoticed (regression-tested).
                _rdeadline.raise_if_expired(site, iterations=total,
                                            residual=rn, partial=x)
            if rn < atol or total >= maxiter:
                break
            d, it = inner_solve(
                A_in, r.astype(inner_dt),
                max(atol, _REFINE_INNER_RTOL * rn), maxiter - total,
            )
            total += max(int(it), 1)
            x = x + d.astype(b.dtype)
        if sp is not None:
            sp.set(iters=total, resid=rn)
    return x, total


# --------------------------------------------------------------------------
# CG (reference ``linalg.py:465-535``)
# --------------------------------------------------------------------------
def _cg_builders(A_mv: Callable, M_mv: Callable, conv_test_iters: int):
    """The (cond, body) pair of the CG while_loop — shared verbatim by
    the one-shot loop (``_cg_loop``) and the chunked resilience loop
    (``_cg_loop_resil``), so the two apply the *identical* iteration
    and differ only in where the loop bound lives."""

    def cond(state):
        x, r, p, rho, iters, done, atol2, miter = state
        return jnp.logical_and(iters < miter, jnp.logical_not(done))

    def body(state):
        x, r, p, rho_old, iters, done, atol2, miter = state
        z = M_mv(r)
        rho = jnp.vdot(r, z)
        # Safe divides: an exactly-zero residual (x0 == solution) must
        # flow through to the convergence check, not produce NaNs.
        beta = jnp.where(
            jnp.logical_or(iters == 0, rho_old == 0),
            jnp.zeros_like(rho),
            rho / jnp.where(rho_old == 0, jnp.ones_like(rho_old), rho_old),
        )
        p = z + beta * p
        q = A_mv(p)
        pq = jnp.vdot(p, q)
        alpha = jnp.where(
            pq == 0,
            jnp.zeros_like(rho),
            rho / jnp.where(pq == 0, jnp.ones_like(pq), pq),
        )
        x = x + alpha * p
        r = r - alpha * q
        iters = iters + 1
        check = jnp.logical_or(
            iters % conv_test_iters == 0, iters == miter - 1
        )
        rnorm2 = jnp.real(jnp.vdot(r, r))
        done = jnp.logical_or(done, jnp.logical_and(check, rnorm2 < atol2))
        return (x, r, p, rho, iters, done, atol2, miter)

    return cond, body


def _cg_state0(A_mv: Callable, b, x0, atol: float, maxiter: int):
    r0 = b - A_mv(x0)
    return (
        x0,
        r0,
        jnp.zeros_like(b),
        jnp.ones((), dtype=b.dtype),
        jnp.asarray(0, dtype=index_dtype()),
        jnp.asarray(False),
        jnp.asarray(atol, dtype=jnp.real(b).dtype) ** 2,
        jnp.asarray(maxiter, dtype=index_dtype()),
    )


def _cg_loop(A_mv: Callable, M_mv: Callable, b, x0, atol: float,
             maxiter: int, conv_test_iters: int):
    """Whole preconditioned-CG solve as one XLA while_loop.

    State carries (x, r, p, rho, iters, done) plus the loop-invariant
    (atol2, maxiter) *as state* — dynamic values rather than trace-time
    constants, so solves with different tolerances/iteration budgets
    (e.g. a warmup run followed by a timed run) reuse one compiled
    loop instead of recompiling.
    """
    cond, body = _cg_builders(A_mv, M_mv, conv_test_iters)
    out = jax.lax.while_loop(
        cond, body, _cg_state0(A_mv, b, x0, atol, maxiter))
    return out[0], out[4]


def _resil_solver_active() -> bool:
    """Route a solve through the chunked resilience driver?  Requires
    the master switch AND something that needs per-cycle host
    decisions (an active deadline scope, health detection opted in,
    or a checkpoint scope that wants the fetch cadence) — so
    ``LEGATE_SPARSE_TPU_RESIL=1`` alone leaves the one-shot
    while_loop path untouched."""
    return _rsettings.resil and (
        _rdeadline.current() is not None or _rhealth.active()
        or _rckpt.active())


def _cg_chunk(A_mv: Callable, M_mv: Callable, conv_test_iters: int):
    """``chunk(state, limit)``: the CG while_loop from ``state`` until
    it converges, reaches ``maxiter`` or its count reaches ``limit`` —
    the host-stepped unit of ``_cg_loop_resil`` (``conv_test_iters``
    iterations a chunk) and ``_cg_loop_observed`` (one).  Returns the
    state and the stacked scalars (iters, done, ||r||^2)."""
    cond, body = _cg_builders(A_mv, M_mv, conv_test_iters)

    def chunk(state, limit):
        def cond_chunk(st):
            return jnp.logical_and(cond(st), st[4] < limit)

        out = jax.lax.while_loop(cond_chunk, body, state)
        rdt = out[6].dtype
        rn2 = jnp.real(jnp.vdot(out[1], out[1]))
        stats = jnp.stack([out[4].astype(rdt), out[5].astype(rdt),
                           rn2.astype(rdt)])
        return out, stats

    return chunk


def _cg_chunk_state0(A_mv: Callable, b, x0, atol: float, maxiter: int):
    """``_cg_state0`` placed as a chunk's outputs are — on a mesh, the
    scalars replicated over it — so a host-stepped loop traces its
    chunk once, not again at the second step."""
    state = _cg_state0(A_mv, b, x0, atol, maxiter)
    sh = b.sharding
    if not isinstance(sh, jax.sharding.NamedSharding):
        return state
    rep = jax.sharding.NamedSharding(sh.mesh, jax.sharding.PartitionSpec())
    return state[:3] + tuple(jax.device_put(s, rep) for s in state[3:])


def _cg_loop_resil(A_mv: Callable, M_mv: Callable, b, x0, atol: float,
                   maxiter: int, conv_test_iters: int,
                   site: str = "solver.cg.conv"):
    """Deadline/health-aware CG (docs/RESILIENCE.md): the SAME
    while_loop body as ``_cg_loop``, dispatched in chunks of
    ``conv_test_iters`` iterations with ONE stacked-scalar fetch
    (iters, done, ||r||^2) per chunk — the existing convergence
    cadence, so deadline and health checks add zero extra host syncs.
    The carried Krylov state crosses chunk boundaries intact: the
    sequence of body applications is identical to the one-shot loop.

    Deadline expiry raises ``DeadlineExceeded`` with the partial
    iterate; health verdicts (non-finite/divergence/stagnation, when
    opted in) raise ``SolverHealthError``.  The per-chunk dispatch is
    the ``solver.cg.conv`` fault/retry site: a chunk re-runs from its
    entry state, so retries are bit-identical.

    The carried state keeps the TRUE ``maxiter`` (the chunk bound is a
    separate traced limit in the loop condition), so the in-kernel
    convergence checks — including the ``iters == maxiter - 1`` final
    check — fire at exactly the one-shot loop's iterations and the two
    drivers converge at the same count."""
    chunk_fn = maybe_jit(_cg_chunk(A_mv, M_mv, conv_test_iters))
    state = _cg_chunk_state0(A_mv, b, x0, atol, maxiter)
    step = max(int(conv_test_iters), 1)
    monitor = _rhealth.Monitor(site)
    ckpt = _rckpt.current()
    it = 0
    resid = None
    while it < maxiter:
        _rdeadline.raise_if_expired(site, iterations=it,
                                    residual=resid, partial=state[0])
        limit = jnp.asarray(min(it + step, maxiter),
                            dtype=index_dtype())

        def attempt(state=state, limit=limit):
            out, stats = chunk_fn(state, limit)
            return out, _rfaults.fault_point(site, stats)

        # Per-chunk cadence latency (dispatch + the convergence fetch
        # below is timed separately — the chunk IS the cadence unit).
        with _lat.timer("lat.cg.chunk."
                        + _lat.shape_bucket(b.shape[0])):
            state, stats = _rpolicy.run(site, attempt)
        # The chunk's one host sync — the same fetch the convergence
        # decision needs (counted like gmres's cadence counter).
        _obs.inc("transfer.host_sync.cg_conv")
        arr = np.asarray(stats)
        it = int(arr[0])
        done = bool(arr[1])
        resid = float(np.sqrt(arr[2]))
        monitor.observe(resid, it, partial=state[0])
        if ckpt is not None and not done:
            # Checkpoint cadence rides the chunk fetch: snapshot the
            # restartable Krylov state (x, r, p) into host buffers.
            ckpt.maybe_save(it, (state[0], state[1], state[2]))
        if done:
            break
    return state[0], state[4]


def _cg_loop_observed(A_mv: Callable, M_mv: Callable, b, x0, atol: float,
                      maxiter: int, conv_test_iters: int, callback):
    """CG that calls ``callback(x)`` after every iteration: the chunk
    of ``_cg_chunk`` stepped one iteration a dispatch, so the iterates
    are ``_cg_loop``'s.  The host reads the ``done`` flag only where
    the body tests convergence (every ``conv_test_iters``-th iteration
    and at ``maxiter - 1``): one fetch there, counted like
    ``_cg_loop_resil``'s chunk fetches (``transfer.host_sync.cg_conv``)."""
    chunk = _cg_chunk(A_mv, M_mv, conv_test_iters)
    # Stats unused: their ||r||^2 reduction (a psum on a mesh) drops out.
    step_fn = maybe_jit(lambda state, limit: chunk(state, limit)[0])
    state = _cg_chunk_state0(A_mv, b, x0, atol, maxiter)
    it = 0
    while it < maxiter:
        with _obs.span("cg.iter", i=it):
            state = step_fn(state, jnp.asarray(it + 1, dtype=index_dtype()))
        it += 1
        callback(state[0])
        if it % conv_test_iters == 0 or it == maxiter - 1:
            _obs.inc("transfer.host_sync.cg_conv")
            if bool(state[5]):
                break
    return state[0], it


def _cg_solve(name: str, n: int, solve: Callable, host_attrs: Callable,
              b, x0, tol, atol, rtol, maxiter: int, callback, **attrs):
    """The one CG solve routine of ``cg`` and ``parallel.dist_cg``
    (``name`` ``"cg"`` or ``"dist_cg"``): the ``<name>`` ⊃
    ``<name>.launch`` ⊃ ``<name>.norm`` spans, the ‖b‖ fetch (one
    ``transfer.host_sync.<name>_norm``), atol and x0, then
    ``solve(loop, b, x0, atol)`` with ``loop(A_mv, M_mv, b, x0, atol,
    maxiter, conv_test_iters)`` the callback stepper, ``_cg_loop_resil``
    or ``_cg_loop``.  Where the iteration count reaches the host
    (tracing, or a callback solve) ``host_attrs(iters)`` returns the
    span's attributes."""
    with _lat.timer(f"lat.{name}.solve." + _lat.shape_bucket(n)), \
            _obs.span(name, n=n, maxiter=int(maxiter), **attrs) as sp:
        with _obs.span(name + ".launch"):
            with _obs.span(name + ".norm"):
                _obs.inc(f"transfer.host_sync.{name}_norm")
                bnrm2 = float(jnp.linalg.norm(b))
            atol, _ = _get_atol_rtol(bnrm2, tol, atol, rtol)
            if x0 is None:
                x0 = jnp.zeros_like(b)

            def loop(*args):
                # Chosen at the call: a checkpoint scope that ``solve``
                # opens (dist_cg) routes to ``_cg_loop_resil``.
                if callback is not None:
                    return _cg_loop_observed(*args, callback)
                return (_cg_loop_resil if _resil_solver_active()
                        else _cg_loop)(*args)

            x, iters = solve(loop, b, x0, atol)
        if sp is not None or callback is not None:
            # Tracing mode trades one host sync for honest span timing
            # (the fetch is the only trusted completion signal on
            # detached-dispatch backends) and the true iteration count.
            # It is not counted under transfer.host_sync.*: the counted
            # syncs are the same with tracing on and off.
            it = int(iters)
            extra = host_attrs(it)
            if sp is not None:
                sp.set(iters=it, **extra)
    return x, iters


def cg(
    A,
    b,
    x0=None,
    tol=None,
    maxiter=None,
    M=None,
    callback=None,
    atol=0.0,
    rtol=1e-5,
    conv_test_iters: int = 25,
    refine=None,
):
    """Conjugate Gradient solve of ``A x = b`` (scipy-shaped signature,
    reference ``linalg.py:465-535``).  Returns ``(x, iters)``.

    Without a callback the solve is a single jitted while_loop (no host
    sync per iteration).  With a callback the same loop is stepped one
    iteration a dispatch so user code observes every iterate.

    ``refine="auto"`` (or a positive cycle count) switches to
    mixed-precision iterative refinement: inner CG sweeps run over the
    compressed-storage operator (``A.compress()`` — bf16 values under
    f32 systems, f32 under f64, int16 indices where they fit) while
    full-precision residual corrections keep the final residual at the
    same ``atol`` the unrefined solve meets (``_refined_solve``).
    """
    b = jnp.asarray(b)
    if b.ndim == 2 and b.shape[1] == 1:
        b = b.reshape(-1)
    assert b.ndim == 1
    assert len(A.shape) == 2 and A.shape[0] == A.shape[1]
    n = b.shape[0]
    if maxiter is None:
        maxiter = n * 10
    if refine is not None and (M is not None or callback is not None):
        raise ValueError(
            "cg: refine= composes with neither M= nor callback= — "
            "inner sweeps run over the compressed operator without "
            "the outer preconditioner/observer")
    _obs.inc("op.cg")
    A_op = make_linear_operator(A)
    b = _promote_rhs(b, A_op)
    M_op = (
        IdentityOperator(A_op.shape, dtype=A_op.dtype)
        if M is None
        else make_linear_operator(M)
    )
    if x0 is not None:
        x0 = jnp.asarray(x0, dtype=b.dtype).reshape(-1)

    if refine is None:
        def solve(loop, b, x, atol):
            return loop(A_op.matvec, M_op.matvec, b, x, atol,
                        int(maxiter), int(conv_test_iters))
    else:
        def _inner(A_in, r, inner_atol, budget):
            return cg(A_in, r, atol=inner_atol, rtol=0.0,
                      maxiter=budget, conv_test_iters=conv_test_iters)

        def solve(loop, b, x, atol):
            return _refined_solve(
                "cg", _inner, A_op, _refine_inner_operator(A), b, x,
                atol, int(maxiter), _refine_cycles(refine))

    def traffic(it):
        # The per-iteration traffic model of a package operator.
        src = getattr(A_op, "A", None)
        if refine is not None or not isinstance(src, csr_array):
            return {}
        return dict(nnz=src.nnz * it, bytes=src.spmv_traffic_bytes(b) * it,
                    flops=2 * src.nnz * it)

    return _cg_solve("cg", n, solve, traffic, b, x0, tol, atol, rtol,
                     maxiter, callback)


# --------------------------------------------------------------------------
# GMRES (reference ``linalg.py:540-668``)
# --------------------------------------------------------------------------
def _gmres_cycle(A_mv, M_mv, x, b, restart: int):
    """One restart cycle, sync-free: Arnoldi (modified Gram-Schmidt) +
    progressive Givens QR of the Hessenberg + back-substitution +
    solution update, all in one traced program.

    The reference — and this package until PR 2 — stopped the cycle at
    the Hessenberg and round-tripped it to the host for a small
    ``lstsq`` (reference ``linalg.py:640-650``).  Here each new
    Hessenberg column is rotated by the accumulated Givens rotations
    (the ``_sym_ortho``/``_givens`` machinery the MINRES/LSQR/LSMR
    loops already use) as it is produced, so at cycle end the
    factorization R y = g is ready on device: no host transfer exists
    anywhere in the cycle body.

    Returns ``(x_new, stats)`` with ``stats = [beta, resid]``: ``beta``
    is the residual norm at cycle START and ``resid = |g[restart]|``
    the least-squares residual at cycle end — equal to the true
    residual norm of ``x_new`` in exact arithmetic (right-
    preconditioned full cycle).  One host fetch of ``stats`` per cycle
    is the driver's entire convergence cadence.

    Rank deficiency (happy breakdown mid-cycle leaves trailing zero
    columns in R) is handled in the back-substitution: a zero pivot
    contributes y_i = 0, matching ``lstsq``'s minimum-norm solution on
    the decoupled system.
    """
    from .krylov_extra import _givens

    dtype = b.dtype
    rdt = jnp.real(b).dtype
    n = b.shape[0]
    r = b - A_mv(x)
    beta = jnp.linalg.norm(r).astype(rdt)
    V0 = jnp.zeros((restart + 1, n), dtype=dtype)
    V0 = V0.at[0].set(
        jnp.where(beta > 0, r / beta.astype(dtype), r))
    R0 = jnp.zeros((restart, restart), dtype=dtype)
    g0 = jnp.zeros((restart + 1,), dtype=dtype).at[0].set(
        beta.astype(dtype))
    cs0 = jnp.zeros((restart,), dtype=dtype)
    sn0 = jnp.zeros((restart,), dtype=dtype)

    def body(j, carry):
        V, R, g, cs, sn = carry
        w = A_mv(M_mv(V[j]))

        def mgs_step(i, wh):
            w, h = wh
            hij = jnp.vdot(V[i], w) * (i <= j)
            return (w - hij * V[i], h.at[i].set(hij))

        h0 = jnp.zeros((restart + 1,), dtype=dtype)
        w, h = jax.lax.fori_loop(0, j + 1, mgs_step, (w, h0))
        hnorm = jnp.linalg.norm(w)
        h = h.at[j + 1].set(hnorm.astype(dtype))
        V = V.at[j + 1].set(
            jnp.where(hnorm > 1e-30, w / hnorm.astype(dtype), w))

        # Rotate the new column by the accumulated rotations, then form
        # the rotation annihilating its subdiagonal.  O(restart) scalar
        # work fused into the matvec program.
        def rot_step(i, h):
            hi, hi1 = h[i], h[i + 1]
            active = i < j
            new_i = cs[i] * hi + sn[i] * hi1
            new_i1 = -jnp.conj(sn[i]) * hi + jnp.conj(cs[i]) * hi1
            h = h.at[i].set(jnp.where(active, new_i, hi))
            return h.at[i + 1].set(jnp.where(active, new_i1, hi1))

        h = jax.lax.fori_loop(0, j, rot_step, h)
        c, s = _givens(h[j], h[j + 1])
        cs = cs.at[j].set(c)
        sn = sn.at[j].set(s)
        h = h.at[j].set(c * h[j] + s * h[j + 1])
        h = h.at[j + 1].set(jnp.zeros((), dtype))
        g = g.at[j + 1].set(-jnp.conj(s) * g[j])
        g = g.at[j].set(c * g[j])
        R = R.at[:, j].set(h[:restart])
        return (V, R, g, cs, sn)

    V, R, g, cs, sn = jax.lax.fori_loop(
        0, restart, body, (V0, R0, g0, cs0, sn0))

    # Back-substitution on the (restart, restart) triangle — O(m^2)
    # scalar flops, noise next to one SpMV.  Zero pivots (breakdown
    # columns) contribute nothing.
    def back_step(t, y):
        i = restart - 1 - t
        num = g[i] - jnp.dot(R[i], y)
        d = R[i, i]
        safe = jnp.where(d == 0, jnp.ones_like(d), d)
        return y.at[i].set(
            jnp.where(d == 0, jnp.zeros_like(num), num / safe))

    y = jax.lax.fori_loop(0, restart, back_step,
                          jnp.zeros((restart,), dtype=dtype))
    x_new = x + M_mv(y @ V[:restart])
    resid = jnp.abs(g[restart]).astype(rdt)
    return x_new, jnp.stack([beta, resid])


def gmres(
    A,
    b,
    x0=None,
    tol=None,
    restart=None,
    maxiter=None,
    M=None,
    callback=None,
    restrt=None,
    atol=0.0,
    callback_type=None,
    rtol=1e-5,
    refine=None,
):
    """Restarted GMRES (scipy/cupy-shaped signature, reference
    ``linalg.py:540-668``).  Returns ``(x, iters)``.

    ``refine="auto"`` (or a positive cycle count) runs mixed-precision
    iterative refinement: inner restarted-GMRES solves over the
    compressed-storage operator, full-precision residual corrections
    between them — same contract as :func:`cg`'s ``refine=``.

    Each restart cycle — Arnoldi, progressive Givens QR of the
    Hessenberg, triangular solve, solution update — runs as ONE traced
    program with zero host round-trips (``_gmres_cycle``).  The only
    host sync in the whole iteration is one scalar fetch per cycle for
    the convergence decision (counted as
    ``transfer.host_sync.gmres_conv``).  The reference ships the
    Hessenberg to the host for a per-cycle ``lstsq`` (``linalg.py:
    640-650``) — the split this package previously copied and now
    eliminates.
    """
    b = jnp.asarray(b)
    if b.ndim == 2 and b.shape[1] == 1:
        b = b.reshape(-1)
    assert b.ndim == 1
    assert len(A.shape) == 2 and A.shape[0] == A.shape[1]
    assert restrt is None or not restart
    if restrt is not None:
        restart = restrt

    n = b.shape[0]
    bnrm2 = float(jnp.linalg.norm(b))
    atol, _ = _get_atol_rtol(bnrm2, tol, atol, rtol)
    if maxiter is None:
        maxiter = n * 10
    if restart is None:
        restart = 20
    restart = min(int(restart), n)

    A_op = make_linear_operator(A)
    b = _promote_rhs(b, A_op)
    M_op = (
        IdentityOperator(A_op.shape, dtype=A_op.dtype)
        if M is None
        else make_linear_operator(M)
    )
    x = (jnp.zeros(n, dtype=b.dtype) if x0 is None
         else jnp.asarray(x0, dtype=b.dtype).reshape(-1))

    if refine is not None:
        if M is not None or callback is not None:
            raise ValueError(
                "gmres: refine= composes with neither M= nor "
                "callback= — inner cycles run over the compressed "
                "operator without the outer preconditioner/observer")
        _obs.inc("op.gmres")

        def _inner(A_in, r, inner_atol, budget):
            return gmres(A_in, r, atol=inner_atol, rtol=0.0,
                         restart=restart, maxiter=budget)

        return _refined_solve(
            "gmres", _inner, A_op, _refine_inner_operator(A), b, x,
            atol, int(maxiter), _refine_cycles(refine))

    cycle = maybe_jit(
        partial(_gmres_cycle, A_op.matvec, M_op.matvec, restart=restart)
    )

    _obs.inc("op.gmres")
    # Resilience (docs/RESILIENCE.md): the per-cycle dispatch is the
    # ``solver.gmres.conv`` fault/retry site (a cycle re-runs from its
    # entry iterate — bit-identical), the cycle fetch feeds the opt-in
    # health monitor, and deadlines are enforced at the same cadence —
    # all riding the one existing host sync per cycle.
    resil = _rsettings.resil
    monitor = _rhealth.Monitor("solver.gmres.conv") if resil else None
    ckpt = _rckpt.current() if resil else None
    resid_f = None
    iters = 0
    while iters < maxiter:
        if resil:
            _rdeadline.raise_if_expired("solver.gmres.conv",
                                        iterations=iters,
                                        residual=resid_f, partial=x)
        with _lat.timer("lat.gmres.cycle." + _lat.shape_bucket(n)), \
                _obs.span("gmres.cycle", restart=restart,
                          iters_done=iters):
            if resil:
                def _cycle_guarded(x=x):
                    xn, st = cycle(x, b)
                    return xn, _rfaults.fault_point("solver.gmres.conv",
                                                    st)

                x_new, stats = _rpolicy.run("solver.gmres.conv",
                                            _cycle_guarded)
            else:
                x_new, stats = cycle(x, b)
            # The convergence cadence: ONE stacked-scalar fetch per
            # cycle — the only host sync in the restarted iteration
            # (the cycle body is sync-free; tests assert it through
            # this counter).
            _obs.inc("transfer.host_sync.gmres_conv")
            beta_f, resid_f = (float(v) for v in np.asarray(stats))
            if monitor is not None:
                # beta (cycle-start norm) going non-finite is the
                # earliest silent-NaN signal; otherwise judge the
                # cycle-end least-squares residual.
                monitor.observe(
                    beta_f if not np.isfinite(beta_f) else resid_f,
                    iters + restart, partial=x_new)
            if beta_f < atol:
                break          # converged at cycle start: keep x
            x = x_new
        iters += restart
        if ckpt is not None:
            # GMRES restarts from its iterate alone — the Arnoldi seed
            # x is the whole restartable state.
            ckpt.maybe_save(iters, (x,))
        if callback is not None:
            if callback_type == "pr_norm":
                callback(float(jnp.linalg.norm(b - A_op.matvec(x))) / bnrm2)
            else:
                callback(x)
        if resid_f < atol:
            # The Givens estimate equals the true residual norm only in
            # exact arithmetic; confirm on the real residual so MGS
            # drift can never fabricate convergence (one extra sync at
            # suspected convergence only).
            _obs.inc("transfer.host_sync.gmres_conv")
            if float(jnp.linalg.norm(b - A_op.matvec(x))) < atol:
                break
    return x, iters


def _safe_div(num, den):
    """num/den with an exact-0 result (not NaN) when den == 0 — lets
    exactly-converged states flow to the convergence check."""
    return jnp.where(
        den == 0, jnp.zeros_like(num),
        num / jnp.where(den == 0, jnp.ones_like(den), den),
    )


def _bicgstab_body(A_mv: Callable, M_mv: Callable, conv_test_iters: int):
    """One BiCGSTAB iteration as a state->state function (shared by the
    while_loop path and the callback path, so both run the identical
    algorithm with carried shadow-residual/direction state)."""

    def body(state):
        (x, r, rtilde, p, v, rho_prev, alpha, omega, iters, done, atol2,
         miter) = state
        rho = jnp.vdot(rtilde, r)
        beta = _safe_div(rho, rho_prev) * _safe_div(alpha, omega)
        first = iters == 0
        p = jnp.where(first, r, r + beta * (p - omega * v))
        phat = M_mv(p)
        v = A_mv(phat)
        alpha = _safe_div(rho, jnp.vdot(rtilde, v))
        s = r - alpha * v
        shat = M_mv(s)
        t = A_mv(shat)
        omega = _safe_div(jnp.vdot(t, s), jnp.vdot(t, t))
        x = x + alpha * phat + omega * shat
        r = s - omega * t
        iters = iters + 1
        check = jnp.logical_or(
            iters % conv_test_iters == 0, iters == miter - 1
        )
        rnorm2 = jnp.real(jnp.vdot(r, r))
        done = jnp.logical_or(done, jnp.logical_and(check, rnorm2 < atol2))
        return (x, r, rtilde, p, v, rho, alpha, omega, iters, done,
                atol2, miter)

    return body


def _bicgstab_state0(A_mv, b, x0, atol, maxiter):
    r0 = b - A_mv(x0)
    one = jnp.ones((), dtype=b.dtype)
    return (
        x0, r0, r0, jnp.zeros_like(b), jnp.zeros_like(b),
        one, one, one,
        jnp.asarray(0, dtype=index_dtype()), jnp.asarray(False),
        jnp.asarray(atol, dtype=jnp.real(b).dtype) ** 2,
        jnp.asarray(maxiter, dtype=index_dtype()),
    )


def _bicgstab_loop(A_mv: Callable, M_mv: Callable, b, x0, atol: float,
                   maxiter: int, conv_test_iters: int):
    """Whole preconditioned-BiCGSTAB solve as one XLA while_loop (same
    state-carried (atol2, maxiter) and deferred-convergence design as
    ``_cg_loop``)."""

    def cond(state):
        return jnp.logical_and(
            state[8] < state[11], jnp.logical_not(state[9])
        )

    body = _bicgstab_body(A_mv, M_mv, conv_test_iters)
    out = jax.lax.while_loop(
        cond, body, _bicgstab_state0(A_mv, b, x0, atol, maxiter)
    )
    return out[0], out[8]


def bicgstab(
    A,
    b,
    x0=None,
    tol=None,
    maxiter=None,
    M=None,
    callback=None,
    atol=0.0,
    rtol=1e-5,
    conv_test_iters: int = 25,
):
    """BiCGSTAB solve of ``A x = b`` (scipy-shaped signature).

    Beyond-reference solver (the reference ships cg/gmres only,
    ``linalg.py:465-668``): handles non-symmetric systems without
    GMRES's restart memory, entirely jitted like ``cg``.
    """
    A_op = make_linear_operator(A)
    b = jnp.asarray(b)
    if b.ndim == 2 and b.shape[1] == 1:
        b = b.reshape(-1)
    b = _promote_rhs(b, A_op)
    assert b.ndim == 1
    assert len(A_op.shape) == 2 and A_op.shape[0] == A_op.shape[1]
    n = b.shape[0]
    bnrm2 = float(jnp.linalg.norm(b))
    atol, _ = _get_atol_rtol(bnrm2, tol, atol, rtol)
    if maxiter is None:
        maxiter = n * 10
    M_op = (
        IdentityOperator(A_op.shape, dtype=A_op.dtype)
        if M is None
        else make_linear_operator(M)
    )
    x0_arr = (jnp.zeros(n, dtype=b.dtype) if x0 is None
              else jnp.asarray(x0, dtype=b.dtype).reshape(-1))
    _obs.inc("op.bicgstab")
    if callback is None:
        with _lat.timer("lat.bicgstab.solve."
                        + _lat.shape_bucket(n)), \
                _obs.span("bicgstab", n=n, maxiter=int(maxiter)) as sp:
            xs, iters = _bicgstab_loop(
                A_op.matvec, M_op.matvec, b, x0_arr, atol, int(maxiter),
                int(conv_test_iters),
            )
            if sp is not None:
                sp.set(iters=int(iters))
        return xs, iters
    # Callback path: step the SAME state->state iteration (shadow
    # residual and direction state carried across steps) Python-side so
    # user code observes every iterate; r lives in the state, so the
    # convergence check costs no extra matvec.
    body = maybe_jit(_bicgstab_body(A_op.matvec, M_op.matvec,
                                  conv_test_iters=1))
    state = _bicgstab_state0(A_op.matvec, b, x0_arr, atol, int(maxiter))
    iters = 0
    while iters < maxiter:
        state = body(state)
        iters = int(state[8])
        callback(state[0])
        if bool(state[9]):  # done flag: ||r|| < atol at the cadence
            break
    return state[0], iters


def norm(A, ord=None, axis=None):
    """Sparse matrix/vector norms (scipy.sparse.linalg.norm surface).

    Matrix norms (``axis=None``): Frobenius (default/'fro'), 1 /
    -1 (max/min absolute column sum), inf / -inf (max/min absolute row
    sum), 2 (spectral — delegated to scipy on host, it needs an SVD).
    ``axis=0``/``1`` give per-column/per-row vector norms (ord None/2 =
    Euclidean, 1 = abs sum, inf = abs max, -inf = abs min including
    implicit zeros, 0 = count of nonzeros), returned as numpy arrays
    (scipy returns numpy).  Computed on device from the stored values
    (duplicates canonicalized first).
    """
    from .utils import is_sparse_matrix

    if not is_sparse_matrix(A):
        raise TypeError("input is not a sparse matrix")
    A = A.tocsr() if A.format != "csr" else A
    if A.shape[0] == 0 or A.shape[1] == 0:
        raise ValueError("zero-size array to reduction operation")
    if A.nnz and not A.has_canonical_format:
        A.sum_duplicates()

    def absA():
        return A._with_data(jnp.abs(A.data))

    if axis is None:
        if ord in (None, "fro", "f"):
            return float(jnp.sqrt(jnp.sum(jnp.abs(A.data) ** 2)))
        if ord == 1:
            return float(jnp.max(absA().sum(axis=0)))
        if ord == -1:
            return float(jnp.min(absA().sum(axis=0)))
        if ord in (np.inf, float("inf")):
            return float(jnp.max(absA().sum(axis=1)))
        if ord in (-np.inf, float("-inf")):
            return float(jnp.min(absA().sum(axis=1)))
        if ord == 2:
            # Spectral norm needs an SVD; scipy computes it on host.
            import scipy.sparse.linalg as _ssl

            return float(_ssl.norm(A.toscipy(), ord=2))
        raise ValueError(f"Invalid norm order {ord!r} for matrices")

    if axis not in (0, 1, -1, -2):
        raise ValueError(f"invalid axis {axis}")
    axis = axis % 2
    if ord in (None, 2):
        sq = A._with_data(A.data * jnp.conj(A.data))
        return np.asarray(jnp.sqrt(jnp.real(sq.sum(axis=axis))))
    if ord == 1:
        return np.asarray(absA().sum(axis=axis))
    if ord in (np.inf, float("inf")):
        return np.asarray(absA().max(axis=axis))
    if ord in (-np.inf, float("-inf")):
        # Min absolute value per row/column, implicit-zero aware: any
        # row/column with fewer stored entries than its length has an
        # implicit zero, so its min is 0 (scipy semantics via todense).
        counts = np.asarray(A.getnnz(axis=axis))
        # Reducing along ``axis`` spans shape[axis] elements per slice
        # (axis=1: each row has ncols entries).
        full = A.shape[axis]
        m = np.asarray(absA().min(axis=axis))
        return np.where(counts < full, np.minimum(m, 0.0), m)
    if ord == 0:
        # Count of explicit nonzero *values* (scipy counts (x != 0)).
        nz = A._with_data(
            (A.data != 0).astype(jnp.result_type(A.dtype, jnp.float32))
        )
        return np.asarray(nz.sum(axis=axis))
    raise ValueError(f"Invalid norm order {ord!r} for vectors")


# Device-native eigensolvers and extra Krylov solvers (module
# attributes take priority over the __getattr__ fallback below, so
# these shadow the host-scipy versions).
from .eigen import eigs, eigsh, lobpcg, svds  # noqa: E402
from .expm import expm_multiply  # noqa: E402
from .krylov_extra import (differentiable_solve, lsmr, lsqr,  # noqa: E402
                           minres)
from .precond import block_jacobi, jacobi  # noqa: E402


def __getattr__(name):
    """scipy.sparse.linalg fallback for names without a native
    implementation (spsolve, splu, expm, tfqmr, ...): host-side
    scipy with this package's arrays converted at the boundary.  The
    reference offers no fallback here at all (its linalg is cg/gmres
    only); a drop-in replacement must not strand the rest of a user's
    solver code."""
    import scipy.sparse.linalg as _ssl

    from .coverage import scipy_fallback

    try:
        value = getattr(_ssl, name)
    except AttributeError:
        raise AttributeError(
            f"module 'legate_sparse_tpu.linalg' has no attribute {name!r}"
        ) from None
    if callable(value) and not isinstance(value, type):
        value = scipy_fallback(value, f"linalg.{name}")
    globals()[name] = value   # cache: stable identity, one wrapper
    return value
