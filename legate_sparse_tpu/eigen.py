# Copyright 2026.
# SPDX-License-Identifier: Apache-2.0
"""Device-native sparse eigensolvers: ``eigs``, ``eigsh``, ``lobpcg``,
``svds``.

The reference's linalg surface stops at cg/gmres (its ``linalg.py`` has
no eigensolvers); this package's scipy-compatibility layer previously
served the eigensolver names through host scipy at the module
boundary.  These are the native TPU paths for the common cases:

- ``eigs``: non-symmetric restarted Arnoldi — the full Hessenberg
  recurrence (MGS applied twice) as one jitted ``lax.scan``, real
  arithmetic for real operators; only the small (m, m) ``eig`` runs on
  host.
- ``eigsh``: m-step Lanczos with full reorthogonalization.  The matvec
  chain runs as one jitted ``lax.scan`` on device (SpMV is the hot op);
  only the m x m tridiagonal eigenproblem is solved on host (O(m^2)
  scalar work, m ~ tens — MXU-irrelevant by design).
- ``lobpcg``: blocked Rayleigh-Ritz iteration via
  ``jax.experimental.sparse.linalg.lobpcg_standard`` (all block matmuls
  and the 3k x 3k dense eigensolves stay on device).
- ``svds``: Lanczos on the Gram operator ``v -> A^T (A v)`` (never
  materializes A^T A — two SpMVs per step); left vectors recovered as
  ``U = A V / s``.

Shift-invert ``sigma`` runs NATIVELY (VERDICT r4 #6): the inner
``(A - sigma I)^{-1} v`` apply is an inexact Krylov solve — the
package's jitted MINRES while_loop for symmetric/Hermitian operators
(indefinite-safe), BiCGSTAB for general ones — nested inside the same
Lanczos/Arnoldi ``lax.scan``, so the whole outer-inner iteration
compiles to ONE device program (where scipy/ARPACK factorizes with
``splu`` — a sequential host path with no TPU analog, this is the
device-native rendition).  Complex-Hermitian ``lobpcg`` likewise runs
through the native Lanczos machinery (jax's ``lobpcg_standard`` builds
mixed real/complex while_loop carries on complex operands).

``which='SM'`` (eigsh and eigs) also runs natively — shift-invert at
sigma=0 — with a probe solve that detects a singular/ill-conditioned
operator up front and falls back to host ARPACK's direct mode (an
inexact inverse would otherwise silently drop null-space eigenvalues).

Generalized symmetric pencils run natively too: ``eigsh(A, M=M)`` and
``lobpcg(A, X, B=B)`` (M/B SPD) use an M-inner-product Lanczos whose
basis recurrence, inner ``M^{-1}`` CG solves and
M-reorthogonalization compile as one ``lax.scan`` program
(``_lanczos_general`` — ARPACK mode 2's device rendition), guarded by
an M-solve probe and a pencil-residual acceptance test.

``svds(which='SM')`` runs the same shift-invert-at-0 machinery on the
Gram operator, and the ``buckling``/``cayley`` shift-invert modes
(ARPACK 4/5) run through the same B-inner Lanczos with their own
inner-product matrices and back-transforms.  Generalized
non-symmetric ``eigs(M=...)`` — with or without sigma — runs Arnoldi
on ``M^{-1} A`` / ``(A - sigma M)^{-1} M`` with the same inner-solve
and guard machinery.

Remaining host-fallback corners: preconditioned/constrained lobpcg
and complex lobpcg past 32k rows.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["eigs", "eigsh", "lobpcg", "svds"]


def _operator_parts(A):
    """(matvec, n_rows, n_cols, dtype) for a sparse array, dense array,
    LinearOperator, or scipy sparse operand."""
    from .linalg import LinearOperator, make_linear_operator

    if isinstance(A, LinearOperator):
        op = A
    else:
        op = make_linear_operator(A)
    m, n = op.shape
    dtype = op.dtype
    if dtype is None:
        op._init_dtype()
        dtype = op.dtype
    return op.matvec, int(m), int(n), np.dtype(dtype)


def _host_fallback(name):
    import scipy.sparse.linalg as _ssl

    from .coverage import scipy_fallback

    return scipy_fallback(getattr(_ssl, name), f"linalg.{name}")


def _complex_matvec(matvec, dtype, cdtype):
    """Complex basis over a REAL operator: two real matvecs per apply
    (shared by ``eigs``'s complex-start case and the complex-shift
    shift-invert path)."""

    def mv(x):
        return (matvec(jnp.real(x).astype(dtype)).astype(cdtype)
                + 1j * matvec(jnp.imag(x).astype(dtype)).astype(cdtype))

    return mv


def _restart_direction(V, key0, j, n, rdtype, dtype, mask=None):
    """Fresh random direction orthogonal to the rows of V — the shared
    breakdown restart for the Lanczos and Arnoldi scans (an invariant
    subspace was found; the zero vector would fabricate spectrum)."""
    eps = jnp.finfo(rdtype).eps
    fresh = jax.random.normal(jax.random.fold_in(key0, j), (n,),
                              rdtype).astype(dtype)
    if mask is not None:
        fresh = fresh * mask
    for _ in range(2):
        fresh = fresh - V.T @ (jnp.conj(V) @ fresh)
    return fresh / jnp.maximum(jnp.linalg.norm(fresh), eps)


def _outer_atol(tol, rdtype):
    """Default convergence tolerance (single source for the escalation
    drivers AND the shift-invert inner-solve sizing)."""
    return float(tol) if tol else float(np.finfo(rdtype).eps ** 0.5)


def _validate_be_k(which, k):
    """scipy/ARPACK parity shared by eigsh and dist_eigsh: NEV=1 with
    BE is info=-13; returning a single high-end value would silently
    alias which='LA'."""
    if which == "BE" and k < 2:
        from scipy.sparse.linalg import ArpackError

        raise ArpackError(
            -13, {-13: "NEV and WHICH = 'BE' are incompatible."})


def _require_real_sigma(sigma):
    """scipy parity: float(sigma) raises on ANY complex (even with a
    zero imaginary part) — a Hermitian spectrum is real."""
    if np.iscomplexobj(sigma):
        raise TypeError(
            "eigsh sigma must be a real number, not complex")


def _escalation_params(tol, rdtype, ncv, k, rank, maxiter,
                       min_extra: int = 1):
    """Shared host-side escalation knobs for the eigsh/eigs drivers:
    (atol, first subspace size m, retry count)."""
    atol = _outer_atol(tol, rdtype)
    m = int(ncv) if ncv is not None else min(rank, max(2 * k + 1, 20))
    m = min(max(m, k + min_extra), rank)
    tries = max(int(maxiter) if maxiter is not None else 6, 1)
    return atol, m, tries


def _require_converged(resid, atol, scale, m, cap, w_k, X=None):
    """scipy parity on escalation exhaustion: raise
    ``ArpackNoConvergence`` (carrying the converged subset) instead of
    silently returning unconverged Ritz pairs.  ``m >= cap`` means the
    Krylov space is the whole (masked) space — exact up to roundoff,
    never an error."""
    ok = resid <= atol * scale
    if bool(np.all(ok)) or m >= cap:
        return
    from scipy.sparse.linalg import ArpackNoConvergence

    raise ArpackNoConvergence(
        f"ARPACK-style error: no convergence "
        f"({int(ok.sum())}/{ok.size} eigenvalues converged; "
        f"subspace m={m}, cap={cap})",
        np.asarray(w_k)[ok],
        (np.asarray(X)[:, ok] if X is not None
         else np.empty((0, int(ok.sum())))),
    )


# ----------------------------------------------------- shift-invert inner


def _shift_invert_op(matvec, sigma, dtype, n, outer_atol, sym: bool):
    """Jax-traceable ``v -> (A - sigma I)^{-1} v`` via an inexact inner
    Krylov solve (MINRES for symmetric/Hermitian — A - sigma I is
    indefinite for interior sigma; BiCGSTAB for general operators).

    The returned closure nests inside the outer Lanczos/Arnoldi
    ``lax.scan``, so outer+inner compile to one device program.  The
    operands fed to it by the outer recurrences are unit-norm, so a
    fixed absolute inner tolerance (two digits tighter than the outer
    Ritz tolerance, floored near eps) bounds the backward error of the
    inexact apply below the outer convergence test's resolution.
    """
    from .krylov_extra import _minres_loop
    from .linalg import _bicgstab_loop

    rdtype = jnp.finfo(jnp.dtype(dtype)).dtype
    inner_atol, inner_maxiter = _inner_solver_params(outer_atol, rdtype,
                                                     n)
    shift = jnp.asarray(sigma, dtype=dtype)
    ident = lambda r: r  # noqa: E731

    if sym:
        def solve(v):
            v = jnp.asarray(v, dtype=dtype)
            x, _ = _minres_loop(matvec, ident, v, jnp.zeros_like(v),
                                shift, inner_atol, inner_maxiter, 10)
            return x
    else:
        def shifted(x):
            return matvec(x) - shift * x

        def solve(v):
            v = jnp.asarray(v, dtype=dtype)
            x, _ = _bicgstab_loop(shifted, ident, v, jnp.zeros_like(v),
                                  inner_atol, inner_maxiter, 10)
            return x

    return solve, inner_atol


def _probe_inverse(matvec, solve, sigma, dtype, n, inner_atol, name,
                   mask=None):
    """One explicit (A - sigma I)x = v solve with a TRUE residual check
    before any Lanczos/Arnoldi runs.

    This is the honesty gate a Krylov inner solve owes the caller that
    an exact splu factorization does not need: on a SINGULAR (A - sigma
    I) the iterative solve converges to a pseudo-inverse apply whose
    Ritz pairs are genuine eigenpairs of A — they pass every residual
    test — while silently MISSING the null-space eigenvalue nearest
    sigma (found empirically: eigsh(diag(0..n), which='SM') returned
    [1, 2], not [0, 1]).  A stagnated probe residual is the observable
    signature; raise ``ArpackNoConvergence`` so sigma callers surface
    it and the SM route falls back to host ARPACK's direct mode."""
    shift = jnp.asarray(sigma, dtype=dtype)
    _probe_apply(lambda x: matvec(x) - shift * x, solve, n, dtype,
                 inner_atol, f"shift-invert {name}", mask=mask)


def _check_original_residuals(matvec, lam, X, atol, name):
    """Post-hoc guard for the inexact shift-invert paths: judge the
    returned Ritz pairs in the ORIGINAL operator's metric (k matvecs).
    A stagnated inner solve (sigma pathologically close to an
    eigenvalue for the iterative inner tolerance) corrupts OP silently;
    the outer recurrence then converges *on the corrupted operator*, so
    this original-spectrum check is the only honest acceptance test —
    scipy/ARPACK's splu factorization is exact and needs none.  Raises
    ``ArpackNoConvergence`` (carrying the converged subset) like scipy
    does on its own convergence failures."""
    Xj = jnp.asarray(X)
    AX = np.asarray(jax.vmap(matvec, in_axes=1, out_axes=1)(Xj))
    resid = np.linalg.norm(AX - np.asarray(X) * lam[None, :], axis=0)
    scale = np.maximum(np.abs(lam), 1.0)
    # Slack x50: the inner solve is inexact by design (inner_atol is
    # 1e-2 * atol); this bound rejects stagnation (errors orders of
    # magnitude out), not honest last-digit noise.
    ok = resid <= 50.0 * atol * scale
    if bool(np.all(ok)):
        return
    from scipy.sparse.linalg import ArpackNoConvergence

    raise ArpackNoConvergence(
        f"shift-invert {name}: inexact inner solve did not reach the "
        f"requested accuracy ({int(ok.sum())}/{ok.size} pairs pass the "
        f"original-spectrum residual test; sigma may be too close to "
        f"an eigenvalue for the iterative inner solver — widen sigma "
        f"or loosen tol)",
        np.asarray(lam)[ok], np.asarray(X)[:, ok],
    )


# ---------------------------------------------------------------- Lanczos


def _lanczos_general(matvec_a, matvec_m, solve_m, v0, m: int,
                     si: bool = False, rhs_fn=None):
    """m-step B-inner-product Lanczos for the generalized symmetric
    problem — ARPACK modes 2-5 re-designed for the device: the basis
    recurrence, the inner Krylov solves, and the full
    B-reorthogonalization all live in ONE ``lax.scan`` (one compiled
    program, no per-step dispatch).  ``matvec_m`` is the inner-product
    matrix B (M for modes 2/3/5, A for buckling).

    ``si=False`` (mode 2): the operator is ``M^{-1} A`` and ``solve_m``
    solves with M.  ``si=True`` (shift-invert family): the operator is
    ``(A - sigma M)^{-1} rhs(v)`` where ``rhs_fn`` defaults to the
    inner-product matvec (modes 3/4) or is ``(A + sigma M) v`` for
    cayley (mode 5); T then approximates the mode's transformed
    spectrum ``nu``.

    Returns (V, alphas, betas): V has B-orthonormal rows
    (``V B V^H = I``) and T = tridiag(betas[1:], alphas, betas[1:])
    holds the Ritz approximation of the operator's spectrum in the
    B-inner product.
    """
    n = v0.shape[0]
    dtype = v0.dtype
    rdtype = jnp.finfo(dtype).dtype
    eps = jnp.finfo(rdtype).eps
    key0 = jax.random.PRNGKey(23)

    def m_reorth(V, w):
        # w -= V^T <V, w>_M, applied twice (classical GS, Parlett).
        for _ in range(2):
            q = matvec_m(w)
            w = w - V.T @ (jnp.conj(V) @ q)
        return w

    def m_normalize(w):
        nrm = jnp.sqrt(jnp.maximum(
            jnp.real(jnp.vdot(w, matvec_m(w))), 0)).astype(rdtype)
        return w / jnp.where(nrm == 0, 1.0, nrm).astype(dtype), nrm

    def step(carry, j):
        V, v, beta, v_prev = carry
        if si:
            mv = matvec_m(v)
            rhs = mv if rhs_fn is None else rhs_fn(v)
            w = solve_m(rhs)                  # (A - sigma M)^{-1} rhs
            # <v, OP v>_B = (B v)^H w (B Hermitian).
            alpha = jnp.real(jnp.vdot(mv, w)).astype(dtype)
        else:
            av = matvec_a(v)
            w = solve_m(av)                   # M^{-1} A v
            alpha = jnp.real(jnp.vdot(v, av)).astype(dtype)  # <v, Av>
        w = w - alpha * v - beta * v_prev
        V = V.at[j].set(v)
        w = m_reorth(V, w)
        w, beta_next = m_normalize(w)
        broke = beta_next <= 100 * eps * jnp.maximum(
            jnp.abs(jnp.real(alpha)), 1.0)
        fresh = jax.random.normal(
            jax.random.fold_in(key0, j), (n,), rdtype).astype(dtype)
        fresh = m_reorth(V, fresh)
        fresh, _ = m_normalize(fresh)
        beta_out = jnp.where(broke, jnp.zeros((), rdtype), beta_next)
        v_next = jnp.where(broke, fresh, w)
        return (V, v_next, beta_out.astype(dtype), v), (
            alpha, beta_out.astype(dtype))

    V0 = jnp.zeros((m, n), dtype=dtype)
    (V, _, _, _), (alphas, betas) = jax.lax.scan(
        step, (V0, v0, jnp.zeros((), dtype), jnp.zeros_like(v0)),
        jnp.arange(m, dtype=jnp.int32))
    return V, alphas, betas


def _inner_solver_params(outer_atol: float, rdtype, n: int):
    """Shared inner-Krylov sizing for every inexact-inverse path
    (shift-invert, generalized pencil): (absolute atol for a UNIT-NORM
    rhs, iteration cap)."""
    eps = float(np.finfo(np.dtype(rdtype)).eps)
    return (max(1e-2 * float(outer_atol), 50.0 * eps),
            int(min(10 * n + 20, 100_000)))


def _select_sym_ritz(w, y, k: int, which: str):
    """Shared LA/SA/LM/SM/BE Ritz selection for the symmetric drivers
    (ascending-eigenvalue output order, scipy convention).  Under
    shift-invert the caller passes the TRANSFORMED spectrum, so SM
    there means smallest |nu| = farthest from sigma — exactly ARPACK's
    semantics."""
    if which == "LA":
        sel = np.argsort(w)[-k:]
    elif which == "SA":
        sel = np.argsort(w)[:k]
    elif which == "SM":
        sel = np.argsort(np.abs(w))[:k]
    elif which == "BE":
        # scipy: k/2 from each end, the extra one from the HIGH end.
        lo = k // 2
        order = np.argsort(w)
        sel = np.concatenate([order[:lo], order[lo - k:]])
    else:  # LM
        sel = np.argsort(np.abs(w))[-k:]
    sel = sel[np.argsort(w[sel])]
    return w[sel], y[:, sel]


def _normalized_rhs_solver(solve_unit):
    """Wrap a unit-rhs inner solver so its absolute tolerance applies
    RELATIVE to each right-hand side's norm.  The generalized apply's
    rhs is A v or M v with norm ~||A||/||M|| — NOT the unit norm of the
    standard shift-invert recurrences' operands — so an absolute inner
    tolerance would silently lose digits on small-norm pencils (found
    by review with a 1e-6-scaled operator repro) and never be reachable
    on large-norm ones."""

    def solve(b):
        nrm = jnp.linalg.norm(b)
        safe = jnp.where(nrm == 0, 1.0, nrm).astype(b.dtype)
        return solve_unit(b / safe) * safe

    return solve


def _probe_apply(apply_fn, solve, n, dtype, inner_atol, what,
                 mask=None):
    """One explicit solve of ``apply_fn(x) = v`` with a TRUE residual
    check before any recurrence runs — the honesty gate every inexact
    inner solve owes its caller (see ``_probe_inverse``): a stagnating
    probe means the operator is singular or too ill-conditioned for
    the iterative inner solver, in which case silent pseudo-inverse
    behavior would drop eigenvalues without failing any residual test.
    Returns the probe RNG so callers draw consistent start vectors.
    ``mask`` restricts the probe to the valid subspace (distributed
    padded operators: the padding block of A - sigma*I is singular at
    sigma=0 by construction, which must not trip the gate)."""
    rng = np.random.default_rng(20260801)
    v = jnp.asarray(rng.standard_normal(n), dtype=dtype)
    if mask is not None:
        v = v * mask
    v = v / jnp.linalg.norm(v)
    x = solve(v)
    res = float(jnp.linalg.norm(apply_fn(x) - v))
    if not np.isfinite(res) or res > 100.0 * inner_atol:
        from scipy.sparse.linalg import ArpackNoConvergence

        raise ArpackNoConvergence(
            f"{what}: inner solve stagnated at residual {res:.2e} "
            f"(target {inner_atol:.2e}) — operator singular or too "
            f"ill-conditioned for the iterative inner solver",
            np.empty(0), np.empty((n, 0)))
    return rng


def _m_normalized_start(v0, matvec_m, dtype, n, rng):
    """Start vector for the M-inner recurrences, M-normalized."""
    if v0 is None:
        v0 = rng.standard_normal(n)
    v0 = jnp.asarray(v0, dtype=dtype)
    mnrm = float(np.sqrt(max(
        float(jnp.real(jnp.vdot(v0, matvec_m(v0)))), 1e-300)))
    return v0 / v0.dtype.type(mnrm)


def _general_lanczos_drive(matvec_a, matvec_m, solve, si, v0, k, which,
                           ncv, maxiter, tol, rank, rdtype, dtype,
                           rhs_fn=None):
    """Shared escalation loop for the generalized modes 2-5: returns
    ``(w_k, X, resid, atol, scale, m)`` (w_k in the operator's own
    spectrum — pencil eigenvalues for mode 2, the mode's transformed
    nu otherwise)."""
    import scipy.linalg as _sl

    from .linalg import maybe_jit

    lanczos = maybe_jit(_lanczos_general, static_argnums=(0, 1, 2),
                        static_argnames=("m", "si", "rhs_fn"))
    atol, m, tries = _escalation_params(tol, rdtype, ncv, k, rank,
                                        maxiter)
    for try_i in range(tries):
        if try_i:
            m = min(rank, 2 * m)
        V, alphas, betas = lanczos(matvec_a, matvec_m, solve, v0, m=m,
                                   si=si, rhs_fn=rhs_fn)
        a = np.real(np.asarray(alphas)).astype(np.float64)
        b_all = np.real(np.asarray(betas)).astype(np.float64)
        w, y = _sl.eigh_tridiagonal(a, b_all[:-1])
        w_k, y_k = _select_sym_ritz(w, y, k, which)
        resid = np.abs(b_all[-1]) * np.abs(y_k[-1, :])
        # Relative scale with a SPECTRUM-magnitude floor (not the
        # absolute 1.0 of the standard driver): a pencil scaled by
        # 1e-6 must get 1e-6-scaled acceptance, else inexact digits
        # pass silently.
        floor = max(float(np.max(np.abs(w))), np.finfo(rdtype).tiny)
        scale = np.maximum(np.abs(w_k), floor)
        if np.all(resid <= atol * scale) or m >= rank:
            break
    X = np.asarray(jnp.einsum(
        "mn,mk->nk", V, jnp.asarray(y_k, dtype=dtype)))
    return w_k, X, resid, atol, scale, m


def _eigsh_generalized(matvec_a, matvec_m, n, dtype, k, which, v0, ncv,
                       maxiter, tol, return_eigenvectors,
                       max_rank=None):
    """Native generalized ``eigsh(A, M=M)`` (ARPACK mode 2): M-inner
    Lanczos on ``M^{-1} A`` with an inexact jitted inner CG solve.
    ``max_rank`` bounds the escalated basis (the lobpcg-B route passes
    its O(max(8k,128)) memory cap)."""
    rdtype = np.dtype(np.finfo(dtype).dtype)
    atol_outer = _outer_atol(tol, rdtype)
    inner_atol, inner_maxiter = _inner_solver_params(atol_outer, rdtype,
                                                    n)
    from .linalg import _cg_loop

    ident = lambda r: r  # noqa: E731
    solve_m = _normalized_rhs_solver(
        lambda b: _cg_loop(matvec_m, ident, b, jnp.zeros_like(b),
                           inner_atol, inner_maxiter, 10)[0])
    # Probe: M must be solvable to the inner tolerance (SPD and
    # nonsingular), else the whole pencil transform is untrustworthy.
    rng = _probe_apply(matvec_m, solve_m, n, dtype, inner_atol,
                       "generalized eigsh")
    v0 = _m_normalized_start(v0, matvec_m, dtype, n, rng)
    rank = int(max_rank) if max_rank is not None else n
    w_k, X, resid, atol, scale, m = _general_lanczos_drive(
        matvec_a, matvec_m, solve_m, False, v0, k, which, ncv, maxiter,
        tol, rank, rdtype, dtype)
    w_k = w_k.astype(rdtype)
    _pencil_residual_guard(matvec_a, matvec_m, w_k, X, atol_outer,
                           rdtype)
    _require_converged(resid, atol, scale, m, rank, w_k, X)
    if not return_eigenvectors:
        return w_k
    return w_k, X


def _pencil_residual_guard(matvec_a, matvec_m, w_k, X, atol_outer,
                           rdtype):
    """Original-PENCIL residual guard (the inexact-inner honesty test,
    shared by modes 2 and 3): ``||A x - lambda M x||`` judged RELATIVE
    to the pencil's own magnitude per pair."""
    AX = np.asarray(jax.vmap(matvec_a, in_axes=1, out_axes=1)(
        jnp.asarray(X)))
    MX = np.asarray(jax.vmap(matvec_m, in_axes=1, out_axes=1)(
        jnp.asarray(X)))
    res_p = np.linalg.norm(AX - MX * w_k[None, :], axis=0)
    denom = np.maximum.reduce([
        np.linalg.norm(AX, axis=0),
        np.abs(w_k) * np.linalg.norm(MX, axis=0),
        np.full(res_p.shape, np.finfo(rdtype).tiny),
    ])
    ok = res_p / denom <= 50.0 * atol_outer
    if not bool(np.all(ok)):
        from scipy.sparse.linalg import ArpackNoConvergence

        raise ArpackNoConvergence(
            f"generalized eigsh: {int(ok.sum())}/{ok.size} pairs pass "
            f"the pencil residual test", w_k[ok], X[:, ok])


def _eigsh_generalized_si(matvec_a, matvec_m, sigma: float, n, dtype,
                          k, which, v0, ncv, maxiter, tol,
                          return_eigenvectors, mode: str = "normal"):
    """Native generalized shift-invert (ARPACK modes 3/4/5):
    B-inner-product Lanczos on the mode's operator with an inexact
    jitted MINRES inner solve of the (symmetric indefinite) shifted
    pencil ``A - sigma M``.  ``which`` applies to the transformed
    spectrum ``nu`` (scipy semantics); results transform back and
    return ascending.

    ========  =========================  ==========  ====================
    mode      operator                   B (inner)   back-transform
    ========  =========================  ==========  ====================
    normal    (A - sM)^{-1} M            M           s + 1/nu
    buckling  (A - sM)^{-1} A            A           s*nu / (nu - 1)
    cayley    (A - sM)^{-1} (A + sM)     M           s*(nu+1) / (nu-1)
    ========  =========================  ==========  ====================
    """
    from .krylov_extra import _minres_loop

    rdtype = np.dtype(np.finfo(dtype).dtype)
    atol_outer = _outer_atol(tol, rdtype)
    inner_atol, inner_maxiter = _inner_solver_params(atol_outer, rdtype,
                                                    n)
    ident = lambda r: r  # noqa: E731
    sig = jnp.asarray(sigma, dtype=dtype)

    def shifted(x):
        return matvec_a(x) - sig * matvec_m(x)

    solve_si = _normalized_rhs_solver(
        lambda b: _minres_loop(shifted, ident, b, jnp.zeros_like(b),
                               jnp.zeros((), b.dtype), inner_atol,
                               inner_maxiter, 10)[0])
    # Probe the shifted solve (sigma on an eigenvalue of the pencil /
    # hopeless conditioning -> fall back, never silently corrupt).
    rng = _probe_apply(shifted, solve_si, n, dtype, inner_atol,
                       "generalized shift-invert")
    # Per-mode inner-product matrix, rhs, and back-transform.
    tiny = np.finfo(rdtype).tiny
    if mode == "buckling":
        inner_mv = matvec_a           # B = A (A must be positive)
        rhs_fn = None

        def back(nu):
            d = np.where(np.abs(nu - 1.0) < tiny, tiny, nu - 1.0)
            return (float(sigma) * nu / d).astype(rdtype)
    elif mode == "cayley":
        inner_mv = matvec_m

        def rhs_fn(v):
            return matvec_a(v) + sig * matvec_m(v)

        def back(nu):
            d = np.where(np.abs(nu - 1.0) < tiny, tiny, nu - 1.0)
            return (float(sigma) * (nu + 1.0) / d).astype(rdtype)
    else:
        inner_mv = matvec_m
        rhs_fn = None

        def back(nu):
            nz = np.where(nu == 0, tiny, nu)
            return (float(sigma) + 1.0 / nz).astype(rdtype)

    v0 = _m_normalized_start(v0, inner_mv, dtype, n, rng)
    w_nu, X, resid, atol, scale, m = _general_lanczos_drive(
        matvec_a, inner_mv, solve_si, True, v0, k, which, ncv, maxiter,
        tol, n, rdtype, dtype, rhs_fn=rhs_fn)
    lam = back(w_nu)
    # Unconverged Ritz pairs raise (scipy parity) — BEFORE reordering,
    # while resid/scale still align with lam's columns.
    _require_converged(resid, atol, scale, m, n, lam, X)
    order = np.argsort(lam)
    lam, X = lam[order], X[:, order]
    _pencil_residual_guard(matvec_a, matvec_m, lam, X, atol_outer,
                           rdtype)
    if not return_eigenvectors:
        return lam
    return lam, X


def _lanczos(matvec, v0, mask, m: int):
    """m-step Lanczos with full (twice-applied) reorthogonalization.

    Returns (V, alphas, betas): V is (m, n) with orthonormal rows,
    T = tridiag(betas[1:], alphas, betas[1:]).  Static shapes; the whole
    recurrence is one ``lax.scan`` so the SpMV chain compiles to a
    single device program (no per-step host dispatch).
    """
    n = v0.shape[0]
    dtype = v0.dtype
    rdtype = jnp.finfo(dtype).dtype
    eps = jnp.finfo(rdtype).eps
    key0 = jax.random.PRNGKey(7)

    def step(carry, j):
        V, v, beta, v_prev, alphas, betas = carry
        w = matvec(v)
        alpha = jnp.real(jnp.vdot(v, w)).astype(dtype)
        w = w - alpha * v - beta * v_prev
        # Full reorthogonalization, applied twice (classical
        # Gram-Schmidt is unstable once; twice is enough — Parlett).
        # Rows j+1.. of V are zero so they contribute nothing.
        V = V.at[j].set(v)
        for _ in range(2):
            w = w - V.T @ (jnp.conj(V) @ w)
        beta_next = jnp.linalg.norm(w).astype(dtype)
        # Breakdown (invariant subspace found): continue with a fresh
        # random direction orthogonal to V — T decouples at the zero
        # off-diagonal and its spectrum stays a valid union, instead of
        # the zero vector padding T with fabricated zero eigenvalues.
        broke = jnp.real(beta_next) <= 100 * eps * jnp.maximum(
            jnp.abs(jnp.real(alpha)), 1.0)
        # Restart inside the valid subspace only (padded/masked entries
        # must stay exactly zero — distributed operators carry inert
        # padding rows).
        fresh = _restart_direction(V, key0, j, n, rdtype, dtype,
                                   mask=mask)
        beta_next = jnp.where(broke, jnp.zeros((), dtype), beta_next)
        v_next = jnp.where(
            broke, fresh,
            w / jnp.where(beta_next == 0, 1.0, beta_next))
        # alphas/betas accumulate in the CARRY at our int32 j rather
        # than as stacked scan outputs: with x64 on, sharding
        # propagation shards the scan-ys stacking buffer and its s64
        # loop-counter index trips the spmd partitioner's hlo verifier
        # ("compare s64 vs s32") on the installed jaxlib.
        alphas = alphas.at[j].set(alpha)
        betas = betas.at[j].set(beta_next)
        return (V, v_next, beta_next, v, alphas, betas), None

    V0 = jnp.zeros((m, n), dtype=dtype)
    (V, _, _, _, alphas, betas), _ = jax.lax.scan(
        step, (V0, v0, jnp.zeros((), dtype), jnp.zeros_like(v0),
               jnp.zeros((m,), dtype), jnp.zeros((m,), dtype)),
        jnp.arange(m, dtype=jnp.int32))
    return V, alphas, betas


def _lanczos_eigsh(matvec, n, dtype, k, which, v0, ncv, maxiter, tol,
                   return_eigenvectors, mask=None, max_rank=None):
    import scipy.linalg as _sl

    # The REAL precision of the operand dtype (complex64 -> float32):
    # an itemsize test would hand complex64 float64-grade tolerances.
    rdtype = np.dtype(np.finfo(dtype).dtype)
    if v0 is None:
        rng = np.random.default_rng(0)
        v0 = rng.standard_normal(n)
    # jnp.asarray keeps device (incl. sharded) arrays in place.
    v0 = jnp.asarray(v0, dtype=dtype)
    v0 = v0 / jnp.linalg.norm(v0)

    rank = int(max_rank) if max_rank is not None else n
    # matvec is a closure: static (hashable) so the scan jits around it.
    from .linalg import maybe_jit

    lanczos = maybe_jit(_lanczos, static_argnums=(0,),
                        static_argnames=("m",))

    # Escalate the subspace until the Ritz residuals converge (scipy's
    # implicit restarts analog, kept host-side and simple: each retry
    # doubles m; n caps it).  tol=0 means machine precision (scipy).
    atol, m, tries = _escalation_params(tol, rdtype, ncv, k, rank,
                                        maxiter)
    for try_i in range(tries):
        if try_i:
            m = min(rank, 2 * m)
        # m is only ever doubled right before a run, so the post-loop
        # convergence checks always judge the size actually run.
        V, alphas, betas = lanczos(matvec, v0, mask, m=m)
        a = np.real(np.asarray(alphas)).astype(np.float64)
        b_all = np.real(np.asarray(betas)).astype(np.float64)
        b = b_all[:-1]            # off-diagonal of T
        beta_last = b_all[-1]     # final recurrence beta: residual term
        w, y = _sl.eigh_tridiagonal(a, b)
        # Select k per `which` from the Ritz values (ascending, scipy).
        w_k, y_k = _select_sym_ritz(w, y, k, which)
        # Ritz residual bound: |beta_{m+1} * e_m^T y_i| — the *final*
        # recurrence beta, not T's last off-diagonal.
        resid = np.abs(beta_last) * np.abs(y_k[-1, :])
        scale = np.maximum(np.abs(w_k), 1.0)
        if np.all(resid <= atol * scale) or m >= rank:
            break
    w_k = w_k.astype(rdtype)
    converged = bool(np.all(resid <= atol * scale)) or m >= rank
    if converged and not return_eigenvectors:
        return w_k          # skip forming X entirely
    X = np.asarray(jnp.einsum("mn,mk->nk", V, jnp.asarray(y_k, dtype=dtype)))
    _require_converged(resid, atol, scale, m, rank, w_k, X)
    if not return_eigenvectors:
        return w_k
    return w_k, X


def eigsh(A, k=6, M=None, sigma=None, which="LM", v0=None, ncv=None,
          maxiter=None, tol=0, return_eigenvectors=True, **kwargs):
    """k eigenpairs of a symmetric/Hermitian operator (scipy
    ``eigsh``).

    Capability split: the standard problem with ``which`` in
    {LM, LA, SA} runs the NATIVE device Lanczos below; shift-invert
    ``sigma`` (mode='normal') also runs natively — Lanczos on
    ``(A - sigma I)^{-1}`` with the inner apply an inexact jitted
    MINRES solve nested in the same scan (``_shift_invert_op``), where
    scipy/ARPACK uses a host ``splu`` factorization.  Per scipy
    semantics ``which`` then refers to the TRANSFORMED eigenvalues
    ``nu = 1/(lambda - sigma)`` (LM = closest to sigma) and results
    transform back via ``lambda = sigma + 1/nu``.  ``which='SM'``
    without sigma routes through the same machinery at sigma=0 (the
    classic trick — scipy documents it as the recommended alternative
    to its slow direct-SM mode), falling back to host ARPACK when the
    inexact inverse cannot converge (e.g. singular A).  Generalized
    pencils ``A x = lambda M x`` (SPD M) run natively too —
    M-inner-product Lanczos with a jitted inner CG for ``M^{-1}``
    (``_eigsh_generalized``) without sigma, and the shift-invert
    family ``mode='normal'/'buckling'/'cayley'`` (ARPACK modes 3/4/5,
    ``_eigsh_generalized_si``) with it — host fallback when an
    inner-solve probe stagnates.  Remaining delegations convert
    operands at the boundary and return scipy's results unchanged."""
    mode = kwargs.pop("mode", "normal")
    native_which = ("LM", "LA", "SA", "BE", "SM")
    si_modes = ("normal", "buckling", "cayley")
    sm_native = which == "SM" and sigma is None and M is None and not kwargs
    gen_native = (M is not None and sigma is None and mode == "normal"
                  and which in native_which and not kwargs)
    gen_si_native = (sigma is not None and mode in si_modes
                     and which in native_which and not kwargs
                     and (M is not None or mode != "normal"))
    if not sm_native and not gen_native and not gen_si_native and (
            M is not None or which not in native_which or kwargs
            or (sigma is not None and mode != "normal")):
        return _host_fallback("eigsh")(
            A, k=k, M=M, sigma=sigma, which=which, v0=v0, ncv=ncv,
            maxiter=maxiter, tol=tol, mode=mode,
            return_eigenvectors=return_eigenvectors, **kwargs)
    matvec, m_rows, n_cols, dtype = _operator_parts(A)
    if m_rows != n_cols:
        raise ValueError("expected square matrix")
    if not (0 < k < n_cols):
        raise ValueError(f"k={k} must satisfy 0 < k < n={n_cols}")
    _validate_be_k(which, k)
    if gen_native or gen_si_native:
        # Generalized pencil A x = lambda M x (M SPD): native B-inner
        # Lanczos — mode 2 (M^{-1} A, inner CG on M) without sigma;
        # modes 3/4/5 (normal/buckling/cayley shift-invert, inner
        # MINRES on the shifted pencil) with it; scipy factorizes on
        # host for all of them.  A stagnating inner-solve probe falls
        # back to host ARPACK.  M=None (buckling/cayley on a standard
        # problem) is the identity.
        from scipy.sparse.linalg import ArpackNoConvergence

        if gen_si_native:
            _require_real_sigma(sigma)
            if mode != "normal" and float(sigma) == 0.0:
                raise ValueError(
                    f"mode={mode!r} requires a nonzero sigma "
                    f"(the transform degenerates at 0)")
        if M is not None:
            mv_m, mr, mc, mdtype = _operator_parts(M)
            if (mr, mc) != (n_cols, n_cols):
                raise ValueError(
                    f"M has shape {(mr, mc)}, "
                    f"expected {(n_cols, n_cols)}")
            pdtype = np.promote_types(dtype, mdtype)
        else:
            mv_m = lambda x: x  # noqa: E731
            pdtype = dtype
        # Separate locals for the SM remap (same idiom as the eigs
        # generalized branch): the ArpackNoConvergence host fallback
        # below must see the CALLER's sigma/which — passing the
        # remapped sigma=0.0 makes scipy splu(A - 0*M), which raises
        # "Factor is exactly singular" for exactly the singular-A case
        # the fallback exists to serve (ADVICE r5 medium).
        use_si, sig, wch = gen_si_native, sigma, which
        if not gen_si_native and which == "SM":
            # Direct smallest-magnitude on a pencil is the hardest
            # Krylov target; serve it as generalized shift-invert at 0
            # (largest of (A - 0*M)^{-1} M = smallest |lambda|), the
            # same remap as the standard SM route.
            use_si, sig, wch = True, 0.0, "LM"
        try:
            if use_si:
                return _eigsh_generalized_si(
                    matvec, mv_m, float(sig), n_cols,
                    np.dtype(pdtype), int(k), wch, v0, ncv, maxiter,
                    tol, return_eigenvectors, mode=mode)
            return _eigsh_generalized(
                matvec, mv_m, n_cols, np.dtype(pdtype), int(k), wch,
                v0, ncv, maxiter, tol, return_eigenvectors)
        except ArpackNoConvergence:
            return _host_fallback("eigsh")(
                A, k=k, M=M, sigma=sigma, which=which, v0=v0, ncv=ncv,
                maxiter=maxiter, tol=tol, mode=mode,
                return_eigenvectors=return_eigenvectors)
    if sm_native:
        # Smallest-magnitude = largest of A^{-1}: shift-invert at 0.
        from scipy.sparse.linalg import ArpackNoConvergence

        try:
            return _eigsh_shift_invert(
                matvec, n_cols, dtype, int(k), 0.0, "LM", v0, ncv,
                maxiter, tol, return_eigenvectors)
        except ArpackNoConvergence:
            # Inexact inverse stagnated (singular / near-singular A):
            # host ARPACK's direct-SM Lanczos handles those.
            return _host_fallback("eigsh")(
                A, k=k, which="SM", v0=v0, ncv=ncv, maxiter=maxiter,
                tol=tol, return_eigenvectors=return_eigenvectors)
    if sigma is None:
        return _lanczos_eigsh(matvec, n_cols, dtype, int(k), which, v0,
                              ncv, maxiter, tol, return_eigenvectors)

    # Native shift-invert: Lanczos on OP = (A - sigma I)^{-1}.  Same
    # ArpackNoConvergence -> host ladder as the SM route above (ADVICE
    # r5 low): a sigma near an eigenvalue stagnates the inexact inner
    # MINRES where scipy's exact splu factorization succeeds — serve
    # those through host ARPACK instead of raising.
    _require_real_sigma(sigma)
    from scipy.sparse.linalg import ArpackNoConvergence

    try:
        return _eigsh_shift_invert(matvec, n_cols, dtype, int(k),
                                   float(sigma), which, v0, ncv,
                                   maxiter, tol, return_eigenvectors)
    except ArpackNoConvergence:
        return _host_fallback("eigsh")(
            A, k=k, sigma=sigma, which=which, v0=v0, ncv=ncv,
            maxiter=maxiter, tol=tol,
            return_eigenvectors=return_eigenvectors)


def _eigsh_shift_invert(matvec, n_cols, dtype, k, sigma, which, v0,
                        ncv, maxiter, tol, return_eigenvectors,
                        mask=None, max_rank=None, name="eigsh",
                        trunc_rows=None):
    """Native shift-invert eigsh body (see ``eigsh``): Lanczos on
    ``OP = (A - sigma I)^{-1}`` with the inexact MINRES inner apply.

    ``mask``/``max_rank``/``trunc_rows`` serve the DISTRIBUTED caller
    (``dist_eigsh``): the probe and Krylov space stay in the valid
    (non-padding) subspace, the Krylov dimension caps at the true row
    count, and every returned/raised eigenvector block is truncated to
    the true rows."""
    rdtype = np.dtype(np.finfo(dtype).dtype)
    atol_outer = _outer_atol(tol, rdtype)
    op, inner_atol = _shift_invert_op(matvec, float(sigma), dtype,
                                      n_cols, atol_outer, sym=True)
    _probe_inverse(matvec, op, float(sigma), dtype, n_cols, inner_atol,
                   name, mask=mask)

    # Always form X: the original-spectrum residual check below is what
    # catches a silently-stagnated INNER solve (sigma too close to an
    # eigenvalue) — the outer Ritz test alone only measures convergence
    # on the possibly-corrupted operator.
    def back_l(nu):
        nz = np.where(nu == 0, np.finfo(rdtype).tiny, nu)
        return (float(sigma) + 1.0 / nz).astype(rdtype)

    def trunc(Xa):
        Xa = np.asarray(Xa)
        return Xa if trunc_rows is None else Xa[:trunc_rows]

    from scipy.sparse.linalg import ArpackNoConvergence

    try:
        w_nu, X = _lanczos_eigsh(op, n_cols, dtype, int(k), which, v0,
                                 ncv, maxiter, tol, True, mask=mask,
                                 max_rank=max_rank)
    except Exception as e:
        if not isinstance(e, ArpackNoConvergence):
            raise
        # The inner escalation raised with TRANSFORMED nu values;
        # re-raise carrying back-transformed lambdas so a caller
        # salvaging e.eigenvalues gets actual eigenvalues (matching
        # the eigs shift-invert path).
        raise ArpackNoConvergence(
            str(e), back_l(np.asarray(e.eigenvalues)),
            trunc(e.eigenvectors),
        ) from None
    # nu = 1/(lambda - sigma): eigenvectors are shared with A.
    lam = back_l(w_nu)
    order = np.argsort(lam)                 # scipy returns ascending
    lam, X = lam[order], X[:, order]
    try:
        _check_original_residuals(matvec, lam, X, atol_outer, name)
    except ArpackNoConvergence as e:
        if trunc_rows is None:
            raise
        raise ArpackNoConvergence(
            str(e), np.asarray(e.eigenvalues), trunc(e.eigenvectors),
        ) from None
    if not return_eigenvectors:
        return lam
    return lam, trunc(X)


# ---------------------------------------------------------------- LOBPCG


def _block_seed(X, dtype):
    """Single Lanczos start vector carrying the WHOLE guess block: a
    fixed-seed random combination of the orthonormalized columns of X.

    Lanczos is a single-vector recurrence, so it cannot consume X as a
    block the way LOBPCG proper does; seeding with ``X[:, 0]`` alone
    (the pre-r6 behavior) silently discarded the remaining columns — a
    first column (near-)orthogonal to a target eigenvector that another
    column carries would only be recovered through breakdown restarts.
    Almost-surely-nonzero weights give the Krylov space overlap with
    every direction the block spans."""
    Xa = np.asarray(X)
    q, _ = np.linalg.qr(Xa.astype(np.promote_types(Xa.dtype, dtype)))
    w = np.random.default_rng(11).standard_normal(q.shape[1])
    return q @ w.astype(q.dtype)


def lobpcg(A, X, B=None, M=None, Y=None, tol=None, maxiter=20,
           largest=True, **kwargs):
    """Locally optimal block PCG eigensolver (scipy ``lobpcg``).

    Standard problem (no B/M/Y): runs fully on device via
    ``jax.experimental.sparse.linalg.lobpcg_standard``; smallest
    eigenvalues come from the negated operator.  Generalized ``B``
    (SPD) runs through the native M-inner Lanczos machinery
    (``_eigsh_generalized``) at lobpcg-class sizes, falling back to
    host scipy when B's inner CG stagnates or past 32k rows;
    preconditioned / constrained forms delegate to host scipy.

    Block-seed semantics of the Lanczos-backed routes (generalized
    ``B`` and complex-Hermitian): the driver is a single-vector Lanczos
    recurrence, not a block iteration, so the initial guess block
    enters as ONE start vector — a fixed random combination of the
    orthonormalized columns of ``X`` (``_block_seed``), which overlaps
    every direction the block spans.  Results match scipy's; per-column
    convergence *rates* of true block LOBPCG do not transfer.

    ``maxiter`` semantics: scipy counts *block iterations* — each one
    is one Rayleigh-Ritz step on the (X, R, P) subspace, and
    ``maxiter=20`` means at most 20 such steps.  The Lanczos-backed
    routes here have no block iteration to count; ``maxiter`` instead
    bounds the **escalation retry count** — how many times the driver
    may widen its Krylov subspace (growing ``ncv`` toward the
    ``max(8k, 128)`` basis cap) and restart after a non-converged
    attempt, clamped to [1, 10].  Consequences: (a) ``maxiter=1`` is
    one full Lanczos solve at the initial subspace width, not one
    Rayleigh-Ritz step — usually *more* work than scipy's first
    iteration; (b) raising ``maxiter`` past 10 buys nothing on these
    routes; (c) iteration-matched comparisons against scipy's
    ``lobpcg`` are not meaningful — compare residual tolerances
    instead.  The ``jax.experimental`` ``lobpcg_standard`` route (real
    standard problems) keeps scipy-style semantics: ``maxiter`` is the
    block-iteration count ``m`` passed straight through.
    """
    if (B is not None and M is None and Y is None and not kwargs
            and np.asarray(X).shape[0] <= (1 << 15)):
        from scipy.sparse.linalg import ArpackNoConvergence

        Xa = np.asarray(X)
        mv_a, ar, ac, adt = _operator_parts(A)
        mv_b, br, bc, bdt = _operator_parts(B)
        if ar != ac or (br, bc) != (ar, ac):
            raise ValueError("A and B must be square and conformal")
        if Xa.ndim != 2 or Xa.shape[0] != ac:
            raise ValueError(f"X must be (n, k) with n={ac}")
        kb = Xa.shape[1]
        cap_b = min(ac, max(8 * kb, 128))
        tries_b = max(1, min(int(maxiter) if maxiter is not None
                             else 6, 10))
        pdt_b = np.dtype(np.result_type(adt, bdt, Xa.dtype))
        try:
            w, V = _eigsh_generalized(
                mv_a, mv_b, ac, pdt_b,
                kb, "LA" if largest else "SA", _block_seed(Xa, pdt_b),
                None, tries_b, (tol if tol else 0), True,
                max_rank=cap_b)
            order = (np.argsort(w)[::-1] if largest
                     else np.argsort(w))
            return np.asarray(w)[order], np.asarray(V)[:, order]
        except ArpackNoConvergence:
            return _host_fallback("lobpcg")(
                A, Xa, B=B, tol=tol, maxiter=maxiter, largest=largest)
    if B is not None or M is not None or Y is not None or kwargs:
        return _host_fallback("lobpcg")(
            A, X, B=B, M=M, Y=Y, tol=tol, maxiter=maxiter,
            largest=largest, **kwargs)
    from jax.experimental.sparse.linalg import lobpcg_standard

    matvec, m_rows, n_cols, dtype = _operator_parts(A)
    if m_rows != n_cols:
        raise ValueError("expected square matrix")
    if (np.issubdtype(dtype, np.complexfloating)
            or np.iscomplexobj(np.asarray(X))):
        # jax's lobpcg_standard builds mixed real/complex while_loop
        # carries on complex operands (upstream limitation); serve
        # complex-Hermitian operators through the native device Lanczos
        # instead (same answers, one jitted scan — VERDICT r4 #6).
        Xa = np.asarray(X)
        if Xa.ndim != 2 or Xa.shape[0] != n_cols:
            raise ValueError(f"X must be (n, k) with n={n_cols}")
        k = Xa.shape[1]
        cdtype = np.result_type(dtype, np.complex64)
        if n_cols > (1 << 15):
            # The full-basis Lanczos route stores an (m, n) basis:
            # fine at the sizes complex lobpcg is actually called at,
            # but it loses LOBPCG's O(n k) memory story at large n —
            # keep the host boundary for those.
            return _host_fallback("lobpcg")(
                A, Xa, tol=tol, maxiter=maxiter, largest=largest)
        which = "LA" if largest else "SA"
        # Bound the basis at O(max(8k, 128) * n) — LOBPCG-class memory,
        # not full-rank Lanczos — and map lobpcg's maxiter onto the
        # (bounded) escalation retry count.
        cap = min(n_cols, max(8 * k, 128))
        tries = max(1, min(int(maxiter) if maxiter is not None else 6,
                           10))
        seed = _block_seed(Xa, np.dtype(cdtype))
        try:
            w, V = _lanczos_eigsh(
                matvec, n_cols, np.dtype(cdtype), k, which, seed,
                None, tries, (tol if tol else 0), True, max_rank=cap)
        except Exception as e:
            from scipy.sparse.linalg import ArpackNoConvergence

            if not isinstance(e, ArpackNoConvergence):
                raise
            # scipy's lobpcg NEVER raises on non-convergence — it
            # returns the current approximation with a warning.  Honor
            # that contract with ONE pass at the full capped subspace
            # (ncv=cap, tol=inf accepts its Ritz pairs), which matches
            # the best subspace the escalation reached.
            import warnings

            warnings.warn(
                "lobpcg (native Lanczos route) did not converge to the "
                "requested tolerance; returning the current "
                "approximation (scipy-compatible behavior)",
                UserWarning, stacklevel=2)
            w, V = _lanczos_eigsh(
                matvec, n_cols, np.dtype(cdtype), k, which, seed,
                cap, 1, np.inf, True, max_rank=cap)
        order = np.argsort(w)[::-1] if largest else np.argsort(w)
        return np.asarray(w)[order], np.asarray(V)[:, order]
    X = jnp.asarray(np.asarray(X), dtype=dtype)
    if X.ndim != 2 or X.shape[0] != n_cols:
        raise ValueError(f"X must be (n, k) with n={n_cols}")
    if 5 * X.shape[1] >= n_cols:
        # jax's lobpcg_standard requires 5k < n; scipy handles these
        # small/fat cases, so serve them the same way.
        return _host_fallback("lobpcg")(
            A, np.asarray(X), tol=tol, maxiter=maxiter, largest=largest)

    sign = 1.0 if largest else -1.0

    def mv_block(S):   # lobpcg_standard wants (n, k) -> (n, k)
        return sign * jax.vmap(matvec, in_axes=1, out_axes=1)(S)

    iters = int(maxiter) if maxiter is not None else 20
    theta, U, _n_iter = lobpcg_standard(mv_block, X, m=max(iters, 1),
                                        tol=tol)
    w = sign * np.asarray(theta)
    order = np.argsort(w)[::-1] if largest else np.argsort(w)
    return w[order], np.asarray(U)[:, order]


# ---------------------------------------------------------------- svds


def svds(A, k=6, ncv=None, tol=0, which="LM", v0=None, maxiter=None,
         return_singular_vectors=True, **kwargs):
    """k largest singular triplets (scipy ``svds``).

    Native path: Lanczos on the Gram operator ``v -> A^T (A v)`` (two
    SpMVs per step, A^T A never materialized), then ``U = A V / s``.
    ``which='SM'`` (smallest) also runs natively — shift-invert at 0 on
    the Gram operator (largest of (A^T A)^{-1}), the same machinery as
    ``eigsh(which='SM')`` — falling back to host scipy when the
    inexact inverse stagnates (rank-deficient A, or kappa(A)^2 beyond
    the iterative inner solver).
    """
    if which not in ("LM", "SM") or kwargs:
        return _host_fallback("svds")(
            A, k=k, ncv=ncv, tol=tol, which=which, v0=v0,
            maxiter=maxiter,
            return_singular_vectors=return_singular_vectors, **kwargs)
    from .linalg import LinearOperator, make_linear_operator

    op = A if isinstance(A, LinearOperator) else make_linear_operator(A)
    m_rows, n_cols = op.shape
    if not (0 < k < min(m_rows, n_cols)):
        raise ValueError(
            f"k={k} must satisfy 0 < k < min(shape)={min(m_rows, n_cols)}")
    if op.dtype is None:
        op._init_dtype()
    dtype = np.dtype(op.dtype)

    try:
        op.rmatvec(jnp.zeros((m_rows,), dtype=dtype))
        has_rmatvec = True
    except Exception:
        has_rmatvec = False

    if has_rmatvec:
        def gram(v):
            return op.rmatvec(op.matvec(v))
    else:
        # Fall back to transposing a sparse operand once.
        AT = A.transpose() if hasattr(A, "transpose") else None
        if AT is None:
            return _host_fallback("svds")(
                A, k=k, ncv=ncv, tol=tol, which=which, v0=v0,
                maxiter=maxiter,
                return_singular_vectors=return_singular_vectors, **kwargs)

        def gram(v):
            return AT @ (op.matvec(v))

    if which == "SM":
        from scipy.sparse.linalg import ArpackNoConvergence

        if m_rows < n_cols:
            # Wide operator: rank(A^T A) <= m_rows < n_cols, so the
            # Gram operator is singular BY CONSTRUCTION — the probe
            # would burn a full MINRES budget just to discover it.
            # Skip straight to the host path.
            return _host_fallback("svds")(
                A, k=k, ncv=ncv, tol=tol, which="SM", v0=v0,
                maxiter=maxiter,
                return_singular_vectors=return_singular_vectors)
        try:
            w, V = _eigsh_shift_invert(
                gram, int(n_cols), dtype, int(k), 0.0, "LM", v0, ncv,
                maxiter, tol, True, name="svds")
        except ArpackNoConvergence:
            return _host_fallback("svds")(
                A, k=k, ncv=ncv, tol=tol, which="SM", v0=v0,
                maxiter=maxiter,
                return_singular_vectors=return_singular_vectors)
    else:
        w, V = _lanczos_eigsh(gram, int(n_cols), dtype, int(k), "LA",
                              v0, ncv, maxiter, tol, True)
    s = np.sqrt(np.clip(w, 0.0, None))            # ascending (scipy order)
    if not return_singular_vectors:
        return s
    Vj = jnp.asarray(V, dtype=dtype)
    AV = np.asarray(jax.vmap(op.matvec, in_axes=1, out_axes=1)(Vj))
    U = AV / np.where(s > 0, s, 1.0)[None, :]
    return U, s, V.T


# ---------------------------------------------------------------- Arnoldi


def _arnoldi(matvec, v0, m: int):
    """m-step Arnoldi with full (twice-applied) reorthogonalization.

    Returns (V, H): V is (m, n) orthonormal, H is the (m + 1, m) upper
    Hessenberg with H[j+1, j] the recurrence norms.  One ``lax.scan``
    (same shape as ``_lanczos``, but the projection coefficients feed
    the full Hessenberg column rather than a tridiagonal pair).
    """
    n = v0.shape[0]
    dtype = v0.dtype
    rdtype = jnp.finfo(dtype).dtype
    eps = jnp.finfo(rdtype).eps
    key0 = jax.random.PRNGKey(11)

    def step(carry, j):
        V, v = carry
        V = V.at[j].set(v)
        w = matvec(v)
        # Modified-Gram-Schmidt-by-blocks, applied twice.
        h = jnp.conj(V) @ w
        w = w - V.T @ h
        h2 = jnp.conj(V) @ w
        w = w - V.T @ h2
        h = h + h2
        beta = jnp.linalg.norm(w).astype(rdtype)
        broke = beta <= 100 * eps * jnp.maximum(
            jnp.max(jnp.abs(h)), 1.0)
        fresh = _restart_direction(V, key0, j, n, rdtype, dtype)
        beta_out = jnp.where(broke, jnp.zeros((), rdtype), beta)
        v_next = jnp.where(
            broke, fresh,
            w / jnp.where(beta == 0, 1.0, beta).astype(dtype))
        # Hessenberg column j: projections h[0..j] on top, the
        # recurrence norm at SUBDIAGONAL position j+1 (h[j+1] is ~0 by
        # orthogonality, so a scatter-add is a clean set).
        col = jnp.concatenate([h, jnp.zeros((1,), dtype)])
        col = col.at[j + 1].add(beta_out.astype(dtype))
        return (V, v_next), col

    V0 = jnp.zeros((m, n), dtype=dtype)
    (V, _), cols = jax.lax.scan(step, (V0, v0), jnp.arange(m, dtype=jnp.int32))
    # cols[j] is the length-(m+1) Hessenberg column j (entries beyond
    # j+1 are ~0 by orthogonality).
    H = cols.T
    return V, H


def _select_ritz(w, k, which):
    if which == "LM":
        sel = np.argsort(np.abs(w))[-k:]
    elif which == "SM":
        # Under shift-invert (the only native route here): smallest
        # |nu| = farthest from sigma, ARPACK's transformed semantics.
        sel = np.argsort(np.abs(w))[:k]
    elif which == "LR":
        sel = np.argsort(np.real(w))[-k:]
    elif which == "SR":
        sel = np.argsort(np.real(w))[:k]
    elif which == "LI":
        sel = np.argsort(np.imag(w))[-k:]
    else:  # SI
        sel = np.argsort(np.imag(w))[:k]
    return sel


def eigs(A, k=6, M=None, sigma=None, which="LM", v0=None, ncv=None,
         maxiter=None, tol=0, return_eigenvectors=True, **kwargs):
    """k eigenpairs of a general (non-symmetric) operator (scipy
    ``eigs``).

    Capability split: the standard problem with ``which`` in
    {LM, LR, SR, LI, SI} runs the NATIVE restarted Arnoldi below;
    shift-invert ``sigma`` also runs natively — Arnoldi on
    ``(A - sigma I)^{-1}`` with an inexact jitted BiCGSTAB inner solve
    (``_shift_invert_op``) nested in the same scan, where scipy/ARPACK
    factorizes on host.  Per scipy semantics ``which`` then refers to
    the transformed ``nu = 1/(lambda - sigma)``; results transform back
    via ``lambda = sigma + 1/nu``.  ``which='SM'`` without sigma routes
    through the same shift-invert at sigma=0 (largest of A^{-1}),
    falling back to host ARPACK if the inexact inverse stagnates.
    Generalized pencils ``A x = lambda M x`` (positive-definite M) run
    natively too: Arnoldi on ``M^{-1} A`` (inner CG on M) without
    sigma, or on ``(A - sigma M)^{-1} M`` (inner BiCGSTAB) with it —
    ``_eigs_generalized`` — with host fallback when an inner-solve
    probe stagnates.  Eigenvalues return complex, like scipy."""
    native_which = ("LM", "LR", "SR", "LI", "SI")
    if M is not None and not kwargs and (
            which in native_which
            or which == "SM"):
        from scipy.sparse.linalg import ArpackNoConvergence

        sig = sigma
        wch = which
        if which == "SM" and sigma is None:
            sig, wch = 0.0, "LM"     # smallest |lambda| of the pencil
        try:
            return _eigs_generalized(
                A, M, int(k), (None if sig is None else complex(sig)),
                wch, v0, ncv, maxiter, tol, return_eigenvectors)
        except ArpackNoConvergence:
            return _host_fallback("eigs")(
                A, k=k, M=M, sigma=sigma, which=which, v0=v0, ncv=ncv,
                maxiter=maxiter, tol=tol,
                return_eigenvectors=return_eigenvectors)
    if which == "SM" and sigma is None and M is None and not kwargs:
        from scipy.sparse.linalg import ArpackNoConvergence

        try:
            return _eigs_shift_invert(A, int(k), complex(0.0), "LM",
                                      v0, ncv, maxiter, tol,
                                      return_eigenvectors)
        except ArpackNoConvergence:
            return _host_fallback("eigs")(
                A, k=k, which="SM", v0=v0, ncv=ncv, maxiter=maxiter,
                tol=tol, return_eigenvectors=return_eigenvectors)
    if (M is not None
            or which not in native_which + ("SM",) or kwargs):
        return _host_fallback("eigs")(
            A, k=k, M=M, sigma=sigma, which=which, v0=v0, ncv=ncv,
            maxiter=maxiter, tol=tol,
            return_eigenvectors=return_eigenvectors, **kwargs)
    if sigma is not None:
        if which == "SM":
            # Same fallback ladder as every other SM route: a
            # stagnating inexact inverse (sigma pathologically close
            # to an eigenvalue) serves through host ARPACK instead of
            # raising.
            from scipy.sparse.linalg import ArpackNoConvergence

            try:
                return _eigs_shift_invert(
                    A, int(k), complex(sigma), which, v0, ncv, maxiter,
                    tol, return_eigenvectors)
            except ArpackNoConvergence:
                return _host_fallback("eigs")(
                    A, k=k, sigma=sigma, which=which, v0=v0, ncv=ncv,
                    maxiter=maxiter, tol=tol,
                    return_eigenvectors=return_eigenvectors)
        # Explicit-sigma LM/LR/SR/LI/SI: same ArpackNoConvergence ->
        # host ladder as the SM route (ADVICE r5 low) — a sigma
        # pathologically close to an eigenvalue stagnates the inexact
        # BiCGSTAB inverse where scipy's splu succeeds.
        from scipy.sparse.linalg import ArpackNoConvergence

        try:
            return _eigs_shift_invert(A, int(k), complex(sigma), which,
                                      v0, ncv, maxiter, tol,
                                      return_eigenvectors)
        except ArpackNoConvergence:
            return _host_fallback("eigs")(
                A, k=k, sigma=sigma, which=which, v0=v0, ncv=ncv,
                maxiter=maxiter, tol=tol,
                return_eigenvectors=return_eigenvectors)
    matvec, m_rows, n_cols, dtype = _operator_parts(A)
    if m_rows != n_cols:
        raise ValueError("expected square matrix")
    n = n_cols
    if not (0 < k < n - 1):
        raise ValueError(f"k={k} must satisfy 0 < k < n - 1 = {n - 1}")

    # Real operators run the whole recurrence in REAL arithmetic (the
    # Krylov basis of a real operator from a real start is real — a
    # complex basis would double matvec cost and memory); only the
    # small host eig and the Ritz combination go complex.
    cdtype = np.result_type(dtype, np.complex64)
    basis_dtype = dtype
    mv = matvec
    if v0 is None:
        v0 = np.random.default_rng(0).standard_normal(n)
    elif (np.iscomplexobj(np.asarray(v0))
          and not np.issubdtype(dtype, np.complexfloating)):
        # Complex start on a real operator: complex basis, two real
        # matvecs per step (the only case that needs them).
        basis_dtype = cdtype
        mv = _complex_matvec(matvec, dtype, cdtype)
    v0 = jnp.asarray(v0, dtype=basis_dtype)
    v0 = v0 / jnp.linalg.norm(v0)
    return _arnoldi_eigs(mv, n, cdtype, k, which, v0, ncv, maxiter,
                         tol, return_eigenvectors)


def _arnoldi_eigs(mv, n, cdtype, k, which, v0, ncv, maxiter, tol,
                  return_eigenvectors, transform=None):
    """Shared restarted-Arnoldi driver: escalate the subspace until the
    Ritz residuals converge, then (optionally) map the Ritz values
    through ``transform`` (the shift-invert back-transform
    ``lambda = sigma + 1/nu``; residual control stays in the operator's
    own — i.e. transformed — spectrum, exactly like ARPACK)."""
    rdtype = np.finfo(cdtype).dtype
    from .linalg import maybe_jit

    arnoldi = maybe_jit(_arnoldi, static_argnums=(0,),
                        static_argnames=("m",))
    atol, m, tries = _escalation_params(tol, rdtype, ncv, k, n,
                                        maxiter, min_extra=2)
    for try_i in range(tries):
        if try_i:
            m = min(n, 2 * m)
        # Doubling only ever happens right before a run (see
        # _lanczos_eigsh): post-loop checks judge the size that ran.
        V, H = arnoldi(mv, v0, m=m)
        Hm = np.asarray(H)[:m, :m]
        beta_last = float(abs(np.asarray(H)[m, m - 1]))
        w, y = np.linalg.eig(Hm)
        sel = _select_ritz(w, k, which)
        w_k = w[sel]
        y_k = y[:, sel]
        resid = beta_last * np.abs(y_k[-1, :])
        scale = np.maximum(np.abs(w_k), 1.0)
        if np.all(resid <= atol * scale) or m >= n:
            break
    converged = bool(np.all(resid <= atol * scale)) or m >= n
    lam = transform(w_k) if transform is not None else w_k
    # scipy contract: eigs eigenvalues are ALWAYS complex, even when a
    # real Hessenberg's spectrum happens to be all-real (np.linalg.eig
    # returns float64 then) — cast here so every caller inherits it.
    lam = np.asarray(lam).astype(cdtype)
    if converged and not return_eigenvectors:
        return lam          # skip forming X entirely
    X = np.asarray(jnp.einsum("mn,mk->nk", V,
                              jnp.asarray(y_k, dtype=cdtype)))
    _require_converged(resid, atol, scale, m, n, lam, X)
    if not return_eigenvectors:
        return lam
    return lam, X


def _promote_real_operators(matvecs, dtypes, cdtype,
                            extra_complex: bool):
    """Shared complex-promotion ladder for the non-symmetric drivers:
    returns ``(base_dtype, wrapped, guards)`` — the working dtype, the
    matvecs promoted to a complex basis when anything (operand dtypes,
    a complex sigma, a complex start) requires it, and always-complex
    guard matvecs for the residual referees."""
    pdt = dtypes[0] if len(dtypes) == 1 else np.promote_types(*dtypes)
    is_complex = np.issubdtype(pdt, np.complexfloating)
    if is_complex or not extra_complex:
        base = np.dtype(pdt)
        wrapped = list(matvecs)
    else:
        base = np.dtype(cdtype)
        wrapped = [_complex_matvec(mv, np.dtype(d), cdtype)
                   for mv, d in zip(matvecs, dtypes)]
    if np.issubdtype(base, np.complexfloating):
        guards = list(wrapped)
    else:
        guards = [_complex_matvec(mv, np.dtype(d), cdtype)
                  for mv, d in zip(matvecs, dtypes)]
    return base, wrapped, guards


def _si_back_transform(sigma, rdtype, cdtype):
    """Shared ``lambda = sigma + 1/nu`` back-transform for the
    non-symmetric shift-invert drivers (zero-nu guarded by tiny)."""

    def back(nu):
        tiny = np.finfo(rdtype).tiny
        safe = np.where(nu == 0, tiny, nu)
        return (complex(sigma) + 1.0 / safe).astype(cdtype)

    return back


def _eigs_shift_invert(A, k, sigma, which, v0, ncv, maxiter, tol,
                       return_eigenvectors):
    """Native shift-invert ``eigs``: Arnoldi on ``(A - sigma I)^{-1}``
    with the inexact jitted BiCGSTAB inner apply (``_shift_invert_op``).
    A complex sigma (or complex start) on a real operator promotes the
    basis to complex with two real matvecs per inner apply."""
    matvec, m_rows, n_cols, dtype = _operator_parts(A)
    if m_rows != n_cols:
        raise ValueError("expected square matrix")
    n = n_cols
    if not (0 < k < n - 1):
        raise ValueError(f"k={k} must satisfy 0 < k < n - 1 = {n - 1}")
    cdtype = np.result_type(dtype, np.complex64)
    rdtype = np.finfo(cdtype).dtype
    extra_complex = (
        sigma.imag != 0
        or (v0 is not None and np.iscomplexobj(np.asarray(v0)))
    )
    base_dtype, (base_mv,), (check_mv,) = _promote_real_operators(
        [matvec], [dtype], cdtype, extra_complex)
    sig_val = (complex(sigma)
               if np.issubdtype(base_dtype, np.complexfloating)
               else float(sigma.real))
    atol_outer = _outer_atol(tol, rdtype)
    op, inner_atol = _shift_invert_op(base_mv, sig_val, base_dtype, n,
                                      atol_outer, sym=False)
    _probe_inverse(base_mv, op, sig_val, base_dtype, n, inner_atol,
                   "eigs")
    if v0 is None:
        v0 = np.random.default_rng(0).standard_normal(n)
    v0 = jnp.asarray(v0, dtype=base_dtype)
    v0 = v0 / jnp.linalg.norm(v0)

    back = _si_back_transform(sigma, rdtype, cdtype)

    # Always form X: the original-spectrum check below catches a
    # silently-stagnated inner solve (see _check_original_residuals).
    lam, X = _arnoldi_eigs(op, n, cdtype, k, which, v0, ncv, maxiter,
                           tol, True, transform=back)
    _check_original_residuals(check_mv, np.asarray(lam), X,
                              atol_outer, "eigs")
    if not return_eigenvectors:
        return lam
    return lam, X


def _eigs_generalized(A, M, k, sigma, which, v0, ncv, maxiter, tol,
                      return_eigenvectors):
    """Native generalized (non-symmetric) ``eigs``: Arnoldi on
    ``M^{-1} A`` (sigma None; eigenvalues of the operator ARE the
    pencil eigenvalues — no transform) or on ``(A - sigma M)^{-1} M``
    (shift-invert; ``which`` on the transformed nu, back-transform
    ``lambda = sigma + 1/nu``).  Inner solves: CG on the
    positive-definite M, BiCGSTAB on the general shifted pencil — both
    with normalized right-hand sides so the tolerance is relative.
    The pencil-residual guard referees the inexact inner solves."""
    from .linalg import _bicgstab_loop, _cg_loop

    matvec_a, ar, ac, adt = _operator_parts(A)
    mv_m, mr, mc, mdt = _operator_parts(M)
    if ar != ac:
        raise ValueError("expected square matrix")
    if (mr, mc) != (ar, ac):
        raise ValueError(f"M has shape {(mr, mc)}, expected {(ar, ac)}")
    n = ac
    if not (0 < k < n - 1):
        raise ValueError(f"k={k} must satisfy 0 < k < n - 1 = {n - 1}")
    cdtype = np.result_type(adt, mdt, np.complex64)
    rdtype = np.finfo(cdtype).dtype
    extra_complex = (
        (sigma is not None and sigma.imag != 0)
        or (v0 is not None and np.iscomplexobj(np.asarray(v0)))
    )
    base_dtype, (base_a, base_m), (guard_a, guard_m) = (
        _promote_real_operators([matvec_a, mv_m], [adt, mdt], cdtype,
                                extra_complex))
    atol_outer = _outer_atol(tol, rdtype)
    inner_atol, inner_maxiter = _inner_solver_params(atol_outer, rdtype,
                                                     n)
    ident = lambda r: r  # noqa: E731

    if sigma is None:
        solve = _normalized_rhs_solver(
            lambda b: _cg_loop(base_m, ident, b, jnp.zeros_like(b),
                               inner_atol, inner_maxiter, 10)[0])
        _probe_apply(base_m, solve, n, base_dtype, inner_atol,
                     "generalized eigs")
        transform = None
    else:
        sig_val = (complex(sigma) if np.issubdtype(
            base_dtype, np.complexfloating) else float(sigma.real))
        sig_dev = jnp.asarray(sig_val, dtype=base_dtype)

        def shifted(x):
            return base_a(x) - sig_dev * base_m(x)

        solve = _normalized_rhs_solver(
            lambda b: _bicgstab_loop(shifted, ident, b,
                                     jnp.zeros_like(b), inner_atol,
                                     inner_maxiter, 10)[0])
        _probe_apply(shifted, solve, n, base_dtype, inner_atol,
                     "generalized eigs shift-invert")

        transform = _si_back_transform(sigma, rdtype, cdtype)

    def op(v):
        return solve(base_m(v)) if sigma is not None else solve(
            base_a(v))

    if v0 is None:
        v0 = np.random.default_rng(0).standard_normal(n)
    v0 = jnp.asarray(v0, dtype=base_dtype)
    v0 = v0 / jnp.linalg.norm(v0)
    lam, X = _arnoldi_eigs(op, n, cdtype, k, which, v0, ncv, maxiter,
                           tol, True, transform=transform)
    # Pencil-residual referee in complex arithmetic (X is complex).
    _pencil_residual_guard(guard_a, guard_m, np.asarray(lam), X,
                           atol_outer, rdtype)
    if not return_eigenvectors:
        return lam
    return lam, X
