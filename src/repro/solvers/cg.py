"""Preconditioned conjugate gradients, multi-RHS, mixed precision.

``pcg`` runs S independent CG chains that *share* every operator
application: vectors carry a minor RHS axis (..., S) and the recurrence
scalars (alpha, beta, rho) are per-column vectors of shape (S,).  With an
:class:`~repro.core.FFTMatvec` behind the operator this turns the
bandwidth-bound SBGEMV of Phase 3 into the SBGEMM the multi-RHS kernels
are built for — the solver is the workload that motivates batching.

``cg_normal_equations`` is the inverse-problem entry point: CGNR on
(F* F + damp I) m = F* d, i.e. Tikhonov-regularized least squares driven
entirely by ``matmat``/``rmatmat``.

The loop is host-driven (paper-style: per-iteration residual recording
and early exit); each iteration costs one operator application plus
O(1) reductions.  A profile shows each ``pcg`` call as a host span
``pcg.solve`` and each blocking read of a device value in it as
``pcg.sync`` (``jax.profiler.TraceAnnotation``: nothing is recorded when
no profiler runs).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .precision import (SolverPrecision, col_dot, col_norm,
                        resolve_precision)
from .result import SolveResult

_SAFE = lambda x: jnp.where(x == 0, 1, x)


def _host_norm(v, ortho, k: int):
    """``col_norm(v)`` read on the host as float64; the ``pcg.sync`` span
    covers the wait for the device value, not the dispatch."""
    norm = col_norm(v, ortho)
    with jax.profiler.TraceAnnotation("pcg.sync", k=k):
        return np.asarray(norm, np.float64)


def pcg(A: Callable, b, *, x0=None, tol=1e-10, maxiter: int = 500,
        M: Optional[Callable] = None, multi_rhs: bool | None = None,
        col_maxiter=None,
        precision: SolverPrecision | str = SolverPrecision()) -> SolveResult:
    """Preconditioned CG for SPD ``A``, S stacked right-hand sides.

    ``b``'s minor axis is the RHS stack when ``multi_rhs`` is true
    (default: inferred, 3-D and higher — the (R, N_t, S) SOTI layout);
    otherwise ``b`` is one vector and the solve degenerates to classical
    PCG.  Pass ``multi_rhs=True`` explicitly for a flat (n, S) system.
    ``A`` and the optional preconditioner ``M`` receive arrays of ``b``'s
    exact shape and must act column-wise over the RHS axis (any linear
    operator does).

    ``tol`` and ``col_maxiter`` may be per-column (S,) vectors — the
    multi-tenant case where each stacked RHS belongs to a different
    request.  A column is *frozen* the first time its relative residual
    drops below its tolerance (or its iteration budget runs out): its
    alpha/beta are masked to zero from then on, so low-precision
    recurrence legs cannot drift an already-converged column back above
    tol while its batch-mates finish.  The loop stops once every column
    is frozen; ``SolveResult.col_iters[s]`` records the iterations column
    s actually updated.

    Per ``precision``: operator inputs are carried at the apply level,
    steering dots run at the orthogonalize level (accumulated high), and
    x/r/p updates at the recurrence level.  ``precision`` also accepts a
    3-char string ("sds") or ``"auto"`` (per-leg levels derived from
    ``tol`` via :meth:`SolverPrecision.from_tolerance` — the tightest
    column for per-column tolerances).
    """
    precision = resolve_precision(precision, float(np.min(tol)))
    if multi_rhs is None:
        multi_rhs = b.ndim >= 3
    S = b.shape[-1] if multi_rhs else 1
    with jax.profiler.TraceAnnotation("pcg.solve", S=S, maxiter=maxiter):
        return _pcg(A, b, x0=x0, tol=tol, maxiter=maxiter, M=M,
                    squeeze=not multi_rhs, col_maxiter=col_maxiter,
                    precision=precision)


def _pcg(A, b, *, x0, tol, maxiter, M, squeeze, col_maxiter,
         precision) -> SolveResult:
    if squeeze:
        b = b[..., None]
    S = b.shape[-1]
    tol_col = np.broadcast_to(np.asarray(tol, np.float64), (S,))
    budget = (np.full((S,), maxiter, dtype=int) if col_maxiter is None
              else np.minimum(np.broadcast_to(
                  np.asarray(col_maxiter, dtype=int), (S,)), maxiter))
    rec_dt = precision.recurrence_dtype()
    app_dt = precision.apply_dtype()
    ortho = precision.orthogonalize

    def _user_shaped(fn, v):
        if squeeze:
            return fn(v[..., 0])[..., None]
        return fn(v)

    def apply_A(v):
        return _user_shaped(A, v.astype(app_dt)).astype(rec_dt)

    x = (jnp.zeros_like(b, dtype=rec_dt) if x0 is None
         else jnp.asarray(x0).reshape(b.shape).astype(rec_dt))
    r = (b.astype(rec_dt) - apply_A(x)) if x0 is not None else b.astype(rec_dt)
    z = _user_shaped(M, r).astype(rec_dt) if M is not None else r
    p = z
    rz = col_dot(r, z, ortho)
    b_norm = _host_norm(b, ortho, 0)
    b_norm = np.where(b_norm == 0, 1.0, b_norm)

    relres = _host_norm(r, ortho, 0) / b_norm
    conv = relres < tol_col              # converged columns (stay frozen)
    frozen = conv | (budget <= 0)        # frozen = converged or out of budget
    col_iters = np.zeros((S,), dtype=int)
    history = []
    k = 0
    if frozen.all() or maxiter == 0:
        # no iterations will run: report the *initial* residual honestly
        # instead of the old empty-history/untouched-x contract, which
        # claimed nothing even when x0 already violated tol.
        history.append(relres)
    for k in range(1, maxiter + 1):
        if frozen.all():
            k -= 1
            break
        active = jnp.asarray(~frozen)
        Ap = apply_A(p)
        alpha = rz / _SAFE(col_dot(p, Ap, ortho))
        alpha = jnp.where(active, alpha, 0).astype(rec_dt)
        x = (x + p * alpha).astype(rec_dt)
        r = (r - Ap * alpha).astype(rec_dt)
        relres_new = _host_norm(r, ortho, k) / b_norm
        # frozen columns report the residual they froze at (their r is
        # untouched, but recompute noise must never un-freeze them)
        relres = np.where(frozen, relres, relres_new)
        history.append(relres)
        col_iters[~frozen] = k
        conv |= (~frozen) & (relres < tol_col)
        frozen = frozen | conv | (budget <= k)
        if frozen.all():
            break
        z = _user_shaped(M, r).astype(rec_dt) if M is not None else r
        rz_new = col_dot(r, z, ortho)
        beta = rz_new / _SAFE(rz)
        beta = jnp.where(jnp.asarray(~frozen), beta, 0).astype(rec_dt)
        p = (z + p * beta).astype(rec_dt)
        rz = rz_new

    x = x[..., 0] if squeeze else x
    return SolveResult(x=x, converged=bool(conv.all()), n_iters=k,
                       residual_history=np.asarray(history),
                       col_iters=col_iters)


def cg_normal_equations(op, d_obs, *, damp: float = 0.0, tol=1e-10,
                        maxiter: int = 500, M: Optional[Callable] = None,
                        col_maxiter=None,
                        precision: SolverPrecision | str = SolverPrecision(),
                        gram=None) -> SolveResult:
    """CGNR: solve min ||F m - d||^2 + damp ||m||^2 via
    (F* F + damp I) m = F* d, with F an :class:`FFTMatvec`-like operator
    exposing ``matmat``/``rmatmat`` ((R, N_t, S) stacked SOTI layout, 2-D
    inputs treated as S = 1).  ``precision`` accepts the same string
    forms as :func:`pcg` (incl. ``"auto"``).

    The F*F inner product runs through the fused parameter-space
    :class:`~repro.core.GramOperator` (one stage-graph pipeline per
    iteration instead of a composed rmatmat/matmat pair) whenever ``op``
    exposes ``.gram()``; pass ``gram`` to supply a prebuilt one (e.g. a
    retuned or preconditioning variant).  Plain callable-pair operators
    fall back to the composed product.  ``tol``/``col_maxiter`` may be
    per-column vectors exactly as in :func:`pcg`."""
    precision = resolve_precision(precision, float(np.min(tol)))
    rec_dt = precision.recurrence_dtype()

    if gram is None and hasattr(op, "gram"):
        gram = op.gram(space="parameter", mode="exact")
    if gram is not None:
        def normal_op(v):
            return gram.apply(v) + damp * v
    else:
        def normal_op(v):
            return op.rmatmat(op.matmat(v)) + damp * v

    rhs = op.rmatmat(d_obs).astype(rec_dt)
    return pcg(normal_op, rhs, tol=tol, maxiter=maxiter, M=M,
               col_maxiter=col_maxiter, precision=precision)
