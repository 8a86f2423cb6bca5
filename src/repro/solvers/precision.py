"""Per-phase precision configuration for the Krylov solvers.

The FFTMatvec pipeline splits *one* matvec into five phases; a Krylov
iteration has its own natural phase split, and mixed-precision Krylov
practice (GMRES-IR, survey arXiv:2412.19322) shows the three legs tolerate
very different precisions:

    apply         — the operator applications (F / F*, the expensive leg;
                    its *internal* phases are governed by the operator's
                    own :class:`~repro.core.PrecisionConfig`): the level
                    vectors are carried at when handed to the operator.
    orthogonalize — inner products and norms steering the recurrence
                    coefficients (alpha, beta, rho); most sensitive leg.
    recurrence    — the axpy-style updates of x, r, p, w.

Levels reuse the core ladder: "d" (f64), "s" (f32), "h" (bf16).  A config
is written like the operator's flag, e.g. ``SolverPrecision.from_string
("sds")``; all-double is the paper-faithful default.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import precision as _prec

SOLVER_PHASES = ("apply", "orthogonalize", "recurrence")


@dataclasses.dataclass(frozen=True)
class SolverPrecision:
    """Precision level of each Krylov-iteration leg."""

    apply: str = "d"
    orthogonalize: str = "d"
    recurrence: str = "d"

    def __post_init__(self):
        for p in SOLVER_PHASES:
            lvl = getattr(self, p)
            if lvl not in ("h", "s", "d"):
                raise ValueError(
                    f"bad precision level {lvl!r} for solver phase {p!r}")

    @classmethod
    def from_string(cls, s: str) -> "SolverPrecision":
        if len(s) != 3:
            raise ValueError(f"solver precision string must have 3 chars, "
                             f"got {s!r}")
        return cls(*s)

    def to_string(self) -> str:
        return "".join(getattr(self, p) for p in SOLVER_PHASES)

    # -- derived dtypes -----------------------------------------------------
    def apply_dtype(self):
        return _prec.real_dtype(self.apply)

    def ortho_dtype(self):
        return _prec.real_dtype(self.orthogonalize)

    def recurrence_dtype(self):
        return _prec.real_dtype(self.recurrence)

    def replace(self, **kw) -> "SolverPrecision":
        return dataclasses.replace(self, **kw)

    @classmethod
    def from_tolerance(cls, tol: float, *, ladder=("h", "s", "d"),
                       apply_slack: float = 100.0, ortho_margin: float = 10.0,
                       op=None) -> "SolverPrecision":
        """Per-leg precisions for a target relative residual ``tol``.

        Each leg gets the *lowest* ladder level whose unit roundoff meets
        its sensitivity (mixed-precision Krylov practice, survey
        arXiv:2412.19322): the steering scalars (orthogonalize) must
        resolve below the tolerance (``eps <= tol / ortho_margin``), the
        recurrence must carry vectors at the tolerance (``eps <= tol``),
        and the operator-traffic leg tolerates much coarser storage
        (``eps <= tol * apply_slack`` — its rounding enters once per
        application, not cumulatively).  No qualifying level -> the
        ladder's highest.  Examples: tol=1e-4 -> "hss" (== TPU_MIXED),
        tol=1e-10 -> "ddd".

        Pass ``op`` (an FFTMatvec) to floor the target at the operator's
        own eq.-(6) error floor — legs are never provisioned finer than
        the residual the operator can actually deliver."""
        if tol <= 0.0:
            raise ValueError(f"tolerance must be positive, got {tol}")
        if op is not None:
            from . import error_floor   # deferred: package-level helper
            tol = max(tol, error_floor(op))
        ordered = sorted(ladder, key=_prec.level_index)

        def lowest(target: float) -> str:
            for lvl in ordered:
                if _prec.machine_eps(lvl) <= target:
                    return lvl
            return ordered[-1]

        return cls(apply=lowest(tol * apply_slack),
                   orthogonalize=lowest(tol / ortho_margin),
                   recurrence=lowest(tol))


def resolve_precision(precision, tol: float) -> SolverPrecision:
    """Normalize a solver ``precision`` argument: a SolverPrecision passes
    through, ``"auto"`` derives per-leg levels from the solve tolerance
    (:meth:`SolverPrecision.from_tolerance`) over the levels this process
    has — h/s without x64, where a "d" leg would silently run in f32 —,
    any other string is a 3-char config like ``"sds"``."""
    if isinstance(precision, SolverPrecision):
        return precision
    if isinstance(precision, str):
        if precision == "auto":
            ladder = ("h", "s", "d") if jax.config.jax_enable_x64 \
                else ("h", "s")
            return SolverPrecision.from_tolerance(tol, ladder=ladder)
        return SolverPrecision.from_string(precision)
    raise TypeError(f"precision must be SolverPrecision or str, "
                    f"got {type(precision).__name__}")


DOUBLE = SolverPrecision.from_string("ddd")
SINGLE = SolverPrecision.from_string("sss")
# TPU-native mixed config: bf16 operator traffic, f32 steering scalars.
TPU_MIXED = SolverPrecision.from_string("hss")


def accum_dtype():
    """Accumulation dtype for steering dots: highest available."""
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def col_dot(a, b, level: str):
    """Per-RHS-column inner product <a, b> at the given level.

    a, b: (..., S) with the RHS axis minor.  Contracts every axis except
    the last; accumulates at the highest available precision (the paper's
    setup-phase rule: steering scalars must not silently downgrade)."""
    dt = _prec.real_dtype(level)
    acc = accum_dtype()
    af = a.astype(dt).reshape(-1, a.shape[-1])
    bf = b.astype(dt).reshape(-1, b.shape[-1])
    # HIGHEST: a TPU's default for f32 is one bf16 MXU pass
    return jnp.einsum("is,is->s", af, bf, preferred_element_type=acc,
                      precision=jax.lax.Precision.HIGHEST)


def col_norm(a, level: str):
    """Per-column L2 norm at the given level (accumulated high)."""
    return jnp.sqrt(col_dot(a, a, level))
