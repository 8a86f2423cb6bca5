"""Measurement utilities: wall-timing modes and the config-sweep harness.

Lives in ``core`` (not ``tune``) so the layering stays one-directional —
``core.pareto.measure_configs`` and the tuner both build on it; the tuner
re-exports :class:`TimingHarness` as part of its public API.

The naive sweep (``jax.jit(op.matvec)`` per config) pays a fresh trace
for every configuration — and again every time the same config is
re-measured (the exhaustive baseline, an autotune following an
exhaustive sweep, a matvec sweep followed by a matmat sweep...).  The
harness instead keeps ONE jitted applier per variant family with the
precision config as a *static* argument, so jax's executable cache is
shared across the whole lattice and re-measuring any (config, shape,
dtype) combination is a cache hit, never a retrace.

Two timing modes: ``throughput`` (paper protocol, back-to-back async
dispatch, one sync) and ``latency`` (per-call ``block_until_ready``,
min-of-N — what a Krylov iteration actually waits for).  The harness
counts what was timed so callers can verify pruning really reduced
measurement.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Optional

import jax

from .fftmatvec import _local_gram, _local_matmat, _local_matvec

VARIANTS = ("matvec", "rmatvec", "matmat", "rmatmat", "gram")


def time_callable(fn: Callable, arg, repeats: int, warmup: int = 2,
                  mode: str = "throughput") -> float:
    """Wall-time one application of ``fn``.

    ``mode="throughput"`` (paper protocol) issues ``repeats`` calls
    back-to-back and synchronizes once — async dispatch overlaps, so this
    measures sustained per-call cost.  ``mode="latency"`` synchronizes
    every call and returns the minimum — the completion time a solver
    iteration actually waits for."""
    if repeats < 1:
        raise ValueError(f"repeats must be >= 1, got {repeats}")
    if mode not in ("throughput", "latency"):
        raise ValueError(f"unknown timing mode {mode!r}")
    for _ in range(warmup):
        jax.block_until_ready(fn(arg))
    if mode == "latency":
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(arg))
            times.append(time.perf_counter() - t0)
        return min(times)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(arg)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats


@dataclasses.dataclass
class TimedEntry:
    config: object          # PrecisionConfig
    variant: str
    time_s: float


class TimingHarness:
    """Measures operator applications across precision configs.

    Parameters
    ----------
    repeats, warmup, mode:
        forwarded to :func:`time_callable`.
    timer:
        optional override ``timer(cfg, fn, arg) -> seconds``.  Used by the
        oracle tests to make selection deterministic (a synthetic cost
        model shared by the exhaustive and pruned paths); ``None`` means
        real wall-clock timing.
    """

    MAX_MESH_ENTRIES = 8   # distributed-op fallback closures retained

    def __init__(self, *, repeats: int = 5, warmup: int = 2,
                 mode: str = "throughput",
                 timer: Optional[Callable] = None):
        if repeats < 1:
            raise ValueError(f"repeats must be >= 1, got {repeats}")
        if mode not in ("throughput", "latency"):
            raise ValueError(f"unknown timing mode {mode!r}")
        self.repeats = repeats
        self.warmup = warmup
        self.mode = mode
        self.timer = timer
        self._jitted: dict = {}     # family / (variant, id) -> jitted callable
        self.timed: list[TimedEntry] = []
        self.n_runs = 0             # total operator applications issued
        self.n_traces = 0           # executable builds (jit cache misses)

    # -- jit cache ----------------------------------------------------------
    def _shared(self, family: str):
        """One jitted applier per family ("vec"/"mat"/"gram"), config
        static."""
        fn = self._jitted.get(family)
        if fn is None:
            # self.n_traces increments at *trace* time only: a second call
            # with the same (shapes, static args) is an executable-cache
            # hit and leaves the counter untouched — this is the
            # launch-count instrumentation SolveEngine's jit-reuse
            # contract is tested against.
            if family == "gram":
                def apply(F_re, F_im, x, *, N_t, cfg, opts, adjoint,
                          io_dtype):
                    self.n_traces += 1
                    return _local_gram(F_re, F_im, x, N_t, cfg,
                                       opts).astype(io_dtype)
            else:
                local = _local_matvec if family == "vec" else _local_matmat

                def apply(F_re, F_im, x, *, N_t, cfg, opts, adjoint,
                          io_dtype):
                    self.n_traces += 1
                    return local(F_re, F_im, x, N_t, cfg, opts,
                                 adjoint).astype(io_dtype)

            fn = jax.jit(apply, static_argnames=("N_t", "cfg", "opts",
                                                 "adjoint", "io_dtype"))
            self._jitted[family] = fn
        return fn

    def callable_for(self, op, variant: str = "matvec") -> Callable:
        """Single-argument jitted callable for ``op``'s variant.

        Single-device operators route through the shared applier (configs
        as static args — lattice-wide executable reuse); distributed
        operators fall back to jitting the bound method, cached per
        operator instance."""
        if variant not in VARIANTS:
            raise ValueError(f"unknown variant {variant!r}")
        if op.mesh is not None:
            key = (variant, id(op))
            fn = self._jitted.get(key)
            if fn is None:
                def counted(op, x):
                    self.n_traces += 1
                    if variant == "gram":
                        return op.gram(space="parameter").apply(x)
                    return getattr(op, variant)(x)

                # the operator goes in as an argument (its planes are
                # program inputs, not constants); the partial still pins
                # its sharded arrays, so cap how many a long-lived harness
                # retains (FIFO evict)
                fn = functools.partial(jax.jit(counted), op)
                mesh_keys = [k for k in self._jitted
                             if isinstance(k, tuple) and len(k) == 2]
                if len(mesh_keys) >= self.MAX_MESH_ENTRIES:
                    del self._jitted[mesh_keys[0]]
                self._jitted[key] = fn
            return fn
        family = ("gram" if variant == "gram"
                  else "vec" if variant in ("matvec", "rmatvec") else "mat")
        adjoint = variant in ("rmatvec", "rmatmat")
        shared = self._shared(family)
        F_re, F_im = op.F_hat_re, op.F_hat_im
        N_t, cfg, opts, io_dtype = op.N_t, op.precision, op.opts, op.io_dtype

        def call(x):
            # matmat convention (FFTMatvec.matmat): 2-D input is the
            # S = 1 special case — promote and squeeze back
            if family == "mat" and x.ndim == 2:
                return call(x[..., None])[..., 0]
            return shared(F_re, F_im, x, N_t=N_t, cfg=cfg, opts=opts,
                          adjoint=adjoint, io_dtype=io_dtype)

        return call

    # -- measurement --------------------------------------------------------
    def run_once(self, op, v, variant: str = "matvec"):
        """One application (error measurement only — not counted as timed)."""
        fn = self.callable_for(op, variant)
        out = jax.block_until_ready(fn(v))
        self.n_runs += 1
        return out

    def time(self, op, v, variant: str = "matvec"):
        """Measure ``op``'s variant: returns ``(output, seconds)``."""
        fn = self.callable_for(op, variant)
        out = jax.block_until_ready(fn(v))
        self.n_runs += 1
        if self.timer is not None:
            t = float(self.timer(op.precision, fn, v))
        else:
            t = time_callable(fn, v, self.repeats, warmup=self.warmup,
                              mode=self.mode)
            self.n_runs += self.repeats + self.warmup
        self.timed.append(TimedEntry(op.precision, variant, t))
        return out, t

    # -- accounting ---------------------------------------------------------
    @property
    def n_timed(self) -> int:
        return len(self.timed)

    @property
    def n_appliers(self) -> int:
        """Distinct jitted appliers retained (families + mesh fallbacks).
        A SolveEngine serving many buckets keeps this at the family
        count — buckets share appliers, only executables differ."""
        return len(self._jitted)

    def timed_configs(self, variant: str | None = None) -> list:
        return [e.config for e in self.timed
                if variant is None or e.variant == variant]

    def reset_counters(self) -> None:
        """Zero the measurement counters (the jit cache is kept)."""
        self.timed.clear()
        self.n_runs = 0

    def clear_jit_cache(self) -> None:
        """Drop every retained jitted callable (and, for distributed
        operators, the device arrays their closures pin)."""
        self._jitted.clear()
