"""Dynamic mixed-precision selection (paper §3.2 as a runtime service).

``autotune`` answers the paper's central question — which per-phase
precision config is fastest at a given error tolerance — without timing
the whole lattice:

  0. cache lookup: (shape, ladder, variant, device) seen before -> done.
  1. baseline run: the all-highest config is timed and its output becomes
     the error reference (it is also the guaranteed-feasible fallback).
  2. calibration probes: one error-only run per (phase, sub-baseline
     level) fits the eq.-(6) constants (``pruner.calibrate_constants``).
  3. model prune: the calibrated bound over the full lattice discards
     configs whose bound exceeds ``slack * tol``.
  4. frontier search: surviving candidates are visited cheapest-first;
     a candidate precision-dominated by an already-*measured*-feasible
     config is skipped (it cannot be faster), otherwise one error-only
     run decides feasibility.  What remains is the minimal antichain of
     measured-feasible configs.
  5. timing: only baseline + frontier are timed (jit-shared harness);
     the fastest measured-feasible config wins, exactly as the exhaustive
     ``optimal_config`` would pick — at a fraction of the measurements.
  6. cache store (opt-in via ``cache``/``cache_path``).
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pareto import (ConfigRecord, optimal_config, pareto_front,
                               rel_l2)
from repro.core.precision import (PrecisionConfig, all_configs, config_le,
                                  max_level)
from repro.core.toeplitz import random_unrepresentable

from .cache import CacheKey, TuningCache
from .harness import TimingHarness
from .pruner import calibrate_constants, probe_configs, prune_lattice
from .tile_map import tile_map_for_operator

_ADJOINT_VARIANTS = ("rmatvec", "rmatmat")


@dataclasses.dataclass
class TuneResult:
    """Outcome of one autotune run."""
    config: PrecisionConfig              # selected configuration
    op: object                           # operator retuned to ``config``
    record: ConfigRecord                 # its (error, time) record
    records: list[ConfigRecord]          # everything that was *timed*
    front: list[ConfigRecord]            # Pareto front of the timed set
    bounds: dict[str, float]             # calibrated model bound per config
    errors: dict[str, float]             # every measured error (incl. probes)
    constants: dict[str, float]          # calibrated eq.-(6) constants
    n_timed: int
    n_lattice: int
    from_cache: bool = False
    cache_key: Optional[CacheKey] = None

    def summary(self) -> str:
        src = "cache" if self.from_cache else \
            f"timed {self.n_timed}/{self.n_lattice}"
        return (f"autotune -> {self.config.to_string()} "
                f"(rel_err {self.record.rel_error:.2e}, "
                f"{self.record.time_s * 1e3:.3f} ms, "
                f"speedup {self.record.speedup:.2f}x; {src})")


def default_input(op, variant: str = "matvec", *, n_rhs: int = 4,
                  seed: int = 0):
    """Probe input for tuning: unrepresentable-mantissa values (paper
    §4.2.1 — lossy at every sub-f64 level, so copy phases show true
    error) when x64 is on, plain normals otherwise."""
    rows = op.N_d if variant in _ADJOINT_VARIANTS else op.N_m
    shape = (rows, op.N_t) if variant in ("matvec", "rmatvec", "gram") \
        else (rows, op.N_t, n_rhs)
    key = jax.random.PRNGKey(seed)
    if jax.config.jax_enable_x64:
        v = random_unrepresentable(key, shape)
    else:
        v = jax.random.normal(key, shape, dtype=jnp.float32)
    return v.astype(op.io_dtype)


def _assert_op_clean(op) -> None:
    """``lint=True`` pre-flight: statically lint the plans a candidate
    operator would lower (abstract tracing, nothing executes) and raise
    before any timing budget is spent on a contract-violating config."""
    from repro import analysis  # deferred: tune must import without it
    bad = analysis.errors(analysis.lint_operator(op))
    if bad:
        raise analysis.PlanLintError(
            f"candidate config {op.precision.to_string()!r} failed "
            f"static analysis:\n" + analysis.format_findings(bad), bad)


def autotune(op, *, tol: float, v=None, ladder: Sequence[str] | None = None,
             variant: str = "matvec", harness: TimingHarness | None = None,
             repeats: int = 5, warmup: int = 2, mode: str = "throughput",
             timer=None, cache: TuningCache | None = None,
             cache_path=None, slack: float = 8.0, kappa: float = 1.0,
             constants: dict | None = None, p_r: int | None = None,
             p_c: int | None = None, n_rhs: int = 4,
             seed: int = 0,
             tiles: bool | tuple[int, int] | None = None,
             lint: bool = False) -> TuneResult:
    """Pick the fastest precision config of ``op`` meeting ``tol``.

    ``op`` should be the *highest-precision* operator (its stored Fourier
    blocks are recast down per candidate; upcasting cannot restore lost
    bits).  ``ladder`` defaults to ("d","s") when the operator is
    double-based and its backend runs f64, ("s","h") otherwise (always on
    a TPU).  ``variant`` may also be ``"gram"``:
    the fused parameter-space Gram pipeline (Hessian actions / CGNR's
    F*F), pruned with its own eq.-(6) factors (doubled transform terms,
    squared condition number — see ``core.error_model.phase_factors``).  ``slack`` widens the model-prune
    cutoff to absorb calibration error; every kept candidate is still
    rechecked against its *measured* error before selection, so slack
    only trades pruning aggressiveness, never correctness of the final
    config.  Pass ``constants`` to skip probe calibration and prune with
    the given eq.-(6) constants directly.

    ``tiles`` enables tile-centric refinement (DESIGN.md §8): after the
    uniform frontier search, each frontier config gets a per-tile
    precision map derived from F_hat's block norms (``True`` = a 2x2
    grid, or pass an explicit ``(R_tiles, C_tiles)``), and the mixed-tile
    candidates whose *measured* error still meets ``tol`` join the timed
    set — on a backend whose :class:`repro.backend.BackendSpec` gates
    tile precision off, refinement is skipped (the uniform search is
    unchanged).  Tile-enabled tunes cache under a ``;tiles=RxC`` key.

    ``lint=True`` pre-flights every config that is about to be *timed*
    (the baseline and each frontier survivor) through the static
    analyzer (:func:`repro.analysis.lint_operator` — abstract tracing,
    nothing executes) and raises
    :class:`repro.analysis.PlanLintError` on any error-severity
    finding, so a contract-violating lowering fails in milliseconds
    instead of polluting the timed record set.

    Persistence is opt-in: pass ``cache`` (a :class:`TuningCache`) or
    ``cache_path``; hits answer any tolerance from stored measurements.
    A cached answer is optimal w.r.t. the *cached* record set (like the
    exhaustive sweep, the baseline's error-vs-itself is 0, so some config
    always qualifies); re-tune with a fresh cache to re-measure a
    tolerance far from the one originally tuned for.

    The operator's pipelined-collective setting (``ExecOpts.overlap``,
    DESIGN.md §9) changes every candidate's measured *time* but none of
    the measured *errors* (the chunked schedule is row-partition-exact),
    so it needs no eq.-(6) term — but cached entries key on it
    (``;ov=`` detail): timings taken under one schedule never answer a
    query for another, while the error model stays schedule-blind.
    """
    if ladder is None:
        full = op.opts.resolve().spec.ladder()        # highest first
        ladder = full[:2] if op.precision.highest() == full[0] \
            else full[-2:]
    ladder = tuple(ladder)
    adjoint = variant in _ADJOINT_VARIANTS
    model_variant = variant if variant == "gram" else None
    # the comm-precision knob lives on the operator; the model prices it
    # and the cache keys on it (a reduced-precision-comm tune must never
    # answer a full-precision query).  Grid defaults come off the op's
    # mesh; explicit p_r/p_c — including an explicit (1, 1) — override.
    comm_level = getattr(op, "comm_level", None)
    if (p_r is None or p_c is None) \
            and getattr(op, "mesh", None) is not None:
        grid = op.grid_shape()
        p_r = grid[0] if p_r is None else p_r
        p_c = grid[1] if p_c is None else p_c
    p_r, p_c = p_r or 1, p_c or 1
    lattice = list(all_configs(ladder))
    top = max_level(ladder)
    base_cfg = PrecisionConfig(*([top] * 5))
    tile_shape = (2, 2) if tiles is True else (tuple(tiles) if tiles else None)
    if tile_shape is not None and \
            not op.opts.resolve().spec.tile_precision:
        tile_shape = None          # backend gates tile precision off

    if cache is None and cache_path is not None:
        cache = TuningCache(cache_path)
    key = None
    if cache is not None:
        n_rhs_eff = (v.shape[-1] if v is not None else n_rhs) \
            if variant in ("matmat", "rmatmat") else None
        if v is not None:
            digest = hashlib.sha1(np.ascontiguousarray(
                np.asarray(v)).tobytes()).hexdigest()[:12]
            input_tag = f"v{digest}"
        else:
            input_tag = f"seed{seed}"
        # an explicit harness carries its own mode/timer; key must
        # reflect what is actually measured
        key_mode = harness.mode if harness is not None else mode
        synthetic = (harness.timer if harness is not None else timer) \
            is not None
        key = CacheKey.for_operator(op, ladder, variant, mode=key_mode,
                                    n_rhs=n_rhs_eff, input_tag=input_tag,
                                    synthetic_timer=synthetic,
                                    comm_level=comm_level, tiles=tile_shape)
    if cache is not None:
        cached = cache.lookup_config(key, tol)
        if cached is not None:
            recs = cache.records(key)
            rec = next(r for r in recs if r.config == cached)
            entry = cache.get(key)
            return TuneResult(config=cached, op=op.with_precision(cached),
                              record=rec, records=recs,
                              front=pareto_front(recs), bounds={},
                              errors=dict(entry.get("errors", {})),
                              constants={}, n_timed=0,
                              n_lattice=len(lattice), from_cache=True,
                              cache_key=key)

    if harness is None:
        harness = TimingHarness(repeats=repeats, warmup=warmup, mode=mode,
                                timer=timer)
    if v is None:
        v = default_input(op, variant, n_rhs=n_rhs, seed=seed)

    # 1. baseline: timing reference + error reference + fallback selection.
    base_op = op.with_precision(base_cfg)
    if lint:
        _assert_op_clean(base_op)
    ref_out, base_t = harness.time(base_op, v, variant)
    errors: dict[str, float] = {base_cfg.to_string(): 0.0}

    def error_of(cfg: PrecisionConfig) -> float:
        s = cfg.to_string()
        if s not in errors:
            out = harness.run_once(op.with_precision(cfg), v, variant)
            errors[s] = rel_l2(out, ref_out)
        return errors[s]

    # 2. calibrate the eq.-(6) constants from single-phase probes.
    if constants is None:
        probe_errs: dict[str, dict[str, float]] = {}
        for phase, lvl, cfg in probe_configs(ladder):
            probe_errs.setdefault(phase, {})[lvl] = error_of(cfg)
        constants = calibrate_constants(probe_errs, op.N_t, op.N_d, op.N_m,
                                        p_r=p_r, p_c=p_c, adjoint=adjoint,
                                        variant=model_variant)

    # 3. model prune over the full lattice.
    report = prune_lattice(lattice, tol, op.N_t, op.N_d, op.N_m, p_r=p_r,
                           p_c=p_c, adjoint=adjoint, variant=model_variant,
                           kappa=kappa, input_level=top, constants=constants,
                           slack=slack, comm_level=comm_level)

    # 4. frontier search: cheapest-first, dominated-by-measured-feasible
    #    skipped, measured error decides the rest.
    candidates = sorted((c for c in report.model_feasible if c != base_cfg),
                        key=lambda c: (c.cost_rank(),
                                       report.bounds[c.to_string()],
                                       c.to_string()))
    frontier: list[PrecisionConfig] = []
    for cfg in candidates:
        if any(config_le(f, cfg) for f in frontier):
            continue
        if error_of(cfg) <= tol:
            frontier.append(cfg)

    # 4b. tile refinement: derive a block-norm tile map per frontier
    #     config (eq.-(6) tile-aware budget, calibrated constants); a
    #     mixed-tile candidate joins the timed set only if its *measured*
    #     error still meets tol.  derive returns None when the pruner's
    #     budget math provably rejects a map (no cell can drop) — then
    #     the uniform frontier stands.
    if tile_shape is not None:
        from repro.core.error_model import relative_error_bound
        tiled: list[PrecisionConfig] = []
        for cfg in frontier:
            tm, t_w = tile_map_for_operator(
                op, cfg, tol, shape=tile_shape, p_r=p_r, p_c=p_c,
                adjoint=adjoint, kappa=kappa, input_level=top,
                constants=constants, variant=model_variant,
                comm_level=comm_level)
            if tm is None:
                continue
            tcfg = cfg.replace(tiles=tm)
            report.bounds[tcfg.to_string()] = relative_error_bound(
                tcfg, op.N_t, op.N_d, op.N_m, p_r=p_r, p_c=p_c,
                adjoint=adjoint, variant=model_variant, kappa=kappa,
                input_level=top, constants=constants,
                comm_level=comm_level, tile_weights=t_w)
            if error_of(tcfg) <= tol:
                tiled.append(tcfg)
        frontier += tiled

    # 5. time baseline + frontier only; select exactly as optimal_config
    #    would over the exhaustive sweep.
    records = [ConfigRecord(base_cfg, 0.0, base_t, 1.0)]
    for cfg in frontier:
        cand = op.with_precision(cfg)
        if lint:
            _assert_op_clean(cand)
        _, t = harness.time(cand, v, variant)
        records.append(ConfigRecord(cfg, errors[cfg.to_string()], t,
                                    base_t / t))
    best = optimal_config(records, tol)
    front = pareto_front(records)

    result = TuneResult(config=best.config, op=op.with_precision(best.config),
                        record=best, records=records, front=front,
                        bounds=report.bounds, errors=dict(errors),
                        constants=dict(constants),
                        n_timed=len(records), n_lattice=len(lattice),
                        cache_key=key)

    # 6. persist.
    if cache is not None:
        cache.put(key, records=records, front=front, chosen=best.config,
                  tol=tol, baseline=base_cfg, n_lattice=len(lattice),
                  errors=errors)
        cache.save()
    return result
