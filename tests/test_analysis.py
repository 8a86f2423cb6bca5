"""Static-analysis subsystem tests (DESIGN.md §11).

Two halves:

* **clean**: the paper-shape plan families lint with zero findings on
  every registered backend — the CI `analysis` job's contract, asserted
  here at smoke dims so the suite stays fast;
* **seeded**: each deliberately-broken lowering (a downgrading output
  stage, a collective that keeps the comm dtype, a non-Hamiltonian ring
  permutation, a low-precision accumulator, an unhashable static leaf,
  an unstable jit key) fires *exactly* its intended rule — the linter's
  findings are pinned to the bug classes they were built for, not just
  "something complains".

Seeds monkeypatch the executor's dispatch points
(``pipeline._STAGE_IMPLS``, ``pipeline.ring_permutation``) so the
*plans stay valid* — the linter sees a well-formed plan whose lowering
misbehaves, which is precisely the silent-failure shape the passes
exist to catch.
"""

import dataclasses
import itertools
import json

import jax
import jax.numpy as jnp
import pytest

from repro import analysis
from repro.analysis import cli as analysis_cli
from repro.backend import known_backends, resolve_backend
from repro.core import (ExecOpts, FFTMatvec, PrecisionConfig, gram_plan,
                        matvec_plan, random_block_column)
from repro.core import pipeline
from repro.core import precision as prec
from repro.core.timing import TimingHarness

N_T, N_D, N_M = 16, 4, 32
DIMS = dict(N_t=N_T, N_d=N_D, N_m=N_M)
OPTS = ExecOpts(backend="xla-ref")


def fired(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# Clean plans: zero findings, every registered backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", known_backends())
@pytest.mark.parametrize("cfg_s", ["dssdd", "sssss"])
def test_clean_plans_every_backend(backend, cfg_s):
    cfg = PrecisionConfig.from_string(cfg_s)
    opts = ExecOpts(backend=backend)
    if not set(cfg.levels()) <= set(resolve_backend(backend).ladder()):
        # no f64 datapath (a TPU): the plan is refused, and every rule
        # says so instead of linting it clean
        found = analysis.lint_plan(matvec_plan(cfg), opts, **DIMS)
        assert found
        assert {f.detail for f in found} == {"UnsupportedOnBackend"}
        return
    analysis.assert_plan_clean(matvec_plan(cfg), opts, **DIMS)
    analysis.assert_plan_clean(
        matvec_plan(cfg, psum_axis="col", collective="ring",
                    psum_groups=(4,)), opts, **DIMS)


def test_clean_gram_mesh_plan():
    plan = gram_plan(PrecisionConfig.from_string("ddddd"),
                     mid_psum_axis="col", psum_axis="row",
                     mid_psum_groups=(4,), psum_groups=(2,),
                     collective="hierarchical")
    analysis.assert_plan_clean(plan, OPTS, **DIMS)


def test_lint_operator_clean_both_directions():
    F_col = random_block_column(jax.random.PRNGKey(1), N_T, N_D, N_M)
    op = FFTMatvec.from_block_column(
        F_col, PrecisionConfig.from_string("dssdd"), backend="xla-ref")
    assert analysis.lint_operator(op) == []
    assert analysis.lint_operator(op.gram(mode="circulant")) == []


# ---------------------------------------------------------------------------
# Seeded violations: each fires exactly its intended rule
# ---------------------------------------------------------------------------

def test_seeded_output_downgrade_fires(monkeypatch):
    # the PR-5 bug class: a stage that silently hands f32 downstream of
    # a plan whose last data stage declares f64
    orig = pipeline._STAGE_IMPLS["unpad"]

    def degraded(stage, x, operands, N_t, S, opts):
        return orig(stage, x, operands, N_t, S, opts).astype(jnp.float32)

    monkeypatch.setitem(pipeline._STAGE_IMPLS, "unpad", degraded)
    plan = matvec_plan(PrecisionConfig.from_string("ddddd"))
    found = analysis.lint_plan(plan, OPTS, **DIMS)
    assert fired(found) == {"silent-output-downgrade"}
    assert all(f.severity == analysis.ERROR for f in found)
    with pytest.raises(AssertionError, match="silent-output-downgrade"):
        analysis.assert_plan_clean(plan, OPTS, **DIMS)


def test_seeded_unrestored_comm_fires(monkeypatch):
    # a reduced-precision collective that keeps the comm dtype instead
    # of restoring the carrier (DESIGN.md §5)
    def leaky(stage, x, operands, N_t, S, opts):
        comm_dt = prec.real_dtype(stage.level)
        planes = x if isinstance(x, tuple) else (x,)
        out = tuple(jax.lax.psum(p.astype(comm_dt), stage.axes)
                    for p in planes)
        return out if isinstance(x, tuple) else out[0]

    monkeypatch.setitem(pipeline._STAGE_IMPLS, "psum", leaky)
    plan = matvec_plan(PrecisionConfig.from_string("ddddd"),
                       psum_axis="col", psum_groups=(4,), comm_level="s")
    found = analysis.lint_plan(plan, OPTS, **DIMS)
    # the root cause plus its downstream symptom: the collective is the
    # distributed matvec's final stage, so the unrestored comm dtype
    # necessarily reaches the output as well
    assert fired(found) == {"comm-restores-carrier",
                            "silent-output-downgrade"}
    assert all(f.severity == analysis.ERROR for f in found)
    # in isolation the contract rule pins the exact offending stage
    only = analysis.lint_plan(plan, OPTS, **DIMS,
                              names=("comm-restores-carrier",))
    assert len(only) == 1 and only[0].stage is not None


def test_seeded_invalid_ring_fires(monkeypatch):
    # pair-swap "ring": covers every rank once but splits the 4-group
    # into two disjoint 2-cycles — half the partials never meet
    monkeypatch.setattr(pipeline, "ring_permutation",
                        lambda g: tuple((i, i ^ 1) for i in range(g)))
    plan = matvec_plan(PrecisionConfig.from_string("sssss"),
                       psum_axis="col", collective="ring",
                       psum_groups=(4,))
    found = analysis.lint_plan(plan, OPTS, **DIMS)
    assert fired(found) == {"ring-permutation"}
    assert any("disjoint cycles" in f.message for f in found)


def test_seeded_low_accumulation_fires(monkeypatch):
    # gemv quietly contracts at f32 under a declared-f64 stage, then
    # casts back up — invisible at the output, visible to the pass
    orig = pipeline._STAGE_IMPLS["gemv"]

    def lowered(stage, x, operands, N_t, S, opts):
        out = orig(dataclasses.replace(stage, level="s"), x, operands,
                   N_t, S, opts)
        dt = prec.real_dtype(stage.level)
        if isinstance(out, tuple):
            return tuple(p.astype(dt) for p in out)
        return out.astype(dt)

    monkeypatch.setitem(pipeline._STAGE_IMPLS, "gemv", lowered)
    found = analysis.lint_plan(
        matvec_plan(PrecisionConfig.from_string("ddddd")), OPTS, **DIMS)
    assert fired(found) == {"accum-below-stage"}


def test_seeded_unhashable_stage_fires():
    plan = matvec_plan(PrecisionConfig.from_string("sssss"),
                       psum_axis="col", psum_groups=(4,))
    bad = tuple(dataclasses.replace(s, groups=[4])
                if s.kind == "gemv_psum" else s for s in plan)
    found = analysis.lint_plan(bad, OPTS, **DIMS)
    assert fired(found) == {"static-unhashable"}
    assert any("groups" in f.detail for f in found)


def test_seeded_fallback_collective_fires():
    # ring without static groups cannot build its schedule: the
    # structural rule flags the request and the executor's trace-time
    # fallback counter confirms the flat-psum lowering
    plan = matvec_plan(PrecisionConfig.from_string("sssss"),
                       psum_axis="col", collective="ring")
    found = analysis.lint_plan(plan, OPTS, **DIMS)
    assert fired(found) == {"collective-stage-valid", "collective-fallback"}
    assert all(f.severity == analysis.WARNING for f in found)


# ---------------------------------------------------------------------------
# Recompile hazards: the executed cross-check and the harness counters
# ---------------------------------------------------------------------------

def test_trace_stability_crosschecks_harness_counter():
    harness = TimingHarness(repeats=1, warmup=0)
    F_col = random_block_column(jax.random.PRNGKey(0), N_T, N_D, N_M)
    op = FFTMatvec.from_block_column(
        F_col, PrecisionConfig.from_string("sssss"), backend="xla-ref")
    fn = harness.callable_for(op, "matvec")
    x = jnp.ones((N_M, N_T), jnp.float32)
    assert analysis.trace_stability(fn, x, calls=3) == []
    # the linter's verdict and the harness's launch-count agree: one
    # trace total, every later identical call an executable-cache hit
    assert harness.n_traces == 1


def test_trace_stability_detects_unstable_static_key():
    class UnstableKey:
        _tick = itertools.count()

        def __eq__(self, other):
            return isinstance(other, UnstableKey)

        def __hash__(self):
            return next(self._tick)

    def f(x, mode):
        return x * 2.0

    found = analysis.trace_stability(f, jnp.ones((4,)), UnstableKey(),
                                     calls=3, static_argnums=(1,))
    assert fired(found) == {"retrace-on-identical-call"}


def test_autotune_lint_preflight(monkeypatch):
    from repro.tune import autotune

    F_col = random_block_column(jax.random.PRNGKey(2), N_T, N_D, N_M)
    op = FFTMatvec.from_block_column(F_col, backend="xla-ref")
    res = autotune(op, tol=1e-2, ladder=("d", "s"),
                   timer=lambda cfg, fn, arg: 1.0, lint=True)
    assert res.config is not None

    # a contract-violating lowering now fails the pre-flight before any
    # timing budget is spent on it
    orig = pipeline._STAGE_IMPLS["unpad"]

    def degraded(stage, x, operands, N_t, S, opts):
        return orig(stage, x, operands, N_t, S, opts).astype(jnp.float32)

    monkeypatch.setitem(pipeline._STAGE_IMPLS, "unpad", degraded)
    with pytest.raises(analysis.PlanLintError,
                       match="silent-output-downgrade"):
        autotune(op, tol=1e-2, ladder=("d", "s"),
                 timer=lambda cfg, fn, arg: 1.0, lint=True)


# ---------------------------------------------------------------------------
# Engine plumbing: lint_callable, registry, CLI
# ---------------------------------------------------------------------------

def test_lint_callable_allow_and_forbid():
    def f(a):
        return jnp.concatenate([a, a]).reshape(2, -1)

    ok = analysis.lint_callable(f, (jnp.ones((1, 3)),),
                                allowed={"concatenate", "reshape"})
    assert ok == []
    found = analysis.lint_callable(f, (jnp.ones((1, 3)),),
                                   forbidden={"concatenate"},
                                   name="no-concat")
    assert [g.rule for g in found] == ["no-concat"]


def test_rule_registry_and_catalog():
    cat = analysis.rule_catalog()
    assert {r.family for r in cat} == set(analysis.FAMILIES)
    names = [r.name for r in cat]
    assert len(names) == len(set(names))
    # family-major ordering, names sorted within each family
    order = [(analysis.FAMILIES.index(r.family), r.name) for r in cat]
    assert order == sorted(order)
    with pytest.raises(ValueError, match="duplicate"):
        analysis.rule(names[0], cat[0].family, "dup")(lambda ctx: [])
    with pytest.raises(ValueError, match="unknown rule family"):
        analysis.rule("x", "nonsense", "d")
    with pytest.raises(KeyError):
        analysis.all_rules(names=("no-such-rule",))


def test_rule_family_and_name_filters():
    plan = matvec_plan(PrecisionConfig.from_string("sssss"))
    assert analysis.lint_plan(plan, OPTS, **DIMS,
                              families=("recompile",)) == []
    assert analysis.lint_plan(plan, OPTS, **DIMS,
                              names=("silent-output-downgrade",)) == []


def test_cli_rules_listing(capsys):
    assert analysis_cli.main(["--rules"]) == 0
    text = capsys.readouterr().out
    assert "silent-output-downgrade" in text
    assert "[invariants]" in text


def test_cli_json_smoke(capsys):
    rc = analysis_cli.main(
        ["--smoke", "--backend", "xla-ref", "--config", "sssss",
         "--plan", "matvec", "--plan", "matvec-ring", "--json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert report["errors"] == 0 and report["warnings"] == 0
    assert {row["plan"] for row in report["rows"]} == \
        {"matvec", "matvec-ring"}


def test_cli_exits_nonzero_on_seeded_error(monkeypatch, capsys):
    orig = pipeline._STAGE_IMPLS["unpad"]

    def degraded(stage, x, operands, N_t, S, opts):
        return orig(stage, x, operands, N_t, S, opts).astype(jnp.float32)

    monkeypatch.setitem(pipeline._STAGE_IMPLS, "unpad", degraded)
    rc = analysis_cli.main(
        ["--smoke", "--backend", "xla-ref", "--config", "ddddd",
         "--plan", "matvec"])
    assert rc == 1
    assert "silent-output-downgrade" in capsys.readouterr().out
