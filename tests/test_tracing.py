"""The program's own instrumentation in a profile: stage scopes in the plan
executor's HLO metadata, and the Krylov loop's host spans."""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FFTMatvec, stage_counts
from repro.core.pipeline import STAGE_KINDS
from repro.solvers import cg_normal_equations

N_T, N_D, N_M = 32, 8, 64
# what a profile times: every op that computes or moves data
COMPUTING = {"fusion", "custom-call", "fft", "dot", "convolution", "pad",
             "slice", "transpose", "copy"}
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([a-z][\w\-]*)\(")
_SCOPE = re.compile(r'op_name="[^"]*?/fftmatvec/(\w+)')


def _operator() -> FFTMatvec:
    F = np.random.default_rng(1).standard_normal((N_T, N_D, N_M))
    return FFTMatvec.from_block_column(jnp.asarray(F))


@pytest.mark.parametrize("call", ["matvec", "rmatvec", "gram"])
def test_every_computing_op_carries_its_stage_scope(call):
    """On the CPU's XLA path; the chip's Pallas path is compiled in
    ``test_tpu_compile.py``."""
    op = _operator()
    gram = op.gram(space="parameter", mode="exact")
    fn, target, v, plan = {
        "matvec": (lambda o, v: o.matvec(v), op, (N_M, N_T), op.plan()),
        "rmatvec": (lambda o, v: o.rmatvec(v), op, (N_D, N_T),
                    op.plan(adjoint=True)),
        "gram": (lambda g, v: g.apply(v), gram, (N_M, N_T), gram.plan()),
    }[call]
    hlo = jax.jit(fn).lower(target, jnp.ones(v)).compile().as_text()
    kinds = set(_SCOPE.findall(hlo))
    assert set(stage_counts(plan)) <= kinds <= set(STAGE_KINDS), kinds
    bare = [line.strip()[:120] for line in hlo.splitlines()
            if (m := _INSTR.match(line)) and m.group(2) in COMPUTING
            and not _SCOPE.search(line)]
    assert not bare


def _solve(op, d):
    return cg_normal_equations(op, d, tol=1e-8, maxiter=40)


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name in ("pcg.solve", "pcg.sync")]


def test_solver_spans_under_the_profiler(tmp_path):
    op = _operator()
    d = op.matvec(jnp.asarray(
        np.random.default_rng(2).standard_normal((N_M, N_T))))
    plain = _solve(op, d)
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = _solve(op, d)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    solves = [s for s in spans if s[0] == "pcg.solve"]
    syncs = [s for s in spans if s[0] == "pcg.sync"]
    assert len(solves) == 1
    assert traced.n_iters > 1
    assert len(syncs) == traced.n_iters + 2
    _, s0, s1 = solves[0]
    assert all(s0 <= a and b <= s1 for _, a, b in syncs)
    # the spans change nothing the solver computes
    assert plain.n_iters == traced.n_iters
    np.testing.assert_array_equal(np.asarray(plain.x), np.asarray(traced.x))
