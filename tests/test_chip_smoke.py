"""The checks of ``chip_smoke.py`` on the CPU: its host reference agrees
with the dense operator, its error check rejects wrong outputs that the
eq.-(6) bound alone would pass, and without a TPU it reports no result."""

import importlib.util
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import PrecisionConfig, random_block_column
from repro.core.error_model import relative_error_bound
from repro.core.toeplitz import dense_matvec, dense_rmatvec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE_PATH = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def problem():
    N_t, N_d, N_m = 12, 3, 7
    F = np.asarray(random_block_column(jax.random.PRNGKey(5), N_t, N_d, N_m))
    rng = np.random.default_rng(5)
    return F, rng.standard_normal((N_m, N_t)), rng.standard_normal((N_d, N_t))


@pytest.mark.parametrize("adjoint", [False, True], ids=["matvec", "rmatvec"])
def test_host_reference_matches_dense(smoke, problem, adjoint):
    F, m, d = problem
    steps = (0, 1, 5, 10, 11)
    got = smoke.conv_steps(F, d if adjoint else m, steps, adjoint=adjoint)
    dense = dense_rmatvec(F, d) if adjoint else dense_matvec(F, m)
    np.testing.assert_allclose(got, np.asarray(dense)[:, list(steps)],
                               rtol=1e-12, atol=1e-12)


def test_host_gram_reference_matches_dense(smoke, problem):
    F, m, _ = problem
    steps = (8, 10, 11)
    want = np.asarray(dense_rmatvec(F, dense_matvec(F, m)))[:, list(steps)]
    np.testing.assert_allclose(smoke.gram_steps(F, m, steps), want,
                               rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("planted", ["zeroed", "negated", "halved"])
def test_error_check_rejects_wrong_bf16_output(smoke, problem, planted):
    """At the paper shape the eq.-(6) bound of ``shhss`` matvec is above
    1, so it passes a zeroed or negated output; the ceiling does not."""
    F, m, _ = problem
    ref = smoke.conv_steps(F, m, smoke.STEPS[:2])
    got = {"zeroed": 0 * ref, "negated": -ref, "halved": ref / 2}[planted]
    bound = relative_error_bound(PrecisionConfig.from_string("shhss"),
                                 smoke.N_T, smoke.N_D, smoke.N_M,
                                 variant="matvec", input_level="s")
    err = smoke.rel_err(got, ref)
    assert err <= bound                 # the bound alone would let it by
    with pytest.raises(smoke.SmokeFailure, match="ceiling"):
        smoke.check_error("matvec shhss", err, bound)
    # bf16 rounding of the output is well inside both
    q = ref.astype(np.float32).astype(jax.numpy.bfloat16).astype(np.float64)
    smoke.check_error("matvec shhss", smoke.rel_err(q, ref), bound)


def test_smoke_without_tpu_reports_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_BACKEND", None)
    proc = subprocess.run([sys.executable, SMOKE_PATH], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr
