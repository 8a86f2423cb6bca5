"""The float64 host reference: the exact operator against a direct block
convolution, its adjoint, and the operator at a stated precision."""

import ml_dtypes
import numpy as np
import pytest

from bench_tiny import harness

reference = harness().reference
N_t, N_d, N_m, S = 24, 6, 40, 2


@pytest.fixture(scope="module")
def F():
    rng = np.random.default_rng(7)
    return rng.standard_normal((N_t, N_d, N_m)) * 0.7 ** np.arange(N_t)[:, None, None]


def direct(F, m):
    """y_t = sum_{k<=t} F_k m_{t-k}, one block at a time, no FFT."""
    y = np.zeros((N_d, N_t, m.shape[2]))
    for t in range(N_t):
        for k in range(t + 1):
            y[:, t] += F[k] @ m[:, t - k]
    return y


def test_exact_product_is_the_block_convolution(F):
    m = np.random.default_rng(1).standard_normal((N_m, N_t, S))
    got = reference.HostOperator(F).matvec(m)
    np.testing.assert_allclose(got, direct(F, m), rtol=0, atol=1e-12)


def test_adjoint_is_the_transpose(F):
    rng = np.random.default_rng(2)
    m, d = rng.standard_normal((N_m, N_t, S)), rng.standard_normal((N_d, N_t, S))
    op = reference.HostOperator(F)
    assert np.sum(op.matvec(m) * d) == pytest.approx(np.sum(m * op.rmatvec(d)),
                                                     rel=1e-12)


def test_rounding_to_a_rung():
    a = np.array([1 + 2.0 ** -8 + 2.0 ** -20, -3.3, 1e-3 + 2j])
    np.testing.assert_array_equal(
        reference.round_to(a.real, "h"),
        a.real.astype(ml_dtypes.bfloat16).astype(np.float64))
    assert reference.round_to(a, "h")[0] == 1 + 2.0 ** -7
    assert reference.round_to(a, "h").imag[2] == 2.0
    assert reference.round_to(a, "d") is a
    assert reference.lower("s", "h") == reference.lower("h", "s") == "h"


@pytest.mark.parametrize("lower_path", ["shhhs", "shhhh", "hhhss", "hhhhh"])
def test_each_added_rounding_moves_the_stated_product(F, lower_path):
    """At a stated precision the product moves from the exact one by its
    own bf16 roundings; a lower string moves it again by a whole one."""
    m = np.random.default_rng(3).standard_normal((N_m, N_t, S))
    exact = reference.HostOperator(F).matvec(m)
    assert reference.rel_err(reference.HostOperator(F, "ddddd").matvec(m),
                             exact) < 1e-14
    assert reference.rel_err(reference.HostOperator(F, "sssss").matvec(m),
                             exact) < 1e-6
    stated = reference.HostOperator(F, "shhss").matvec(m)
    assert 3e-4 < reference.rel_err(stated, exact) < 5e-3
    assert reference.rel_err(
        reference.HostOperator(F, lower_path).matvec(m), stated) > 3e-4
