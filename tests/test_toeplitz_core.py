"""Core FFTMatvec correctness: FFT pipeline vs dense reference, adjointness,
circulant embedding, and the paper's heat-equation p2o construction."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backend import DispatchTable
from repro.core import (ExecOpts, FFTMatvec, PrecisionConfig,
                        dense_from_block_column, dense_matvec, dense_rmatvec,
                        heat_equation_p2o, random_block_column, rel_l2)
from repro.core import toeplitz
from repro.core.toeplitz import fourier_block_column

PALLAS_INTERPRET = ExecOpts(backend="cpu-interpret",
                            dispatch=DispatchTable(force="pallas"),
                            fuse_pad_cast=True, block_n=128)


@pytest.mark.parametrize("Nt,Nd,Nm", [(4, 3, 5), (16, 2, 8), (13, 5, 7),
                                      (32, 4, 40)])
def test_matvec_matches_dense(Nt, Nd, Nm):
    F_col = random_block_column(jax.random.PRNGKey(0), Nt, Nd, Nm,
                                dtype=jnp.float64)
    m = jax.random.normal(jax.random.PRNGKey(1), (Nm, Nt), dtype=jnp.float64)
    op = FFTMatvec.from_block_column(F_col)
    assert rel_l2(op.matvec(m), dense_matvec(F_col, m)) < 1e-13


@pytest.mark.parametrize("Nt,Nd,Nm", [(8, 3, 5), (16, 2, 8)])
def test_rmatvec_matches_dense(Nt, Nd, Nm):
    F_col = random_block_column(jax.random.PRNGKey(0), Nt, Nd, Nm,
                                dtype=jnp.float64)
    d = jax.random.normal(jax.random.PRNGKey(1), (Nd, Nt), dtype=jnp.float64)
    op = FFTMatvec.from_block_column(F_col)
    assert rel_l2(op.rmatvec(d), dense_rmatvec(F_col, d)) < 1e-13


def test_dense_materialization_consistent():
    Nt, Nd, Nm = 6, 2, 3
    F_col = random_block_column(jax.random.PRNGKey(2), Nt, Nd, Nm,
                                dtype=jnp.float64)
    F = dense_from_block_column(F_col)
    m = jax.random.normal(jax.random.PRNGKey(3), (Nm, Nt), dtype=jnp.float64)
    # SOTI -> stacked block vector
    m_flat = m.T.reshape(-1)
    d_flat = F @ m_flat
    d = d_flat.reshape(Nt, Nd).T
    assert rel_l2(dense_matvec(F_col, m), d) < 1e-13


def test_adjoint_property():
    Nt, Nd, Nm = 12, 4, 9
    F_col = random_block_column(jax.random.PRNGKey(4), Nt, Nd, Nm,
                                dtype=jnp.float64)
    op = FFTMatvec.from_block_column(F_col)
    m = jax.random.normal(jax.random.PRNGKey(5), (Nm, Nt), dtype=jnp.float64)
    d = jax.random.normal(jax.random.PRNGKey(6), (Nd, Nt), dtype=jnp.float64)
    lhs = jnp.vdot(op.matvec(m), d)
    rhs = jnp.vdot(m, op.rmatvec(d))
    assert abs(lhs - rhs) / abs(lhs) < 1e-13


def test_pallas_path_matches_xla():
    Nt, Nd, Nm = 16, 4, 64
    F_col = random_block_column(jax.random.PRNGKey(7), Nt, Nd, Nm)
    m = jax.random.normal(jax.random.PRNGKey(8), (Nm, Nt), dtype=jnp.float32)
    d = jax.random.normal(jax.random.PRNGKey(9), (Nd, Nt), dtype=jnp.float32)
    prec = PrecisionConfig.from_string("sssss")
    base = FFTMatvec.from_block_column(F_col, precision=prec)
    pal = FFTMatvec.from_block_column(
        F_col, precision=prec,
        opts=PALLAS_INTERPRET)
    assert rel_l2(pal.matvec(m), base.matvec(m)) < 1e-5
    assert rel_l2(pal.rmatvec(d), base.rmatvec(d)) < 1e-5


def test_heat_equation_p2o_is_lti():
    """The heat-equation p2o block column must reproduce the actual PDE
    solve: d(t) for a given source history == F m."""
    Nt, Nd, Nm = 12, 3, 24
    F_col = heat_equation_p2o(Nt, Nd, Nm)
    op = FFTMatvec.from_block_column(F_col)
    m = jax.random.normal(jax.random.PRNGKey(10), (Nm, Nt), dtype=jnp.float64)
    ref = dense_matvec(F_col, m)
    assert rel_l2(op.matvec(m), ref) < 1e-12
    # impulse response decays (diffusion smooths), so kappa is moderate
    assert jnp.linalg.norm(F_col[-1]) <= jnp.linalg.norm(F_col[0]) * 10


@pytest.mark.parametrize("chunk", [1, 3, 7, 17])
def test_chunked_setup_matches_whole_rfft(chunk, monkeypatch):
    """The set-up streams its FFT over chunks of N_m columns; chunking
    (with or without a ragged tail) must not change F_hat."""
    Nt, Nd, Nm = 9, 3, 17
    monkeypatch.setattr(toeplitz, "SETUP_CHUNK_ELEMS", chunk * Nt * Nd)
    F_col = random_block_column(jax.random.PRNGKey(11), Nt, Nd, Nm,
                                dtype=jnp.float64)
    F = np.asarray(F_col)
    want = np.fft.rfft(np.concatenate([F, np.zeros_like(F)]), axis=0)
    F_re, F_im = fourier_block_column(F_col)
    assert F_re.shape == (Nt + 1, Nd, Nm) and F_re.dtype == jnp.float64
    np.testing.assert_allclose(np.asarray(F_re), want.real, atol=1e-12)
    np.testing.assert_allclose(np.asarray(F_im), want.imag, atol=1e-12)
    # stored at a lower rung: the cast of the same transform
    s_re, _ = fourier_block_column(F_col, jnp.float32)
    assert s_re.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(s_re),
                                  np.asarray(F_re).astype(np.float32))


@pytest.mark.parametrize("cfg", ["ddddd", "sssds", "dssdd"])
def test_d_phase_on_tpu_backend_raises(cfg):
    """A TPU has no f64 FFT and no f64 Pallas: a plan with a "d" phase is
    refused on tpu-pallas, never run in f32 in silence.  Its set-up still
    builds (at f32), and the s/h ladder runs."""
    from repro.backend import UnsupportedOnBackend
    Nt, Nd, Nm = 8, 2, 16
    F_col = random_block_column(jax.random.PRNGKey(12), Nt, Nd, Nm)
    # XLA lowerings throughout: the Mosaic kernels cannot run on the CPU
    opts = ExecOpts(backend="tpu-pallas",
                    dispatch=DispatchTable(force="xla"), fuse_pad_cast=False)
    op = FFTMatvec.from_block_column(
        F_col, precision=PrecisionConfig.from_string(cfg), opts=opts)
    m = jnp.ones((Nm, Nt), jnp.float32)
    with pytest.raises(UnsupportedOnBackend, match="no f64"):
        op.matvec(m)
    ok = op.with_precision(PrecisionConfig.from_string("sssss"))
    assert rel_l2(ok.matvec(m), dense_matvec(F_col, m)) < 1e-5


def test_jitted_takes_operator_planes_as_arguments():
    """The operator is a pytree of its F_hat planes: its jitted entry
    points take the planes as program inputs, never as constants baked
    into the program (GBs at the paper shape)."""
    Nt, Nd, Nm = 8, 2, 6
    F_col = random_block_column(jax.random.PRNGKey(13), Nt, Nd, Nm,
                                dtype=jnp.float64)
    op = FFTMatvec.from_block_column(F_col)
    assert jax.tree_util.tree_leaves(op) == [op.F_hat_re, op.F_hat_im]
    m = jax.random.normal(jax.random.PRNGKey(14), (Nm, Nt), jnp.float64)
    d = jax.random.normal(jax.random.PRNGKey(15), (Nd, Nt), jnp.float64)
    mv, rmv = op.jitted()
    mm, _ = op.jitted_block()
    for fn, x, want in ((mv, m, op.matvec(m)), (rmv, d, op.rmatvec(d)),
                        (mm, m, op.matvec(m)),
                        (op.gram().jitted(), m, op.rmatvec(op.matvec(m)))):
        lowered = fn.func.lower(*fn.args, x)
        assert len(jax.tree_util.tree_leaves(lowered.args_info)) == 3
        assert rel_l2(fn(x), want) < 1e-13


def test_io_dtype_follows_highest_level():
    F_col = random_block_column(jax.random.PRNGKey(0), 8, 2, 4,
                                dtype=jnp.float64)
    m = jnp.ones((4, 8), jnp.float64)
    for s, dt in [("ddddd", jnp.float64), ("dssdd", jnp.float64),
                  ("sssss", jnp.float32), ("shhss", jnp.float32),
                  ("hhhhh", jnp.bfloat16)]:
        op = FFTMatvec.from_block_column(
            F_col, precision=PrecisionConfig.from_string(s))
        assert op.matvec(m).dtype == dt, s


# ---------------------------------------------------------------------------
# Multi-RHS operator paths (matmat / rmatmat)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Nt,Nd,Nm,S", [(8, 3, 5, 1), (16, 2, 8, 4),
                                        (13, 5, 7, 3)])
def test_matmat_matches_stacked_matvec(Nt, Nd, Nm, S):
    F_col = random_block_column(jax.random.PRNGKey(20), Nt, Nd, Nm,
                                dtype=jnp.float64)
    op = FFTMatvec.from_block_column(F_col)
    M = jax.random.normal(jax.random.PRNGKey(21), (Nm, Nt, S), jnp.float64)
    want = jnp.stack([op.matvec(M[:, :, s]) for s in range(S)], axis=-1)
    assert rel_l2(op.matmat(M), want) < 1e-13
    D = jax.random.normal(jax.random.PRNGKey(22), (Nd, Nt, S), jnp.float64)
    want_r = jnp.stack([op.rmatvec(D[:, :, s]) for s in range(S)], axis=-1)
    assert rel_l2(op.rmatmat(D), want_r) < 1e-13


def test_matmat_2d_input_is_matvec():
    """matvec is exactly the S = 1 special case of matmat."""
    F_col = random_block_column(jax.random.PRNGKey(23), 12, 3, 6,
                                dtype=jnp.float64)
    op = FFTMatvec.from_block_column(F_col)
    m = jax.random.normal(jax.random.PRNGKey(24), (6, 12), jnp.float64)
    out = op.matmat(m)
    assert out.shape == (3, 12)
    assert rel_l2(out, op.matvec(m)) < 1e-14


def test_matmat_adjoint_property_per_column():
    Nt, Nd, Nm, S = 12, 4, 9, 3
    F_col = random_block_column(jax.random.PRNGKey(25), Nt, Nd, Nm,
                                dtype=jnp.float64)
    op = FFTMatvec.from_block_column(F_col)
    M = jax.random.normal(jax.random.PRNGKey(26), (Nm, Nt, S), jnp.float64)
    D = jax.random.normal(jax.random.PRNGKey(27), (Nd, Nt, S), jnp.float64)
    FM, FtD = op.matmat(M), op.rmatmat(D)
    for s in range(S):
        lhs = jnp.vdot(FM[:, :, s], D[:, :, s])
        rhs = jnp.vdot(M[:, :, s], FtD[:, :, s])
        assert abs(lhs - rhs) / abs(lhs) < 1e-13


def test_matmat_pallas_path_matches_xla():
    Nt, Nd, Nm, S = 16, 4, 64, 5
    F_col = random_block_column(jax.random.PRNGKey(28), Nt, Nd, Nm)
    M = jax.random.normal(jax.random.PRNGKey(29), (Nm, Nt, S), jnp.float32)
    D = jax.random.normal(jax.random.PRNGKey(30), (Nd, Nt, S), jnp.float32)
    prec = PrecisionConfig.from_string("sssss")
    base = FFTMatvec.from_block_column(F_col, precision=prec)
    pal = FFTMatvec.from_block_column(
        F_col, precision=prec,
        opts=dataclasses.replace(PALLAS_INTERPRET, block_s=8))
    assert rel_l2(pal.matmat(M), base.matmat(M)) < 1e-5
    assert rel_l2(pal.rmatmat(D), base.rmatmat(D)) < 1e-5


def test_matmat_io_dtype_follows_highest_level():
    F_col = random_block_column(jax.random.PRNGKey(31), 8, 2, 4,
                                dtype=jnp.float64)
    M = jnp.ones((4, 8, 2), jnp.float64)
    for s, dt in [("ddddd", jnp.float64), ("sssss", jnp.float32),
                  ("hhhhh", jnp.bfloat16)]:
        op = FFTMatvec.from_block_column(
            F_col, precision=PrecisionConfig.from_string(s))
        assert op.matmat(M).dtype == dt, s
