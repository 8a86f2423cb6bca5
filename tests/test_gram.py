"""Stage-graph pipeline + fused Gram operator tests.

Covers the two acceptance properties of the Gram refactor: the exact-mode
``GramOperator.apply`` matches the composed ``rmatvec(matvec(v))`` to
roundoff, and the circulant mode provably executes HALF the FFT/IFFT and
reorder stages of the composed path (instrumented stage counts, not a
claim); plus the Gram kernel dispatch/oracle, the error-model gram
variant, the gram autotune variant, and the chunked Hessian assembly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backend import DispatchTable
from repro.core import (ExecOpts, FFTMatvec, GaussianInverseProblem,
                        GramOperator, PrecisionConfig, gram_plan,
                        matvec_plan, random_block_column,
                        random_unrepresentable, record_stages, rel_l2,
                        stage_counts)
from repro.core.error_model import phase_factors, relative_error_bound
from repro.core.pipeline import Stage
from repro.kernels import ops, ref


def make_op(Nt=16, Nd=3, Nm=7, prec="ddddd", seed=0, **opts):
    F_col = random_block_column(jax.random.PRNGKey(seed), Nt, Nd, Nm,
                                dtype=jnp.float64)
    return FFTMatvec.from_block_column(
        F_col, precision=PrecisionConfig.from_string(prec),
        opts=ExecOpts(**opts))


# ---------------------------------------------------------------------------
# Exact fused Gram == composed pipelines (the acceptance identity)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Nt,Nd,Nm", [(8, 3, 5), (16, 2, 8), (13, 5, 7)])
def test_gram_parameter_matches_composed(Nt, Nd, Nm):
    op = make_op(Nt, Nd, Nm)
    v = jax.random.normal(jax.random.PRNGKey(1), (Nm, Nt), jnp.float64)
    g = op.gram(space="parameter")
    assert rel_l2(g.apply(v), op.rmatvec(op.matvec(v))) < 1e-13


@pytest.mark.parametrize("Nt,Nd,Nm", [(8, 3, 5), (16, 2, 8)])
def test_gram_data_matches_composed(Nt, Nd, Nm):
    op = make_op(Nt, Nd, Nm)
    v = jax.random.normal(jax.random.PRNGKey(2), (Nd, Nt), jnp.float64)
    g = op.gram(space="data")
    assert rel_l2(g.apply(v), op.matvec(op.rmatvec(v))) < 1e-13


@pytest.mark.parametrize("S", [1, 3, 5])
def test_gram_multi_rhs_matches_composed(S):
    op = make_op()
    V = jax.random.normal(jax.random.PRNGKey(3), (op.N_m, op.N_t, S),
                          jnp.float64)
    g = op.gram()
    assert rel_l2(g.apply(V), op.rmatmat(op.matmat(V))) < 1e-13
    # 2-D input squeezes back like matmat
    out2d = g.apply(V[..., 0])
    assert out2d.shape == (op.N_m, op.N_t)
    assert rel_l2(out2d, g.apply(V)[..., 0]) < 1e-13


def test_gram_symmetric_psd():
    op = make_op()
    g = op.gram()
    v = jax.random.normal(jax.random.PRNGKey(4), (op.N_m, op.N_t),
                          jnp.float64)
    w = jax.random.normal(jax.random.PRNGKey(5), (op.N_m, op.N_t),
                          jnp.float64)
    # F*F is symmetric PSD; the fused pipeline must preserve that
    assert float(jnp.vdot(v, g.apply(v))) >= 0.0
    lhs, rhs = jnp.vdot(w, g.apply(v)), jnp.vdot(g.apply(w), v)
    assert abs(lhs - rhs) / abs(lhs) < 1e-12


def test_gram_jitted_and_pallas_interpret_path():
    op = make_op(16, 4, 64, prec="sssss", backend="cpu-interpret",
                 dispatch=DispatchTable(force="pallas"),
                 fuse_pad_cast=True, block_n=128)
    base = make_op(16, 4, 64, prec="sssss")
    v = jax.random.normal(jax.random.PRNGKey(6), (64, 16), jnp.float32)
    got = jax.block_until_ready(op.gram().jitted()(v))
    assert rel_l2(got, base.gram().apply(v)) < 1e-5


def test_gram_validation():
    op = make_op()
    with pytest.raises(ValueError, match="space"):
        op.gram(space="bogus")
    with pytest.raises(ValueError, match="mode"):
        op.gram(mode="bogus")
    with pytest.raises(ValueError):
        Stage("bogus", "d")
    with pytest.raises(ValueError):
        Stage("pad", "x")


# ---------------------------------------------------------------------------
# Circulant mode: periodic-Gram semantics + the stage-count halving
# ---------------------------------------------------------------------------

def test_circulant_gram_matches_spectral_oracle():
    """The circulant mode applies exactly the per-bin G_hat = F_hat^H F_hat
    operator (straight-line spectral reference, independent of the
    pipeline/kernels code paths)."""
    op = make_op()
    Nt, Nm = op.N_t, op.N_m
    v = jax.random.normal(jax.random.PRNGKey(7), (Nm, Nt), jnp.float64)
    got = op.gram(mode="circulant").apply(v)
    F_re, F_im = op.F_hat_re, op.F_hat_im
    F_hat = F_re + 1j * F_im
    G_hat = jnp.einsum("kdm,kdn->kmn", F_hat.conj(), F_hat)
    v_hat = jnp.fft.rfft(jnp.pad(v, ((0, 0), (0, Nt))), axis=-1)
    ref_out = jnp.fft.irfft(jnp.einsum("kmn,nk->mk", G_hat, v_hat),
                            n=2 * Nt, axis=-1)[:, :Nt]
    assert rel_l2(got, ref_out) < 1e-13


def test_circulant_gram_differs_from_composed_by_wrap_term():
    """The periodic Gram drops the inter-pipeline truncation: for a generic
    operator it must NOT equal the composed product (if it did, the exact
    mode's mask stage would be dead code)."""
    op = make_op()
    v = jax.random.normal(jax.random.PRNGKey(8), (op.N_m, op.N_t),
                          jnp.float64)
    diff = rel_l2(op.gram(mode="circulant").apply(v),
                  op.rmatvec(op.matvec(v)))
    assert diff > 1e-8


def test_circulant_gram_halves_fft_and_reorder_stages():
    """The acceptance accounting, from instrumented execution counts: one
    circulant Gram action runs HALF the FFT/IFFT and reorder stages of the
    composed rmatvec(matvec(v)) path (and the exact fused mode saves the
    pad/unpad round trip while keeping the transform count)."""
    op = make_op()
    v = jax.random.normal(jax.random.PRNGKey(9), (op.N_m, op.N_t),
                          jnp.float64)
    with record_stages() as composed:
        op.rmatvec(op.matvec(v))
    with record_stages() as circulant:
        op.gram(mode="circulant").apply(v)
    with record_stages() as exact:
        op.gram(mode="exact").apply(v)
    for kind in ("fft", "ifft", "reorder"):
        assert circulant[kind] * 2 == composed[kind], kind
    # exact mode: identical transform work, but the unpad+pad round trip
    # collapses into one mask stage (one pipeline, no io-dtype exit)
    assert exact["fft"] == composed["fft"]
    assert exact["pad"] + exact["unpad"] + exact["mask"] \
        < composed["pad"] + composed["unpad"]
    # the static plan census agrees with the runtime counts
    assert stage_counts(gram_plan(op.precision, mode="circulant")) \
        == circulant
    assert stage_counts(gram_plan(op.precision, mode="exact")) == exact
    two_pipelines = stage_counts(matvec_plan(op.precision))
    two_pipelines.update(stage_counts(matvec_plan(op.precision,
                                                  adjoint=True)))
    assert two_pipelines == composed


# ---------------------------------------------------------------------------
# Gram kernel dispatch + oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("space", ["parameter", "data"])
@pytest.mark.parametrize("B,m,n", [(3, 4, 16), (1, 2, 40), (2, 8, 8),
                                   (2, 8, 200),    # partial last tile
                                   (1, 8, 1100)])  # data space: 3 row tiles
def test_sbgemm_gram_pallas_matches_oracle(space, B, m, n):
    ks = jax.random.split(jax.random.PRNGKey(10), 2)
    A_re = jax.random.normal(ks[0], (B, m, n), jnp.float32)
    A_im = jax.random.normal(ks[1], (B, m, n), jnp.float32)
    got = ops.sbgemm_gram(A_re, A_im, space=space, backend="cpu-interpret",
                          dispatch=DispatchTable(force="pallas"),
                          block_n=128)
    want = ref.sbgemm_gram_ref(A_re, A_im, space)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("tiled", [False, True], ids=["plain", "tiled"])
def test_sbgemm_gram_kernel_row_tiles(tiled, monkeypatch):
    """The Gram kernels contract over m one row tile at a time (VMEM
    bounds the tile); a partial last row tile is zeroed past m."""
    from repro.kernels import sbgemv
    monkeypatch.setattr(sbgemv, "GRAM_BLOCK_M", 8)
    B, m, n = 2, 20, 16                   # row tiles of 8: 8, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(12), 2)
    A_re = jax.random.normal(ks[0], (B, m, n), jnp.float32)
    A_im = jax.random.normal(ks[1], (B, m, n), jnp.float32)
    if tiled:       # every column tile at "h": the bf16 round trip of A
        lvl = jnp.zeros((B, 1), jnp.int32)
        got = sbgemv.sbgemm_gram_tiled(A_re, A_im, lvl, block_n=n,
                                       interpret=True)
        q = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
        want = ref.sbgemm_gram_ref(q(A_re), q(A_im))
    else:
        got = sbgemv.sbgemm_gram_complex(A_re, A_im, block_n=n,
                                         interpret=True)
        want = ref.sbgemm_gram_ref(A_re, A_im)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


def test_sbgemm_gram_is_exactly_hermitian():
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    A_re = jax.random.normal(ks[0], (2, 3, 12), jnp.float64)
    A_im = jax.random.normal(ks[1], (2, 3, 12), jnp.float64)
    for space in ("parameter", "data"):
        G_re, G_im = ops.sbgemm_gram(A_re, A_im, space=space)
        np.testing.assert_array_equal(np.asarray(G_re),
                                      np.asarray(G_re.transpose(0, 2, 1)))
        np.testing.assert_array_equal(np.asarray(G_im),
                                      -np.asarray(G_im.transpose(0, 2, 1)))
        assert float(jnp.abs(jnp.diagonal(G_im, axis1=1, axis2=2)).max()) \
            == 0.0
    with pytest.raises(ValueError):
        ops.sbgemm_gram(A_re, A_im, space="bogus")


def test_gram_blocks_match_setup_spectrum():
    """Circulant blocks really are F_hat^H F_hat of the operator's stored
    spectrum (parameter) / F_hat F_hat^H (data)."""
    op = make_op(8, 2, 5)
    for space, dim in (("parameter", op.N_m), ("data", op.N_d)):
        g = op.gram(space=space, mode="circulant")
        F_hat = op.F_hat_re + 1j * op.F_hat_im
        want = (jnp.einsum("kdm,kdn->kmn", F_hat.conj(), F_hat)
                if space == "parameter"
                else jnp.einsum("kmn,kpn->kmp", F_hat, F_hat.conj()))
        assert g.G_hat_re.shape == (op.N_t + 1, dim, dim)
        assert rel_l2(g.G_hat_re, want.real) < 1e-14
        assert rel_l2(g.G_hat_im, want.imag) < 1e-13


# ---------------------------------------------------------------------------
# Error model: the gram variant of eq. (6)
# ---------------------------------------------------------------------------

def test_gram_phase_factors_double_the_transform_terms():
    f_mv = phase_factors(64, 8, 32)
    f_rmv = phase_factors(64, 8, 32, adjoint=True)
    f_g = phase_factors(64, 8, 32, variant="gram")
    assert f_g["fft"] == 2 * f_mv["fft"]
    assert f_g["ifft"] == 2 * f_mv["ifft"]
    assert f_g["gemv"] == f_mv["gemv"] + f_rmv["gemv"]
    # variant strings resolve like the adjoint flag
    assert phase_factors(64, 8, 32, variant="rmatvec") == f_rmv
    with pytest.raises(ValueError):
        phase_factors(64, 8, 32, variant="bogus")


def test_gram_bound_squares_kappa_and_dominates_matvec():
    cfg = PrecisionConfig.from_string("dssdd")
    b_mv = relative_error_bound(cfg, 64, 8, 32, kappa=10.0)
    b_g = relative_error_bound(cfg, 64, 8, 32, kappa=10.0, variant="gram")
    assert b_g > b_mv                       # chained passes can't be tighter
    b1 = relative_error_bound(cfg, 64, 8, 32, kappa=1.0, variant="gram")
    b10 = relative_error_bound(cfg, 64, 8, 32, kappa=10.0, variant="gram")
    assert b10 == pytest.approx(100.0 * b1)  # kappa enters squared


# ---------------------------------------------------------------------------
# Autotune over the gram lattice
# ---------------------------------------------------------------------------

def test_autotune_gram_variant():
    from repro.core import all_configs
    from repro.tune import CacheKey, autotune

    _cost = {"h": 1.0, "s": 2.0, "d": 4.0}
    _all = sorted(c.to_string() for c in all_configs(("d", "s", "h")))

    def fake_timer(cfg, fn, arg):
        s = cfg.to_string()
        return sum(_cost[ch] for ch in s) * 1e-3 + _all.index(s) * 1e-9

    Nt, Nd, Nm = 16, 3, 24
    F_col = random_unrepresentable(jax.random.PRNGKey(12),
                                   (Nt, Nd, Nm)) / np.sqrt(Nm)
    op = FFTMatvec.from_block_column(F_col)
    v = random_unrepresentable(jax.random.PRNGKey(13), (Nm, Nt))
    res = autotune(op, tol=3e-6, v=v, ladder=("d", "s"), variant="gram",
                   timer=fake_timer)
    assert res.record.rel_error <= 3e-6
    assert res.n_timed < res.n_lattice // 2
    # the retuned operator's fused gram really meets the tolerance
    err = rel_l2(res.op.gram().apply(v), op.gram().apply(v))
    assert err <= 3e-6
    # gram entries never answer matvec queries (distinct cache key space)
    k_g = CacheKey.for_operator(op, ("d", "s"), "gram")
    k_v = CacheKey.for_operator(op, ("d", "s"), "matvec")
    assert k_g.to_string() != k_v.to_string()


def test_harness_gram_family():
    from repro.core.timing import TimingHarness
    op = make_op()
    v = jax.random.normal(jax.random.PRNGKey(14), (op.N_m, op.N_t),
                          jnp.float64)
    h = TimingHarness(repeats=1, warmup=0)
    out = h.run_once(op, v, "gram")
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(op.gram().apply(v)),
                               rtol=1e-12, atol=0)
    # shares one applier across configs, like the vec/mat families
    h.run_once(op.with_precision(PrecisionConfig.from_string("dssdd")),
               v, "gram")
    assert set(h._jitted) == {"gram"}


# ---------------------------------------------------------------------------
# Chunked dense-Hessian assembly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [1, 5, 32, 10_000])
def test_assemble_hessian_chunked_matches_columnwise(chunk):
    op = make_op(6, 2, 4)
    prob = GaussianInverseProblem(op, noise_var=1e-4)
    H = prob.assemble_data_space_hessian(chunk=chunk)
    n = prob.data_dim
    assert H.shape == (n, n)
    # reference: one composed matvec pair per unit vector
    cols = []
    for i in range(n):
        e = jnp.zeros((n,), op.io_dtype).at[i].set(1.0).reshape(op.N_d,
                                                                op.N_t)
        cols.append((op.matvec(op.rmatvec(e))
                     + prob.noise_var * e).reshape(n))
    H_ref = jnp.stack(cols, axis=1)
    np.testing.assert_allclose(np.asarray(H), np.asarray(H_ref),
                               rtol=1e-12, atol=1e-14)


def test_information_gain_chunked_matches_default():
    op = make_op(6, 2, 4)
    prob = GaussianInverseProblem(op, noise_var=1e-4)
    ig_a = float(prob.expected_information_gain(chunk=3))
    ig_b = float(prob.expected_information_gain(chunk=64))
    assert ig_a == pytest.approx(ig_b, rel=1e-10)
    assert ig_a > 0


def test_gram_operator_identity_helpers():
    op = make_op()
    g = op.gram()
    assert (g.N_t, g.N_d, g.N_m) == (op.N_t, op.N_d, op.N_m)
    assert g.rows == op.N_m
    assert op.gram(space="data").rows == op.N_d
    assert g.io_dtype == op.io_dtype
    g2 = g.with_precision(PrecisionConfig.from_string("dssdd"))
    assert isinstance(g2, GramOperator)
    assert g2.precision.to_string() == "dssdd"
    assert g2.op.F_hat_re.dtype == jnp.float32
