"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles (interpret mode on CPU), plus the dispatcher heuristics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backend import DispatchTable
from repro.kernels import ops, ref
from repro.kernels.pad_cast import pad_cast as pal_pad_cast
from repro.kernels.pad_cast import unpad_cast as pal_unpad_cast

# Interpret-mode Pallas, explicitly forced (the CPU validation spelling).
PALLAS = dict(backend="cpu-interpret", dispatch=DispatchTable(force="pallas"))

SHAPES = [(3, 4, 128), (2, 100, 640), (1, 8, 512), (5, 16, 256),
          (2, 104, 1280)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _planes(key, B, m, n, dtype):
    ks = jax.random.split(key, 4)
    mk = lambda k, shape: (jax.random.normal(k, shape, jnp.float32)
                           .astype(dtype))
    return (mk(ks[0], (B, m, n)), mk(ks[1], (B, m, n)),
            mk(ks[2], (B, m)), mk(ks[3], (B, m)))


def _tol(dtype):
    # interpret-mode f32 accumulation order differs from the einsum ref
    return 2e-2 if dtype == jnp.bfloat16 else 1e-4


@pytest.mark.parametrize("B,m,n", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("mode", ["T", "H"])
def test_sbgemv_th_complex(B, m, n, dtype, mode):
    Ar, Ai, xr, xi = _planes(jax.random.PRNGKey(0), B, m, n, dtype)
    got = ops.sbgemv(Ar, Ai, xr, xi, mode, block_n=128, out_dtype=jnp.float32,
                     **PALLAS)
    want = ref.sbgemv_complex_ref(Ar.astype(jnp.float32),
                                  Ai.astype(jnp.float32),
                                  xr.astype(jnp.float32),
                                  xi.astype(jnp.float32), mode)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=_tol(dtype), atol=_tol(dtype))


@pytest.mark.parametrize("B,m,n", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_sbgemv_n_complex(B, m, n, dtype):
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 4)
    mk = lambda k, shape: jax.random.normal(k, shape, jnp.float32).astype(dtype)
    Ar, Ai = mk(ks[0], (B, m, n)), mk(ks[1], (B, m, n))
    xr, xi = mk(ks[2], (B, n)), mk(ks[3], (B, n))
    got = ops.sbgemv(Ar, Ai, xr, xi, "N", block_n=128, out_dtype=jnp.float32,
                     **PALLAS)
    want = ref.sbgemv_complex_ref(Ar.astype(jnp.float32),
                                  Ai.astype(jnp.float32),
                                  xr.astype(jnp.float32),
                                  xi.astype(jnp.float32), "N")
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=_tol(dtype), atol=_tol(dtype) * n / 64)


@pytest.mark.parametrize("B,m,n", [(2, 7, 130), (3, 100, 999)])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("mode", ["N", "T", "H"])
def test_sbgemv_unaligned_shapes(B, m, n, dtype, mode):
    """A is taken as stored: m is one whole block, the last n tile is
    partial (masked in the N mode's contraction, dropped on write in the
    T/H modes), and vectors ride as (B, 1, .) blocks."""
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    mk = lambda k, shape: jax.random.normal(k, shape, jnp.float32).astype(dtype)
    xd = n if mode == "N" else m
    Ar, Ai = mk(ks[0], (B, m, n)), mk(ks[1], (B, m, n))
    xr, xi = mk(ks[2], (B, xd)), mk(ks[3], (B, xd))
    got = ops.sbgemv(Ar, Ai, xr, xi, mode, block_n=128, out_dtype=jnp.float32,
                     **PALLAS)
    up = lambda a: a.astype(jnp.float32)
    want = ref.sbgemv_complex_ref(up(Ar), up(Ai), up(xr), up(xi), mode)
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=tol, atol=tol * xd / 64)


def _dot_precisions(jaxpr):
    """The ``precision`` of every dot_general in a jaxpr, kernels included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn.params["precision"]
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if isinstance(sub, jax.extend.core.Jaxpr):
                yield from _dot_precisions(sub)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("path", ["pallas", "xla", "ref"])
def test_f32_contractions_run_at_full_precision(path, dtype):
    """f32 operands contract at HIGHEST: a TPU's default for f32 is one
    bf16 MXU pass, which turned the "s" rung into a bf16 one on the chip
    (rel_err 1.9e-3 at the paper shape, vs 4e-7 at HIGHEST).  bf16
    operands are exact in one pass; the Pallas kernels leave them at the
    default."""
    B, m, n = 2, 8, 256
    A = jnp.zeros((B, m, n), dtype)
    x = jnp.zeros((B, n), dtype)
    kw = (PALLAS if path == "pallas"
          else dict(backend="cpu-xla", dispatch=DispatchTable(force="xla"))
          if path == "xla" else dict(backend="xla-ref"))
    jx = jax.make_jaxpr(lambda A, x: ops.sbgemv(A, A, x, x, "N", **kw))(A, x)
    precs = list(_dot_precisions(jx.jaxpr))
    assert precs
    highest = (jax.lax.Precision.HIGHEST,) * 2
    if path == "pallas" and dtype == jnp.bfloat16:
        assert all(p is None for p in precs), precs
    else:
        assert all(p == highest for p in precs), precs


@pytest.mark.parametrize("n", [384, 390])      # 390: a partial last tile
@pytest.mark.parametrize("mode", ["N", "T"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_sbgemv_real(mode, dtype, n):
    B, m = 3, 24
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    A = jax.random.normal(k1, (B, m, n), jnp.float32).astype(dtype)
    x = jax.random.normal(k2, (B, m if mode == "T" else n),
                          jnp.float32).astype(dtype)
    got = ops.sbgemv_real(A, x, mode, block_n=128, out_dtype=jnp.float32,
                          **PALLAS)
    want = ref.sbgemv_real_ref(A.astype(jnp.float32), x.astype(jnp.float32),
                               mode)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=_tol(dtype), atol=_tol(dtype) * 4)


@pytest.mark.parametrize("R,T,P", [(8, 100, 200), (16, 33, 66), (8, 64, 200)])
@pytest.mark.parametrize("din,dout", [(jnp.float32, jnp.bfloat16),
                                      (jnp.bfloat16, jnp.float32),
                                      (jnp.float32, jnp.float32)])
def test_pad_cast_kernel(R, T, P, din, dout):
    x = jax.random.normal(jax.random.PRNGKey(4), (R, T),
                          jnp.float32).astype(din)
    got = pal_pad_cast(x, P, dout, interpret=True)
    want = ref.pad_cast_ref(x, P, dout)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


# (8, 2000, 1000): N_t = 1000 is no multiple of 128, so the input block
# reads 1024 lanes and the kernel keeps the first 1000
@pytest.mark.parametrize("R,P,keep", [(8, 200, 100), (16, 66, 33),
                                      (8, 2000, 1000), (16, 300, 130)])
def test_unpad_cast_kernel(R, P, keep):
    x = jax.random.normal(jax.random.PRNGKey(5), (R, P), jnp.float32)
    got = pal_unpad_cast(x, keep, jnp.bfloat16, interpret=True)
    want = ref.unpad_cast_ref(x, keep, jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_dispatcher_heuristic():
    """rocBLAS-host-dispatcher analogue: custom kernel only for short-wide."""
    assert ops.use_custom_kernel(100, 5000, "H")        # the paper's case
    assert not ops.use_custom_kernel(1000, 1000, "H")   # squarish
    assert ops.use_custom_kernel(100, 400, "T")


def test_dispatcher_f64_auto_falls_back_explicit_raises():
    """Pallas TPU has no f64: *auto* dispatch routes paper mode to the XLA
    lowering, but an explicit Pallas request now raises a clear
    UnsupportedOnBackend instead of being silently overridden."""
    from repro.backend import UnsupportedOnBackend
    B, m, n = 2, 4, 64
    Ar = jnp.ones((B, m, n), jnp.float64)
    xr = jnp.ones((B, m), jnp.float64)
    got = ops.sbgemv(Ar, Ar, xr, xr, "H", backend="cpu-interpret")  # auto
    assert got[0].dtype == jnp.float64
    with pytest.raises(UnsupportedOnBackend, match="f64"):
        ops.sbgemv(Ar, Ar, xr, xr, "H", **PALLAS)


# ---------------------------------------------------------------------------
# Multi-RHS (SBGEMM) kernels
# ---------------------------------------------------------------------------

GEMM_SHAPES = [(3, 4, 128, 4), (2, 100, 640, 1), (1, 8, 512, 16),
               (2, 7, 130, 5)]   # last case: unaligned everywhere


@pytest.mark.parametrize("B,m,n,S", GEMM_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("mode", ["N", "T", "H"])
def test_sbgemm_matches_oracle(B, m, n, S, dtype, mode):
    ks = jax.random.split(jax.random.PRNGKey(10), 4)
    mk = lambda k, shape: jax.random.normal(k, shape, jnp.float32).astype(dtype)
    Ar, Ai = mk(ks[0], (B, m, n)), mk(ks[1], (B, m, n))
    xd = n if mode == "N" else m
    Xr, Xi = mk(ks[2], (B, xd, S)), mk(ks[3], (B, xd, S))
    got = ops.sbgemm(Ar, Ai, Xr, Xi, mode, block_n=128, block_s=8,
                     out_dtype=jnp.float32, **PALLAS)
    want = ref.sbgemm_complex_ref(Ar.astype(jnp.float32),
                                  Ai.astype(jnp.float32),
                                  Xr.astype(jnp.float32),
                                  Xi.astype(jnp.float32), mode)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=_tol(dtype), atol=_tol(dtype) * n / 64)


@pytest.mark.parametrize("mode", ["N", "T", "H"])
@pytest.mark.parametrize("force", ["pallas", "xla"])
def test_sbgemm_equals_columnwise_sbgemv(mode, force):
    """The batched-RHS kernel must reproduce S independent GEMVs."""
    B, m, n, S = 2, 12, 256, 3
    kw = dict(backend="cpu-interpret", dispatch=DispatchTable(force=force))
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    mk = lambda k, shape: jax.random.normal(k, shape, jnp.float32)
    Ar, Ai = mk(ks[0], (B, m, n)), mk(ks[1], (B, m, n))
    xd = n if mode == "N" else m
    Xr, Xi = mk(ks[2], (B, xd, S)), mk(ks[3], (B, xd, S))
    Yr, Yi = ops.sbgemm(Ar, Ai, Xr, Xi, mode, block_n=128, block_s=8, **kw)
    for s in range(S):
        yr, yi = ops.sbgemv(Ar, Ai, Xr[:, :, s], Xi[:, :, s], mode,
                            block_n=128, **kw)
        np.testing.assert_allclose(np.asarray(Yr[:, :, s]), np.asarray(yr),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(Yi[:, :, s]), np.asarray(yi),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mode", ["N", "T"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_sbgemm_real(mode, dtype):
    B, m, n, S = 3, 24, 384, 6
    k1, k2 = jax.random.split(jax.random.PRNGKey(12))
    A = jax.random.normal(k1, (B, m, n), jnp.float32).astype(dtype)
    X = jax.random.normal(k2, (B, m if mode == "T" else n, S),
                          jnp.float32).astype(dtype)
    got = ops.sbgemm_real(A, X, mode, block_n=128, block_s=8,
                          out_dtype=jnp.float32, **PALLAS)
    want = ref.sbgemm_real_ref(A.astype(jnp.float32), X.astype(jnp.float32),
                               mode)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=_tol(dtype), atol=_tol(dtype) * 8)


def test_sbgemm_f64_auto_falls_back_explicit_raises():
    from repro.backend import UnsupportedOnBackend
    B, m, n, S = 2, 4, 64, 3
    A = jnp.ones((B, m, n), jnp.float64)
    X = jnp.ones((B, m, S), jnp.float64)
    got = ops.sbgemm(A, A, X, X, "H", backend="cpu-interpret")      # auto
    assert got[0].dtype == jnp.float64 and got[0].shape == (B, n, S)
    with pytest.raises(UnsupportedOnBackend, match="f64"):
        ops.sbgemm(A, A, X, X, "H", **PALLAS)
