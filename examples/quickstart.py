"""Quickstart: build a block-triangular Toeplitz p2o operator, run FFT
matvecs at several precision configurations, and check against the dense
reference.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402

from repro.backend import DispatchTable, current_backend  # noqa: E402
from repro.core import (ExecOpts, FFTMatvec, PrecisionConfig,  # noqa: E402
                        dense_matvec, random_block_column, rel_l2)
from repro.jax_compat import use_compile_cache  # noqa: E402


def main():
    N_t, N_d, N_m = 64, 8, 128
    key = jax.random.PRNGKey(0)
    F_col = random_block_column(key, N_t, N_d, N_m, dtype=jnp.float64)
    m = jax.random.normal(jax.random.PRNGKey(1), (N_m, N_t), jnp.float64)

    print(f"backend: {current_backend().fingerprint()} "
          f"(override with REPRO_BACKEND=xla-ref|cpu-interpret|...)")
    print(f"p2o operator: N_t={N_t}, N_d={N_d}, N_m={N_m} "
          f"(matrix is {N_t * N_d} x {N_t * N_m}, stored as {F_col.shape})")

    ref = dense_matvec(F_col, m)
    for prec in ["ddddd", "dssdd", "sssss", "shhss", "hhhhh"]:
        op = FFTMatvec.from_block_column(
            F_col, precision=PrecisionConfig.from_string(prec))
        d = op.matvec(m)
        print(f"  prec={prec}  rel_err={rel_l2(d, ref):.3e}  dtype={d.dtype}")

    # adjoint consistency
    op = FFTMatvec.from_block_column(F_col)
    d = jax.random.normal(jax.random.PRNGKey(2), (N_d, N_t), jnp.float64)
    lhs = jnp.vdot(op.matvec(m), d)
    rhs = jnp.vdot(m, op.rmatvec(d))
    print(f"adjoint check: <Fm,d>={lhs:.6f} <m,F*d>={rhs:.6f}")

    # the custom Pallas kernel path (validated in interpret mode on CPU):
    # select the cpu-interpret backend and force the kernel past the
    # dispatch table's short-wide transition point
    op_k = FFTMatvec.from_block_column(
        F_col, precision=PrecisionConfig.from_string("sssss"),
        opts=ExecOpts(backend="cpu-interpret",
                      dispatch=DispatchTable(force="pallas"),
                      fuse_pad_cast=True))
    print(f"pallas kernel path rel_err={rel_l2(op_k.matvec(m), ref):.3e}")

    # the forced reference backend (numerical ground truth, CI parity leg)
    op_r = FFTMatvec.from_block_column(F_col, backend="xla-ref")
    print(f"xla-ref backend  rel_err={rel_l2(op_r.matvec(m), ref):.3e}")


if __name__ == "__main__":
    use_compile_cache()
    main()
