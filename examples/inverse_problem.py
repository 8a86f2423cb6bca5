"""End-to-end Bayesian inverse problem (the paper's application, §2.1-2.2):

1. build the p2o map of a 1-D periodic heat equation (LTI system) — its
   discrete form is a block-lower-triangular Toeplitz matrix;
2. generate noisy observations from a ground-truth source;
3. solve for the MAP point with matrix-free CG on the data-space Hessian
   (every Hessian action = one F and one F* FFT matvec);
4. compare double-precision vs the paper's optimal mixed-precision config
   for the reconstruction, and report the expected information gain
   (the optimal-sensor-placement objective of Remark 1);
5. re-solve with the Krylov subsystem (LSQR / CGNR, repro.solvers) and
   reconstruct a whole batch of noise realizations at once through the
   multi-RHS ``matmat`` path — the outer-loop workload (Remark 1) the
   batched SBGEMM kernels exist for.

    PYTHONPATH=src python examples/inverse_problem.py
"""

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (FFTMatvec, GaussianInverseProblem,  # noqa: E402
                        PrecisionConfig, heat_equation_p2o, rel_l2)
from repro import solvers  # noqa: E402
from repro.jax_compat import use_compile_cache  # noqa: E402


def main():
    N_t, N_d, N_m = 48, 6, 96
    noise_sigma = 1e-3

    print("=== building heat-equation p2o map ===")
    F_col = heat_equation_p2o(N_t, N_d, N_m)
    op = FFTMatvec.from_block_column(F_col)

    # ground-truth source: two localized pulses in space-time
    x = jnp.linspace(0, 1, N_m, endpoint=False)
    t = jnp.linspace(0, 1, N_t)
    m_true = (jnp.exp(-((x[:, None] - 0.3) ** 2) / 0.002
                      - ((t[None, :] - 0.25) ** 2) / 0.01)
              + 0.7 * jnp.exp(-((x[:, None] - 0.7) ** 2) / 0.004
                              - ((t[None, :] - 0.6) ** 2) / 0.02))

    key = jax.random.PRNGKey(0)
    d_clean = op.matvec(m_true)
    d_obs = d_clean + noise_sigma * jax.random.normal(key, d_clean.shape,
                                                      d_clean.dtype)
    print(f"observations: {N_d} sensors x {N_t} steps, "
          f"noise sigma={noise_sigma}")

    prob = GaussianInverseProblem(op, noise_var=noise_sigma ** 2,
                                  prior_var=1.0)
    print("=== MAP solve (matrix-free CG, double precision) ===")
    m_map = prob.map_point(d_obs, method="cg", maxiter=500, tol=1e-10)
    print(f"  data misfit      : {rel_l2(op.matvec(m_map), d_obs):.3e}")
    print(f"  parameter error  : {rel_l2(m_map, m_true):.3f} "
          f"(underdetermined: {N_d} sensors for {N_m} params)")

    print("=== MAP solve with the paper's optimal mixed precision ===")
    # tolerance from the noise level (paper §3.2): sensor noise 1e-3 >>
    # single-precision error 1e-7 -> fft+gemv can run in f32
    op_mixed = FFTMatvec.from_block_column(
        F_col, precision=PrecisionConfig.from_string("dssdd"))
    prob_mixed = GaussianInverseProblem(op_mixed, noise_var=noise_sigma ** 2)
    m_map2 = prob_mixed.map_point(d_obs, method="cg", maxiter=500, tol=1e-10)
    print(f"  data misfit      : {rel_l2(op_mixed.matvec(m_map2), d_obs):.3e}")
    print(f"  vs f64 MAP point : {rel_l2(m_map2, m_map):.3e} "
          f"(below the noise floor -> mixed precision is free accuracy-wise)")

    print("=== Krylov subsystem: LSQR / CGNR on the factored problem ===")
    m_lsqr, res_lsqr = prob.map_point_krylov(d_obs, method="lsqr",
                                             tol=1e-10, maxiter=500)
    print(f"  LSQR iters       : {res_lsqr.n_iters} "
          f"(relres {float(res_lsqr.final_relres.max()):.2e})")
    print(f"  vs CG MAP point  : {rel_l2(m_lsqr, m_map):.3e}")
    m_cgnr, res_cgnr = prob.map_point_krylov(d_obs, method="cgnr",
                                             tol=1e-10, maxiter=500)
    print(f"  CGNR iters       : {res_cgnr.n_iters} "
          f"(relres {float(res_cgnr.final_relres.max()):.2e})")

    print("=== multi-RHS: reconstruct a batch of noise realizations ===")
    S = 8
    noise = noise_sigma * jax.random.normal(
        jax.random.PRNGKey(7), (*d_clean.shape, S), d_clean.dtype)
    D_obs = d_clean[..., None] + noise               # (N_d, N_t, S) stacked
    M_batch, res_b = prob_mixed.map_point_krylov(
        D_obs, method="lsqr", tol=1e-8, maxiter=500,
        solver_precision=solvers.SolverPrecision.from_string("sss"))
    D_fit = op_mixed.matmat(M_batch)
    misfits = [rel_l2(D_fit[..., s], D_obs[..., s]) for s in range(S)]
    spread = float(jnp.std(M_batch, axis=-1).mean())
    print(f"  {S} noise realizations in {res_b.n_iters} shared-matmat "
          f"LSQR iterations (one SBGEMM pipeline per iteration)")
    print(f"  data misfit      : max {max(misfits):.3e} "
          f"(all at the noise level, as expected)")
    print(f"  MAP sampling std : {spread:.3e} per parameter "
          f"(posterior variability across realizations)")

    print("=== fused Gram operator (stage-graph pipeline) ===")
    # every Hessian action above already ran through the fused data-space
    # Gram (one pipeline per action); here is the operator itself, plus the
    # half-transform circulant variant used as a screening proxy
    gram = op.gram(space="data")                     # exact F F*
    v = d_obs
    composed = op.matvec(op.rmatvec(v))
    print(f"  gram.apply vs composed rmatvec/matvec: "
          f"{rel_l2(gram.apply(v), composed):.2e} (exact fusion)")
    circ = op.gram(space="data", mode="circulant")   # per-bin G_hat
    counts_c, counts_g = circ.stage_counts(), gram.stage_counts()
    print(f"  circulant pipeline: {counts_c['fft'] + counts_c['ifft']} "
          f"transforms/action vs {counts_g['fft'] + counts_g['ifft']} "
          f"(periodic Gram: preconditioning/screening only, "
          f"wrap error {rel_l2(circ.apply(v), composed):.1e})")

    print("=== optimal experimental design ingredient (Remark 1) ===")
    # assembled from S-wide identity-block chunks: one SBGEMM-backed fused
    # Gram pipeline per 32 Hessian columns
    ig = float(prob.expected_information_gain())
    print(f"  expected information gain (KL prior->post): {ig:.2f} nats")
    few = GaussianInverseProblem(
        FFTMatvec.from_block_column(F_col[:, :2, :]),
        noise_var=noise_sigma ** 2)
    print(f"  with only 2 sensors: {float(few.expected_information_gain()):.2f} "
          f"nats (fewer sensors -> less information, as expected)")


if __name__ == "__main__":
    use_compile_cache()
    main()
