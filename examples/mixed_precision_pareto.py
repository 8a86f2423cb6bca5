"""Dynamic mixed-precision selection (paper §3.2, Fig. 3) via `repro.tune`.

The paper's Pareto analysis as a *runtime service*: instead of timing all
32 FP64/FP32 per-phase configurations, the tuner evaluates the eq.-(6)
error model over the whole lattice (calibrated from a handful of probe
runs), prunes configs that cannot meet the tolerance or are precision-
dominated by a cheaper candidate, and times only the surviving frontier.
The exhaustive sweep is run alongside for comparison — same selection,
a fraction of the measurements.  Repeats for the TPU-native f32/bf16
ladder.

    PYTHONPATH=src python examples/mixed_precision_pareto.py
"""

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402

from repro.core import (FFTMatvec, all_configs, format_table,  # noqa: E402
                        measure_configs, optimal_config, pareto_front,
                        random_unrepresentable)
from repro.tune import TimingHarness, autotune  # noqa: E402
from repro.jax_compat import use_compile_cache  # noqa: E402


def run(levels, tol, title, exhaustive=False):
    print(f"=== {title} (tolerance {tol:g}) ===")
    N_t, N_d, N_m = 128, 25, 625
    key = jax.random.PRNGKey(0)
    # paper §4.2.1: inputs must NOT be exactly representable at the lower
    # precision, or copy-phases in low precision would show zero error
    F_col = random_unrepresentable(key, (N_t, N_d, N_m)) / np.sqrt(N_m)
    m = random_unrepresentable(jax.random.PRNGKey(1), (N_m, N_t))
    op = FFTMatvec.from_block_column(F_col)

    # shared harness: the exhaustive sweep and the tuner reuse one jitted
    # callable per config — no re-tracing between the two passes
    harness = TimingHarness(repeats=3)
    res = autotune(op, tol=tol, v=m, ladder=levels, harness=harness)
    print(format_table(sorted(res.records, key=lambda r: r.time_s),
                       res.front))
    print(f"--> {res.summary()}")

    if exhaustive:
        records = measure_configs(
            lambda cfg: FFTMatvec.from_block_column(F_col, precision=cfg),
            m, list(all_configs(levels)), harness=harness)
        best = optimal_config(records, tol)
        front = pareto_front(records)
        print(f"    exhaustive sweep: {len(records)} configs timed, "
              f"front size {len(front)}, optimal {best.prec} "
              f"(rel_err {best.rel_error:.2e})")
        agree = "AGREE" if best.config == res.config else \
            "DIFFER (timing noise between runs; errors are identical)"
        print(f"    tuner vs exhaustive: {agree}\n")
    else:
        print()


def main():
    run(("d", "s"), 1e-7, "paper ladder: FP64 baseline / FP32 low",
        exhaustive=True)
    run(("s", "h"), 1e-2, "TPU-native ladder: f32 baseline / bf16 low")


if __name__ == "__main__":
    use_compile_cache()
    main()
