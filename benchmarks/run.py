"""Benchmark harness: one bench per paper table/figure, one registry.

    PYTHONPATH=src python -m benchmarks.run [--only fig1,fig5] [--smoke]

Prints ``name,us_per_call,derived`` CSV.  Every bench exposes
``main(argv)`` with a ``--smoke`` flag (tiny CPU shapes for the CI smoke
job); ``--only`` selects a comma-separated subset by registry name and
extra flags pass through to each selected bench.  Figures map to the
paper:
  fig1  optimized short-wide (conj) transpose SBGEMV vs stock   (Fig. 1)
  fig2  FFTMatvec per-phase runtime breakdown, F and F*         (Fig. 2)
  fig3  mixed-precision Pareto front, 32 configs, tol 1e-7      (Fig. 3)
  fig4  weak scaling w/ comm-aware partitioning + mixed prec    (Fig. 4)
  fig5  multi-RHS matmat + shared-matmat Krylov solver throughput (ext.)
  fig6  SolveEngine serving throughput, coalesced vs naive        (ext.)
  hessian  composed-vs-fused Gram Hessian actions (Remark 1 outer loop)
TPU-target roofline numbers live in benchmarks/roofline_report (reads the
dry-run artifacts; EXPERIMENTS.md §Roofline).
"""

import argparse

import jax

jax.config.update("jax_enable_x64", True)   # paper-faithful f64 ladder

from repro.jax_compat import use_compile_cache  # noqa: E402


def _registry():
    from . import (fig1_sbgemv, fig2_phase_breakdown, fig3_pareto,
                   fig4_scaling, fig5_solver, fig6_serve, hessian_gram)
    return {
        "fig1": fig1_sbgemv.main,
        "fig2": fig2_phase_breakdown.main,
        "fig3": fig3_pareto.main,
        "fig4": fig4_scaling.main,
        "fig5": fig5_solver.main,
        "fig6": fig6_serve.main,
        "hessian": hessian_gram.main,
    }


# plan families each figure's timed path lowers (repro.analysis.cli
# names) — what `--lint` pre-flights before any timing starts
_LINT_PLANS = {
    "fig1": ("matvec",),
    "fig2": ("matvec", "rmatvec"),
    "fig3": ("matvec", "rmatvec"),
    "fig4": ("matvec-hier", "matvec-ring", "rmatvec-ring"),
    "fig5": ("matvec", "rmatvec"),
    "fig6": ("gram", "gram-circulant"),
    "hessian": ("gram", "gram-circulant", "gram-mesh"),
}


def _lint(selected, smoke: bool) -> int:
    """Pre-flight: statically lint the plan families the selected
    figures will lower — every registered backend, abstract tracing,
    nothing executes — so a mis-declared plan fails in seconds instead
    of after the GPU-hours it was about to be timed with."""
    from repro.analysis import cli as analysis_cli

    argv = ["--strict"] + (["--smoke"] if smoke else [])
    for plan in sorted({p for name in selected for p in _LINT_PLANS[name]}):
        argv += ["--plan", plan]
    return analysis_cli.main(argv)


def main(argv=None) -> None:
    benches = _registry()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default="",
                    help=f"comma-separated subset of {sorted(benches)}")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU shapes for the CI smoke job")
    ap.add_argument("--lint", action="store_true",
                    help="pre-flight only: statically lint each selected "
                         "figure's plan families (repro.analysis) and "
                         "exit — no benchmark runs")
    args, passthrough = ap.parse_known_args(argv)

    selected = [s for s in args.only.split(",") if s] or list(benches)
    unknown = [s for s in selected if s not in benches]
    if unknown:
        ap.error(f"unknown bench(es) {unknown}; known: {sorted(benches)}")
    if passthrough and len(selected) != 1:
        ap.error(f"extra flags {passthrough} need --only <one bench>")

    if args.lint:
        raise SystemExit(_lint(selected, args.smoke))

    print("name,us_per_call,derived")
    child_argv = (["--smoke"] if args.smoke else []) + passthrough
    for name in selected:
        benches[name](list(child_argv))


if __name__ == "__main__":
    use_compile_cache()
    main()
