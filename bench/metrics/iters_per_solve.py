"""Solver iterations per solve (``SolveResult.n_iters``), the mean over
the window's solves."""


def read(ctx):
    iters = ctx["run"].get("iters")
    return sum(iters) / len(iters) if iters else None
