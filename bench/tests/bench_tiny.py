"""Shared by the benchmark's CPU tests: the harness, and its cells cut to
a size a test run can hold (the numbers compared keep their limits)."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
TINY = {"N_t": 32, "N_d": 8, "N_m": 64}
SEED = 2**40 + 12345          # more than 32 bits wide


def harness():
    for p in (os.path.join(ROOT, "src"), BENCH):
        if p not in sys.path:
            sys.path.insert(0, p)
    import run
    return run


def tiny_cell(name: str, **sizes):
    cell = harness().load_cell(name)
    sizes = {**TINY, "N_m": TINY["N_m"] * cell["chips"], **sizes}
    cell["config"] = {**cell["config"], **sizes}
    return cell


def stated_cell(precision: str, limit: float, **sizes):
    """The matvec cell at another precision, compared with the operator at
    the rounding points that precision states (``stated_gap``)."""
    cell = tiny_cell("paper_sssss.matvec", **sizes)
    cell["config"]["precision"] = precision
    cell["traffic"] = {**cell["traffic"], "limits": {"stated_gap": limit}}
    return cell
