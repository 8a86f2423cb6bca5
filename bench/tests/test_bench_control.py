"""The controls of each cell, one rung below the precision its
configuration states, fail the cell's comparison with the cell's own
limits; the program at the same size passes it.  A control is the plain
operator of ``control.py`` (``high``, ``fp8``) or the program's own path
at a lower precision string; the latter are read against the operator at
the precision a bf16 configuration states (``stated_gap``)."""

import jax
import pytest

from bench_tiny import SEED, harness, stated_cell, tiny_cell

run = harness()
CONTROLS = [("paper_sssss.matvec", "high"), ("paper_sssss.cgnr", "high")]
SIZE = {"N_t": 64, "N_d": 16, "N_m": 256}


@pytest.mark.parametrize("name,rung", CONTROLS,
                         ids=[f"{n}-{r}" for n, r in CONTROLS])
def test_control_is_not_correct(name, rung):
    cell = tiny_cell(name, **SIZE)
    ok, table = run.judge(run.control_check(cell, SEED, rung),
                          cell["traffic"]["limits"])
    assert not ok, table


@pytest.mark.parametrize("name", sorted({c[0] for c in CONTROLS}))
def test_program_is_correct_at_the_same_size(name):
    cell = tiny_cell(name, **SIZE)
    res = run.run_cell(cell, SEED, 0.2, trace=False,
                       devices=jax.devices()[:1])
    assert res["correct"], res["check"]


# at this size the program reads a stated_gap of 0.9-1.5e-4 at shhss, its
# lower paths 1.7e-3 and more, fp8 3e-2
STATED_LIMIT = 5e-4


@pytest.mark.parametrize("rung", ["fp8", "shhhs", "shhsh", "hhhss", "shhhh",
                                  "hhhhh"])
def test_lower_paths_fail_the_stated_comparison(rung):
    cell = stated_cell("shhss", STATED_LIMIT, **SIZE)
    ok, table = run.judge(run.control_check(cell, SEED, rung),
                          cell["traffic"]["limits"])
    assert not ok, table


def test_program_passes_the_stated_comparison():
    cell = stated_cell("shhss", STATED_LIMIT, **SIZE)
    res = run.run_cell(cell, SEED, 0.2, trace=False,
                       devices=jax.devices()[:1])
    assert res["correct"], res["check"]
