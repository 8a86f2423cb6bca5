"""A whole run of each cell with the look for a chip skipped, at a size a
test can hold: sound, it comes out correct; with the timed path broken
underneath, it comes out not correct."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import SEED, harness, stated_cell, tiny_cell

run = harness()


def cell_of(name):
    """A cell of BENCHMARK.json at a test's size, or ``shhss``: the matvec
    at that precision against the operator it states."""
    return stated_cell("shhss", 5e-4) if name == "shhss" else tiny_cell(name)


def run_tiny(name, seconds=0.3):
    return run.run_cell(cell_of(name), SEED, seconds, trace=False,
                        devices=jax.devices()[:1])


@pytest.mark.parametrize("name", ["paper_sssss.matvec", "shhss",
                                  "paper_sssss.cgnr"])
def test_sound_run_is_correct(name):
    res = run_tiny(name)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == set(cell_of(name)["end_to_end"])


def _altered_matvec(monkeypatch):
    """One observation row of every answer negated where it is made."""
    from repro.core import FFTMatvec
    sound = FFTMatvec.matvec
    monkeypatch.setattr(FFTMatvec, "matvec",
                        lambda self, m: sound(self, m).at[0].multiply(-1))
    jax.clear_caches()          # the sound program is traced already


def _stale_matvec(monkeypatch):
    """Every call answers for the first input of the pool."""
    first = {}
    sound = run.ApplyTraffic.window

    def window(self, op, seconds, keep):
        first.setdefault("y", self.compiled(op, self.pool[0]))
        compiled = self.compiled
        self.compiled = lambda o, x: compiled(o, self.pool[0])
        try:
            return sound(self, op, seconds, keep)
        finally:
            self.compiled = compiled

    monkeypatch.setattr(run.ApplyTraffic, "window", window)


@pytest.mark.parametrize("fault", [_altered_matvec, _stale_matvec],
                         ids=["answer_altered", "stale_answer"])
@pytest.mark.parametrize("name", ["paper_sssss.matvec", "shhss"])
def test_broken_matvec_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    res = run_tiny(name)
    assert not res["correct"], res["check"]


def _solver(monkeypatch, change):
    sound = run.SolveTraffic.__init__

    def init(self, cell, op, seed):
        sound(self, cell, op, seed)
        solve = self.solve
        self.solve = lambda *a, **kw: change(solve, *a, **kw)

    monkeypatch.setattr(run.SolveTraffic, "__init__", init)


def test_solve_that_leaves_its_state_unchanged_is_not_correct(monkeypatch):
    def unchanged(solve, op, d, **kw):
        res = solve(op, d, **kw)
        res.x = jnp.zeros_like(res.x)
        return res
    _solver(monkeypatch, unchanged)
    res = run_tiny("paper_sssss.cgnr")
    assert not res["correct"] and res["check"]["x_gap"]["value"] == 1.0


def test_solve_with_an_altered_answer_is_not_correct(monkeypatch):
    def altered(solve, op, d, **kw):
        res = solve(op, d, **kw)
        res.x = res.x.at[0].multiply(-1)
        return res
    _solver(monkeypatch, altered)
    assert not run_tiny("paper_sssss.cgnr")["correct"]


def test_solve_that_stops_early_is_not_correct(monkeypatch):
    def early(solve, op, d, **kw):
        return solve(op, d, **dict(kw, tol=kw["tol"] * 30))
    _solver(monkeypatch, early)
    res = run_tiny("paper_sssss.cgnr")
    assert not res["correct"]
    assert res["check"]["relres"]["value"] > res["check"]["relres"]["limit"]


def test_reservoir_keeps_a_uniform_sample():
    counts = np.zeros(20)
    for s in range(400):
        keep = run.Reservoir(4, np.random.default_rng(s))
        for i in range(20):
            keep.offer(i)
        counts[keep.items] += 1
    assert counts.sum() == 1600 and counts.min() > 40
