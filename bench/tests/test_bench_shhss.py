"""The ``paper_shhss.matvec`` cell at a test's size, with its committed
``stated_gap`` limit: the program comes out correct; its lower paths, the
``fp8`` control, and a program that leaves Phase 3's output in float32
come out not correct."""

import jax
import jax.numpy as jnp
import pytest

from bench_tiny import SEED, harness, tiny_cell

run = harness()
CELL = "paper_shhss.matvec"


def run_tiny():
    return run.run_cell(tiny_cell(CELL), SEED, 0.3, trace=False,
                        devices=jax.devices()[:1])


def test_program_is_correct():
    res = run_tiny()
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("rung", ["fp8", "shhhs", "shhsh", "hhhss", "shhhh",
                                  "hhhhh"])
def test_control_is_not_correct(rung):
    cell = tiny_cell(CELL)
    ok, table = run.judge(run.control_check(cell, SEED, rung),
                          cell["traffic"]["limits"])
    assert not ok, table


def test_phase3_output_left_in_f32_is_not_correct(monkeypatch):
    """Phase 3 answers in float32 and the reorder into the IFFT keeps it,
    as the TPU compiler made of a cast down and back up: the bfloat16
    rounding ``shhss`` states there never happens."""
    from repro.core import pipeline
    sbgemv, reorder = pipeline.kops.sbgemv, pipeline.reorder_planes

    def f32_out(*args, out_dtype=None, **kw):
        return sbgemv(*args, out_dtype=jnp.float32, **kw)

    def keep_back(re, im, level, *, to_tosi, S=1):
        return reorder(re, im, level if to_tosi else "s", to_tosi=to_tosi,
                       S=S)

    monkeypatch.setattr(pipeline.kops, "sbgemv", f32_out)
    monkeypatch.setattr(pipeline, "reorder_planes", keep_back)
    jax.clear_caches()          # the sound program is traced already
    res = run_tiny()
    jax.clear_caches()
    assert not res["correct"], res["check"]
