"""Without a TPU, or on a chip kind with no peaks, a run exits non-zero
and prints no result."""

import subprocess
import sys
import types

import pytest

from bench_tiny import BENCH, ROOT, harness

run = harness()


def test_no_tpu_exits_without_a_result():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    out = subprocess.run(
        [sys.executable, f"{BENCH}/run.py", "--workload", "paper_sssss.matvec",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=ROOT, env=env)
    assert out.returncode == 3
    assert out.stdout == ""
    assert "no TPU" in out.stderr


@pytest.mark.parametrize("kind,count,needs", [("TPU v9 imaginary", 1, 1),
                                              ("TPU v5 lite", 1, 4)])
def test_unknown_kind_or_too_few_chips_exit(monkeypatch, kind, count, needs):
    import jax
    fake = [types.SimpleNamespace(platform="tpu", device_kind=kind)] * count
    monkeypatch.setattr(jax, "devices", lambda *a: fake)
    with pytest.raises(SystemExit) as e:
        run.require_chip({"chips": needs})
    assert e.value.code == 3
