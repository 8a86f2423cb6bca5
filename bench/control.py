"""The controls: the operator computed one rung below what a cell states.

A control stands in the program's place and must come out as not correct
under the cell's comparison; ``bench/tests/test_bench_control.py`` keeps
that as a test at a small size, and ``python bench/control.py`` reads the
program's and the controls' numbers on the chip at a cell's own size.

A control is either the program's own path at a lower precision string
(``run.control_answers`` with, say, ``shhhh``), or a plain FFT operator on
the device (pad, rfft, one contraction per frequency, irfft, truncate)
whose Phase-3 contraction runs at the rung below the configuration's:

- ``high``: float32 planes multiplied as bf16 hi/lo pairs, three passes
  with float32 accumulation (what ``Precision.HIGH`` does on the MXU),
  for a float32 Phase 3 at ``HIGHEST``;
- ``fp8``: planes and vectors rounded to e4m3 (4 exponent, 3 mantissa
  bits) with one scale per array, float32 accumulation, for a bfloat16
  Phase 3.

The passes are written out as float32 dots of exactly representable
values, rounded by ``lax.reduce_precision``, so the control rounds the
same way on the CPU and on the chip.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time


def _jnp():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def _bf16(a):
    # reduce_precision, not a cast there and back: the TPU compiler may
    # drop such a round trip (excess precision) and keep float32
    jax, _ = _jnp()
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split_bf16(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def _fp8(a):
    """e4m3 rounding (4 exponent, 3 mantissa bits) with one scale per
    array, its largest magnitude mapped to 224 (IEEE-style e4m3 keeps 240
    as its largest finite value)."""
    jax, jnp = _jnp()
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 224.0
    return jax.lax.reduce_precision(a / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def _dot(eq, a, b):
    jax, jnp = _jnp()
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def real_dot(eq, a, b, rung: str):
    """``einsum(eq, a, b)`` of float32 arrays at the control's rung."""
    if rung == "high":
        a_hi, a_lo = _split_bf16(a)
        b_hi, b_lo = _split_bf16(b)
        return _dot(eq, a_hi, b_hi) + _dot(eq, a_hi, b_lo) \
            + _dot(eq, a_lo, b_hi)
    if rung == "fp8":
        return _dot(eq, _fp8(a), _fp8(b))
    raise ValueError(f"unknown control rung {rung!r}")


def fourier_column(F_col, rows: int = 10):
    """(re, im) float32 planes (K, N_d, N_m) of the zero-padded column,
    ``rows`` of N_d at a time into buffers updated in place, so the
    transform's scratch is a tenth of a whole one."""
    jax, jnp = _jnp()
    N_t, N_d, N_m = F_col.shape

    zeros = jax.jit(lambda: 2 * (jnp.zeros((N_t + 1, N_d, N_m),
                                          jnp.float32),))

    @functools.partial(jax.jit, donate_argnums=(0, 1), static_argnums=3)
    def put(re, im, F, i):
        blk = jax.lax.slice_in_dim(F, i, min(i + rows, N_d), axis=1)
        hat = jnp.fft.rfft(blk, n=2 * N_t, axis=0)
        return (jax.lax.dynamic_update_slice_in_dim(re, hat.real, i, 1),
                jax.lax.dynamic_update_slice_in_dim(im, hat.imag, i, 1))

    re, im = zeros()
    for i in range(0, N_d, rows):
        re, im = put(re, im, F_col, i)
    return re, im


def apply(planes, x, rung: str, *, adjoint: bool = False):
    """F x (x: (N_m, N_t)) or F* x (x: (N_d, N_t)) with the Phase-3
    contraction at ``rung``; everything else in float32."""
    _, jnp = _jnp()
    A_re, A_im = planes
    N_t = x.shape[1]
    x_hat = jnp.fft.rfft(x.astype(jnp.float32), n=2 * N_t, axis=1)
    xr, xi = x_hat.real, x_hat.imag
    if adjoint:          # conj(A)^T x: the circular correlation
        eq = "kdm,dk->mk"
        y_re = real_dot(eq, A_re, xr, rung) + real_dot(eq, A_im, xi, rung)
        y_im = real_dot(eq, A_re, xi, rung) - real_dot(eq, A_im, xr, rung)
    else:
        eq = "kdm,mk->dk"
        y_re = real_dot(eq, A_re, xr, rung) - real_dot(eq, A_im, xi, rung)
        y_im = real_dot(eq, A_re, xi, rung) + real_dot(eq, A_im, xr, rung)
    y = jnp.fft.irfft(y_re + 1j * y_im, n=2 * N_t, axis=1)
    return y[:, :N_t]


def cgnr(planes, d, rung: str, *, tol: float, maxiter: int):
    """CG on the normal equations from m = 0 with the control's F and F*,
    float32 recurrence, stopping where ||r|| / ||F* d|| < tol.  Returns
    (m, iterations)."""
    jax, jnp = _jnp()
    mv = jax.jit(lambda p, v: apply(p, v, rung))
    rmv = jax.jit(lambda p, v: apply(p, v, rung, adjoint=True))

    def dot(a, b):
        return jnp.sum(a * b)

    b = rmv(planes, d)
    b_norm = float(jnp.sqrt(dot(b, b)))
    x = jnp.zeros_like(b)
    r, p = b, b
    rho = dot(r, r)
    for k in range(1, maxiter + 1):
        q = rmv(planes, mv(planes, p))
        alpha = rho / dot(p, q)
        x, r = x + alpha * p, r - alpha * q
        rho_new = dot(r, r)
        if float(jnp.sqrt(rho_new)) / b_norm < tol:
            return x, k
        p, rho = r + (rho_new / rho) * p, rho_new
    return x, maxiter


# ---------------------------------------------------------------------------
# readings on the chip: the program's numbers and the control's, per seed
# ---------------------------------------------------------------------------

def readings(workload: str, seeds, seconds: float, rungs, numbers) -> list:
    """For each seed: one short run of the cell as the benchmark makes it
    (its numbers), then the same comparison with each control in the
    program's place.  ``numbers``: numbers to read besides those the
    cell's limits name (a product cell's ``rel_err`` or ``stated_gap``).
    One process, so set-up compiles once."""
    import math
    import run as harness
    cell = harness.load_cell(workload)
    tr = cell["traffic"]
    tr["limits"] = {**{n: math.inf for n in numbers}, **tr["limits"]}
    devices = harness.require_chip(cell)
    out = []
    for seed in seeds:
        row = {"seed": seed}
        res = harness.run_cell(cell, seed, seconds, trace=False,
                               devices=devices)
        row["program"] = {k: c["value"] for k, c in res["check"].items()}
        answers = {}
        for r in rungs:
            # a control that crashes gives no number (and sets no limit)
            try:
                if tr["call"] == "matvec":
                    answers[r] = harness.control_answers(cell, seed, r)
                else:
                    row[r] = harness.control_check(cell, seed, r)
            except Exception as e:          # noqa: BLE001
                row[r] = {"error": f"{type(e).__name__}: {e}"[:300]}
        if answers:
            refs = harness.apply_references(
                cell, seed, next(iter(answers.values()))["inputs"])
            for r, a in answers.items():
                row[r] = harness.apply_numbers(refs, a["outputs"])
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="program and control readings")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--rungs", nargs="*", default=["high"],
                    help="high, fp8, or a precision string of the program")
    ap.add_argument("--numbers", nargs="*", default=[])
    args = ap.parse_args(argv)
    bench = os.path.dirname(os.path.abspath(__file__))
    sys.path[:0] = [bench, os.path.join(os.path.dirname(bench), "src")]
    from repro.jax_compat import use_compile_cache
    use_compile_cache()
    t0 = time.perf_counter()
    readings(args.workload, args.seeds, args.seconds, args.rungs,
             args.numbers)
    print(f"readings took {time.perf_counter() - t0:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
