#!/usr/bin/env python3
"""Smoke test of the main path on a TPU: FFTMatvec -> Krylov -> SolveEngine.

    python3 chip_smoke.py              # one chip, the paper's single-chip shape
    python3 chip_smoke.py --chips 4    # only the 2-D mesh path: 1x4 and 2x2

One chip (``PAPER_SINGLE``: N_t=1000, N_d=100, N_m=5000):

1. builds the operator from a seeded block column at ``sssss`` and at the
   bf16 config ``shhss``, and applies matvec, rmatvec, the exact Gram and
   matmat at S=16;
2. checks each against a float64 host reference (numpy, direct
   convolution at sampled time steps, independent of ``repro.core``);
   the error must stay under the config's eq.-(6) bound and under a
   fixed ceiling (``ERR_CEILING``);
3. runs CGNR and LSQR for a few iterations;
4. lets a ``SolveEngine`` answer a few requests, its cold tune included.

``--chips 4`` weak-scales N_m to 20000 (4 GB of f32 F_hat per chip) and
runs matvec and rmatvec on a 1x4 and a 2x2 mesh against one host
reference, checking that every device holds its shard of F_hat.

Everything runs in this one process (a chip belongs to one process at a
time) with x64 off: the TPU precision ladder is h/s.  Each check prints a
line with its error, compile time, peak device memory and whether
Phase 3 compiled to a Pallas kernel.  The last line of a passing run is
one JSON object, ``{"ok": true, "device": {...}}``.  A failed check, or a
run where JAX finds no TPU, exits non-zero without that line.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
import warnings

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# The eq.-(6) bound is a worst case that grows with kappa and N_d: with a
# bf16 Phase 3 it exceeds 1 at the paper shape, where a zeroed or negated
# output would pass it.  So every result must also come within a fixed
# ceiling of the reference.  bf16's unit roundoff is 3.9e-3; a bf16
# Phase 3 measured 1.7e-3..3.1e-3 on a TPU v5e at the paper shape.
ERR_CEILING = 2e-2


def check_error(what: str, err: float, bound: float) -> None:
    """``err`` is under the config's eq.-(6) bound and under ERR_CEILING."""
    check(err <= bound, f"{what}: rel_err {err:.3e} > eq.-(6) bound "
                        f"{bound:.3e}")
    check(err <= ERR_CEILING, f"{what}: rel_err {err:.3e} > ceiling "
                              f"{ERR_CEILING:.0e}")


# ---------------------------------------------------------------------------
# float64 host reference: direct block convolution at sampled time steps
# ---------------------------------------------------------------------------

def conv_steps(F, x, steps, *, adjoint: bool = False):
    """The block lower-triangular Toeplitz product at selected time steps.

    ``F`` is the first block column (N_t, N_d, N_m) on the host, ``x`` a
    SOTI block (rows, N_t[, S]).  Forward: y_t = sum_{k<=t} F_k x_{t-k};
    adjoint: y_t = sum_{k<N_t-t} F_k^T x_{t+k}.  Returns the columns
    (rows_out, len(steps)[, S]) in float64, one block of F at a time."""
    N_t = F.shape[0]
    steps = np.asarray(steps)
    rows_out = F.shape[2] if adjoint else F.shape[1]
    out = np.zeros((rows_out, len(steps)) + x.shape[2:])
    last = N_t - 1 - steps.min() if adjoint else steps.max()
    for k in range(last + 1):
        src = steps + k if adjoint else steps - k
        ok = (src < N_t) if adjoint else (src >= 0)
        Fk = F[k].astype(np.float64)
        A = Fk.T if adjoint else Fk
        out[:, ok] += np.tensordot(A, x[:, src[ok]].astype(np.float64),
                                   axes=(1, 0))
    return out


def gram_steps(F, v, steps):
    """(F* F v) at selected late time steps: the forward product is needed
    only from the earliest step on."""
    N_t = F.shape[0]
    first = int(min(steps))
    z = np.zeros((F.shape[1], N_t))
    z[:, first:] = conv_steps(F, v, np.arange(first, N_t))
    return conv_steps(F, z, steps, adjoint=True)


def rel_err(got, ref) -> float:
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


# ---------------------------------------------------------------------------
# device helpers
# ---------------------------------------------------------------------------

def require_tpu(chips: int):
    """The device JAX reports, or exit: the smoke never falls back."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r}); nothing was run", file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < chips:
        print(f"chip_smoke: {chips} chips asked for, {len(devs)} visible",
              file=sys.stderr)
        raise SystemExit(2)
    from repro.backend import current_backend
    spec = current_backend()
    check(spec.name == "tpu-pallas" and spec.platform == "tpu",
          f"backend is {spec.fingerprint()!r}, not tpu-pallas on the TPU "
          f"(is REPRO_BACKEND set?)")
    check(not jax.config.jax_enable_x64, "x64 must stay off on the TPU")
    return devs


def peak_bytes(devs) -> list:
    return [int(d.memory_stats()["peak_bytes_in_use"]) for d in devs]


def compile_timed(fn, *args):
    """(compiled, seconds) for jit(fn) on these arguments."""
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


def run_timed(compiled, *args):
    import jax
    out = jax.block_until_ready(compiled(*args))     # first run
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, time.perf_counter() - t0


def phase3_kernels(compiled, n_bins: int) -> int:
    """Pallas kernels in the compiled program that read operator planes:
    the Phase-3 SBGEMV/SBGEMM take (n_bins, rows, cols) operands, whole
    or in the row chunks of the pipelined psum; the pad/cast kernels
    take 2-D ones."""
    plane = re.compile(rf"\[{n_bins},\d+,\d+\]")
    return sum(1 for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and plane.search(line))


def expected_phase3(op, modes) -> int:
    """How many Phase-3 stages auto dispatch sends to the Pallas kernels."""
    r = op.opts.resolve()
    _, n_d, n_m = op.F_hat_re.addressable_shards[0].data.shape
    return sum(r.table.gemv_path(n_d, n_m, mode, op.F_hat_re.dtype, r.spec)
               == "pallas" for mode in modes)


def report(**fields) -> None:
    print(" ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

N_T, N_D, N_M = 1000, 100, 5000       # configs/fftmatvec_paper.PAPER_SINGLE
STEPS = (0, 1, N_T // 2, N_T - 2, N_T - 1)
GRAM_STEPS = (N_T - 64, N_T - 2, N_T - 1)
S_RHS = 16


def apply_checks(op, F, x, devs, tag):
    """matvec / rmatvec / exact Gram / matmat of ``op`` against the host
    reference; each under its eq.-(6) bound, Phase 3 on Pallas where
    auto dispatch chose it."""
    from repro.core.error_model import relative_error_bound
    cfg = op.precision
    # the operator goes in as an argument: its planes are program inputs
    cases = [
        ("matvec", lambda o, v: o.matvec(v), x["m"], ("N",), "matvec",
         lambda: conv_steps(F, x["m"], STEPS), STEPS),
        ("rmatvec", lambda o, v: o.rmatvec(v), x["d"], ("H",), "rmatvec",
         lambda: conv_steps(F, x["d"], STEPS, adjoint=True), STEPS),
        ("gram", lambda o, v: o.gram().apply(v), x["m"], ("N", "H"), "gram",
         lambda: gram_steps(F, x["m"], GRAM_STEPS), GRAM_STEPS),
        (f"matmat_S{S_RHS}", lambda o, v: o.matmat(v), x["M"], ("N",),
         "matmat", lambda: conv_steps(F, x["M"], STEPS), STEPS),
    ]
    import jax
    for name, fn, arg, modes, variant, ref_fn, steps in cases:
        arg = jax.device_put(arg)           # run_ms times no host transfer
        compiled, t_compile = compile_timed(fn, op, arg)
        out, t_run = run_timed(compiled, op, arg)
        del arg
        got = np.asarray(out)[:, list(steps)]
        ref = x.setdefault(f"ref_{name}", None)
        if ref is None:
            ref = x[f"ref_{name}"] = ref_fn()
        err = rel_err(got, ref)
        bound = relative_error_bound(cfg, N_T, N_D, N_M, variant=variant,
                                     input_level="s")
        n_k, want_k = phase3_kernels(compiled, op.N_t + 1), \
            expected_phase3(op, modes)
        report(phase=name, config=tag, rel_err=f"{err:.3e}",
               bound=f"{bound:.3e}", compile_s=f"{t_compile:.2f}",
               run_ms=f"{t_run * 1e3:.3f}",
               phase3_pallas=f"{n_k}/{want_k}",
               peak_bytes=peak_bytes(devs)[0])
        check(np.isfinite(got).all(), f"{name} {tag}: non-finite output")
        check_error(f"{name} {tag}", err, bound)
        check(n_k >= want_k, f"{name} {tag}: Phase 3 compiled to {n_k} "
                             f"Pallas kernels, auto dispatch chose {want_k}")


def solve_checks(op, d_obs, devs):
    """A few CGNR and LSQR iterations: both are the same Krylov iterate in
    exact arithmetic, so they must agree, and the residual must fall."""
    from repro.solvers import cg_normal_equations, lsqr
    out = {}
    for name, solve in (("cgnr", cg_normal_equations), ("lsqr", lsqr)):
        t0 = time.perf_counter()
        res = solve(op, d_obs, tol=1e-6, maxiter=5, precision="auto")
        x = np.asarray(res.x)
        t = time.perf_counter() - t0
        h = np.asarray(res.residual_history).ravel()
        report(phase=name, iters=res.n_iters, relres_first=f"{h[0]:.3e}",
               relres_last=f"{h[-1]:.3e}", wall_s=f"{t:.2f}",
               peak_bytes=peak_bytes(devs)[0])
        check(np.isfinite(x).all(), f"{name}: non-finite solution")
        check(h[-1] < h[0], f"{name}: residual did not fall ({h[0]:.3e} "
                            f"-> {h[-1]:.3e})")
        out[name] = x
    gap = rel_err(out["cgnr"], out["lsqr"])
    report(phase="cgnr_vs_lsqr", rel_diff=f"{gap:.3e}")
    check(gap < 1e-2, f"CGNR and LSQR iterates differ by {gap:.3e}")


def engine_checks(op, D, devs):
    """A SolveEngine answers four coalesced requests (cold tune on the
    first), then four more from its warm state without retracing."""
    from repro.runtime.solve_serve import SolveEngine, SolveRequest
    eng = SolveEngine(op, max_batch=4, tune_kw=dict(repeats=2, warmup=1))
    reqs = [SolveRequest(uid=i, d_obs=D[..., i], tol=1e-6, max_iters=3 + i % 2)
            for i in range(4)]
    t0 = time.perf_counter()
    outs = eng.serve(reqs)
    t_cold = time.perf_counter() - t0
    traces = eng.jit_stats()["n_traces"]
    report(phase="engine_cold", requests=len(outs), config=outs[0].config,
           coalesced=eng.stats["coalesced"], cold_tunes=eng.stats["cold_tunes"],
           wall_s=f"{t_cold:.2f}", peak_bytes=peak_bytes(devs)[0])
    check(len(outs) == 4 and eng.stats["coalesced"] == [4],
          f"engine served {len(outs)} requests in {eng.stats['coalesced']}")
    check(eng.stats["cold_tunes"] == 1, "engine did not tune its bucket")
    for o, r in zip(outs, reqs):
        check(np.isfinite(o.x).all(), f"engine uid {o.uid}: non-finite x")
        check(o.n_iters == r.max_iters or o.converged,
              f"engine uid {o.uid}: {o.n_iters} iterations of "
              f"{r.max_iters}")
        h = o.residual_history
        check(h[-1] < h[0], f"engine uid {o.uid}: residual did not fall")
    t0 = time.perf_counter()
    warm = eng.serve([SolveRequest(uid=10 + i, d_obs=D[..., i], tol=1e-6,
                                   max_iters=3) for i in range(4)])
    t_warm = time.perf_counter() - t0
    retraced = eng.jit_stats()["n_traces"] - traces
    report(phase="engine_warm", requests=len(warm), retraced=retraced,
           wall_s=f"{t_warm:.2f}")
    check(len(warm) == 4 and all(np.isfinite(o.x).all() for o in warm),
          "warm engine requests failed")
    check(retraced == 0, f"warm engine retraced {retraced} times")


def run_one_chip(seed: int, devs) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import FFTMatvec, PrecisionConfig, random_block_column

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    F_col = random_block_column(jax.random.PRNGKey(seed), N_T, N_D, N_M)
    F = np.asarray(F_col)
    op = FFTMatvec.from_block_column(
        F_col, precision=PrecisionConfig.from_string("sssss"))
    jax.block_until_ready((op.F_hat_re, op.F_hat_im))
    del F_col
    report(phase="setup", shape=f"{N_T}x{N_D}x{N_M}",
           F_hat_bytes=2 * op.F_hat_re.nbytes,
           wall_s=f"{time.perf_counter() - t0:.2f}",
           peak_bytes=peak_bytes(devs)[0])
    x = {"m": rng.standard_normal((N_M, N_T), dtype=np.float32),
         "d": rng.standard_normal((N_D, N_T), dtype=np.float32),
         "M": rng.standard_normal((N_M, N_T, S_RHS), dtype=np.float32)}
    apply_checks(op, F, x, devs, "sssss")
    op_h = op.with_precision(PrecisionConfig.from_string("shhss"))
    apply_checks(op_h, F, x, devs, "shhss")
    del op_h

    M_true = jnp.asarray(x["M"][..., :4])
    D = np.asarray(op.jitted_block()[0](M_true))
    solve_checks(op, jnp.asarray(D[..., 0]), devs)
    engine_checks(op, D, devs)


# ---------------------------------------------------------------------------
# four chips: the 2-D mesh path
# ---------------------------------------------------------------------------

def run_mesh(seed: int, devs) -> None:
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import FFTMatvec, PrecisionConfig, random_block_column
    from repro.core.error_model import relative_error_bound

    N_m = N_M * len(devs)                         # weak scaling: 5000 per chip
    cfg = PrecisionConfig.from_string("sssss")
    key = jax.random.PRNGKey(seed)

    def block_column(mesh):
        """F_col made on the devices, each holding its (row, col) shard."""
        return jax.jit(lambda k: random_block_column(k, N_T, N_D, N_m),
                       out_shardings=NamedSharding(mesh, P(None, "row", "col"))
                       )(key)

    grids = ((1, len(devs)), (2, len(devs) // 2))
    t0 = time.perf_counter()
    F = np.asarray(block_column(Mesh(np.asarray(devs).reshape(grids[0]),
                                     ("row", "col"))))
    rng = np.random.default_rng(seed)
    m = rng.standard_normal((N_m, N_T), dtype=np.float32)
    d = rng.standard_normal((N_D, N_T), dtype=np.float32)
    ref = {"matvec": conv_steps(F, m, STEPS),
           "rmatvec": conv_steps(F, d, STEPS, adjoint=True)}
    report(phase="mesh_reference", shape=f"{N_T}x{N_D}x{N_m}",
           wall_s=f"{time.perf_counter() - t0:.2f}")
    for grid in grids:
        mesh = Mesh(np.asarray(devs).reshape(grid), ("row", "col"))
        t0 = time.perf_counter()
        F_col = block_column(mesh)
        check(np.array_equal(np.asarray(F_col[:, :, ::997]), F[:, :, ::997]),
              f"{grid}: the block column differs from the reference's")
        op = FFTMatvec.from_block_column(F_col, precision=cfg, mesh=mesh)
        jax.block_until_ready((op.F_hat_re, op.F_hat_im))
        del F_col
        t_setup = time.perf_counter() - t0
        local = (op.F_hat_re.shape[0], N_D // grid[0], N_m // grid[1])
        shards = op.F_hat_re.addressable_shards
        check({s.device for s in shards} == set(devs)
              and all(s.data.shape == local for s in shards),
              f"{grid}: F_hat shards {[(s.device.id, s.data.shape) for s in shards]}")
        report(phase="mesh_setup", grid=f"{grid[0]}x{grid[1]}",
               shard_shape=local, wall_s=f"{t_setup:.2f}",
               peak_bytes=peak_bytes(devs))
        for name, fn, arg, sharding, mode, reduced in (
                ("matvec", lambda o, v: o.matvec(v), m, op.m_sharding(), "N",
                 grid[1] > 1),
                ("rmatvec", lambda o, v: o.rmatvec(v), d, op.d_sharding(),
                 "H", grid[0] > 1)):
            xs = jax.device_put(arg, sharding)
            compiled, t_compile = compile_timed(fn, op, xs)
            out, t_run = run_timed(compiled, op, xs)
            text = compiled.as_text()
            colls = sorted({c for c in ("all-reduce", "collective-permute",
                                        "reduce-scatter", "all-gather")
                            if c in text})
            err = rel_err(np.asarray(out)[:, list(STEPS)], ref[name])
            bound = relative_error_bound(cfg, N_T, N_D, N_m, p_r=grid[0],
                                         p_c=grid[1], variant=name,
                                         input_level="s")
            n_k, want_k = phase3_kernels(compiled, N_T + 1), \
                expected_phase3(op, (mode,))
            out_devs = {s.device for s in out.addressable_shards}
            report(phase=f"mesh_{name}", grid=f"{grid[0]}x{grid[1]}",
                   rel_err=f"{err:.3e}", bound=f"{bound:.3e}",
                   compile_s=f"{t_compile:.2f}", run_ms=f"{t_run * 1e3:.3f}",
                   collectives=",".join(colls) or "none",
                   phase3_pallas=f"{n_k}/{want_k}",
                   peak_bytes=peak_bytes(devs))
            check_error(f"{name} {grid}", err, bound)
            # the product sums over the sharded axis only where that axis
            # is split: N_m (matvec) over "col", N_d (rmatvec) over "row"
            check(colls or not reduced,
                  f"{name} {grid}: no collective in the program")
            check(out_devs == set(devs),
                  f"{name} {grid}: output on {len(out_devs)} devices")
            check(n_k >= want_k, f"{name} {grid}: Phase 3 compiled to "
                                 f"{n_k} Pallas kernels, wanted {want_k}")
        del op


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2-D mesh path (1x4 and 2x2)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # a program that bakes the operator in as constants (GBs at this
    # shape) is a bug to fail on, not a warning to scroll past
    warnings.filterwarnings("error", message="A large amount of constants")
    sys.path.insert(0, SRC)
    from repro.jax_compat import use_compile_cache
    use_compile_cache()
    devs = require_tpu(args.chips)[:args.chips]
    try:
        if args.chips == 4:
            run_mesh(args.seed, devs)
        else:
            run_one_chip(args.seed, devs)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
