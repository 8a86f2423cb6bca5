"""Paper Figure 4: weak scaling 8 -> 4,096 GPUs on Frontier with
communication-aware partitioning and mixed precision.

Four parts:
  1. MEASURED multi-device execution at 8 simulated devices (subprocess
     with --xla_force_host_platform_device_count=8): distributed F matvec
     error + f64-vs-mixed timing on the flat grid.
  2. MEASURED grid-vs-flat comparison on the same 8 devices: the 2x4
     hierarchical grid (two-stage reductions, d sharded over rows)
     against the flat 1x8 grid — output parity to the precision-config
     tolerance plus timing for matvec/rmatvec, so the modeled-vs-measured
     gap of part 4 is finally observable on real collectives.  Carries
     the rmatvec regression assertion: with the direction-aware
     collective selection the 2x4 grid's rmatvec must not lose to the
     flat grid's (it used to — the adjoint's single-axis row reduction
     was staged hierarchically for no benefit).
  3. MEASURED pipelined-vs-serial schedule on the 2x4 grid
     (``pipelined_vs_serial``): the chunked gemv_psum super-stage
     (``overlap=4``, DESIGN.md §9) against the serial plan
     (``overlap=None``) for matvec and rmatvec — parity to roundoff,
     chunked-launch instrumentation, and speedup ratios asserted >= 1
     within smoke noise.
  3b. MEASURED ring-vs-pipelined schedule on the same grid
     (``ring_vs_pipelined``): the explicit software-pipelined ppermute
     ring (``collective="ring"``, DESIGN.md §10) against the PR-8
     XLA-scheduled pipelined form — bit-exact vs its serial plan,
     parity to roundoff vs pipelined, and no slower than pipelined
     within smoke noise (``gate_ratio`` feeds the smoke-regression
     gate).
  4. MODELED weak scaling to 4,096 devices (N_m = 5000p): per-device
     compute is constant; the comm model (core.partition, two-tier
     network) gives the collective time for the comm-aware grid vs the
     flat 1 x p grid — the paper reports >3x from comm-aware partitioning
     at 4,096 GPUs and a ~30% mixed-precision speedup at 640 GPUs.
"""

import argparse
import json
import subprocess
import sys

from repro.backend import TPU_PALLAS
from repro.core import NetworkModel, choose_grid, matvec_comm_time, paper_grid
from repro.jax_compat import forced_host_devices_env
from .common import row

N_DEV = 8

# per-device compute time for the local slice (5000 cols), from the fig2
# bench scaled: memory-bound SBGEMV traffic / HBM bw of the TPU target
# backend spec: local F_hat slice = (Nt+1) * Nd * 5000 * 8B / hbm_bw
N_T, N_D, NM_PER = 1000, 100, 5000
_HBM = TPU_PALLAS.hbm_bandwidth
T_COMPUTE = (N_T + 1) * N_D * NM_PER * 8 / _HBM          # f64 baseline
T_COMPUTE_MIXED = (N_T + 1) * N_D * NM_PER * 4 / _HBM    # f32 gemv phase


def _run_measured(code: str, results: dict, tag: str):
    """Run a measured leg in the 8-device subprocess (XLA_FLAGS and
    PYTHONPATH extended, never clobbered — see ``forced_host_devices_env``);
    the child reports its jax.device_count() and anything != 8 is a hard
    failure — never silently time a 1-device run.  A failed leg is
    recorded under ``results[tag]["error"]`` and makes :func:`main` exit
    non-zero once the artifact is written."""
    out = subprocess.run([sys.executable, "-c", code],
                         env=forced_host_devices_env(N_DEV),
                         capture_output=True, text=True, timeout=560)
    if out.returncode != 0:
        row(f"fig4/{tag}", 0.0, f"FAILED:{out.stderr[-120:]}")
        results[tag] = {"error": out.stderr[-400:]}
        return None
    res = json.loads(out.stdout.splitlines()[-1])
    if res.get("device_count") != N_DEV:
        msg = f"child saw {res.get('device_count')} devices, wanted {N_DEV}"
        row(f"fig4/{tag}", 0.0, f"FAILED:{msg}")
        results[tag] = {"error": msg}
        return None
    results[tag] = res
    return res


_MEASURED_CODE = r"""
import jax, json
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp, time
from repro.core import FFTMatvec, PrecisionConfig, random_block_column, rel_l2, dense_matvec
from repro.jax_compat import make_mesh
res = {"device_count": jax.device_count()}
mesh = make_mesh((1, 8), ("row", "col"))
Nt, Nd, Nm = %(shape)s
F_col = random_block_column(jax.random.PRNGKey(0), Nt, Nd, Nm, dtype=jnp.float64)
m = jax.random.normal(jax.random.PRNGKey(1), (Nm, Nt), dtype=jnp.float64)
for tag, prec in [("f64", "ddddd"), ("mixed", "dssdd")]:
    op = FFTMatvec.from_block_column(F_col, precision=PrecisionConfig.from_string(prec), mesh=mesh)
    mv = jax.jit(op.matvec, in_shardings=op.m_sharding())
    ms = jax.device_put(m, op.m_sharding())
    out = jax.block_until_ready(mv(ms))
    t0 = time.perf_counter()
    for _ in range(5):
        out = mv(ms)
    jax.block_until_ready(out)
    res[tag] = {"t": (time.perf_counter() - t0) / 5,
                "err": rel_l2(out, dense_matvec(F_col, m))}
print(json.dumps(res))
"""

_GRID_VS_FLAT_CODE = r"""
import jax, json
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp, time
from repro.core import (FFTMatvec, PrecisionConfig, random_block_column,
                        rel_l2, dense_matvec, dense_rmatvec)
from repro.jax_compat import make_mesh
res = {"device_count": jax.device_count()}
Nt, Nd, Nm = %(shape)s
F_col = random_block_column(jax.random.PRNGKey(0), Nt, Nd, Nm, dtype=jnp.float64)
m = jax.random.normal(jax.random.PRNGKey(1), (Nm, Nt), dtype=jnp.float64)
d = jax.random.normal(jax.random.PRNGKey(2), (Nd, Nt), dtype=jnp.float64)

def tmin(fn, x, reps=%(reps)d):
    jax.block_until_ready(fn(x))              # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        ts.append(time.perf_counter() - t0)
    return min(ts)                             # min-of-reps: CPU-noise robust

def bench(op):
    mv = jax.jit(op.matvec, in_shardings=op.m_sharding())
    rmv = jax.jit(op.rmatvec, in_shardings=op.d_sharding())
    ms, ds = jax.device_put(m, op.m_sharding()), jax.device_put(d, op.d_sharding())
    return mv(ms), rmv(ds), tmin(mv, ms), tmin(rmv, ds)

ref_f, ref_a = dense_matvec(F_col, m), dense_rmatvec(F_col, d)
for tag, shape in [("flat_1x8", (1, 8)), ("hier_2x4", (2, 4))]:
    mesh = make_mesh(shape, ("row", "col"))
    op = FFTMatvec.from_block_column(F_col, mesh=mesh)
    out_f, out_a, t_f, t_a = bench(op)
    res[tag] = {"grid": list(shape), "collective": op._collective_kind(("col",)),
                "collective_adjoint": op._collective_kind(
                    ("row",) if shape[0] > 1 else (), adjoint=True),
                "t_matvec": t_f, "t_rmatvec": t_a,
                "err_matvec": rel_l2(out_f, ref_f),
                "err_rmatvec": rel_l2(out_a, ref_a)}
res["parity_matvec"] = abs(res["flat_1x8"]["err_matvec"] - res["hier_2x4"]["err_matvec"])
# the rmatvec regression (direction-aware collective selection): the 2x4
# grid's adjoint must reduce over its single row axis with a FLAT psum and
# not lose to the flat grid's rmatvec
res["rmatvec_flat_over_hier"] = (res["flat_1x8"]["t_rmatvec"]
                                 / res["hier_2x4"]["t_rmatvec"])
assert res["hier_2x4"]["collective_adjoint"] == "psum", res
assert res["rmatvec_flat_over_hier"] >= 0.85, (
    "rmatvec regression: hier 2x4 lost to flat 1x8 beyond smoke noise: "
    f"{res['rmatvec_flat_over_hier']:.3f}")
print(json.dumps(res))
"""

_PIPELINED_CODE = r"""
import jax, json
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp, time
from repro.core import (FFTMatvec, random_block_column, record_stages,
                        rel_l2)
from repro.jax_compat import make_mesh
res = {"device_count": jax.device_count()}
Nt, Nd, Nm = %(shape)s
K = %(chunks)d
F_col = random_block_column(jax.random.PRNGKey(0), Nt, Nd, Nm, dtype=jnp.float64)
m = jax.random.normal(jax.random.PRNGKey(1), (Nm, Nt), dtype=jnp.float64)
d = jax.random.normal(jax.random.PRNGKey(2), (Nd, Nt), dtype=jnp.float64)

def tmin(fn, x, reps=%(reps)d):
    jax.block_until_ready(fn(x))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        ts.append(time.perf_counter() - t0)
    return min(ts)

mesh = make_mesh((2, 4), ("row", "col"))
base = FFTMatvec.from_block_column(F_col, mesh=mesh)
out = {}
for tag, ov in [("pipelined", K), ("serial", None)]:
    op = base.with_overlap(ov)
    mv = jax.jit(op.matvec, in_shardings=op.m_sharding())
    rmv = jax.jit(op.rmatvec, in_shardings=op.d_sharding())
    ms, ds = jax.device_put(m, op.m_sharding()), jax.device_put(d, op.d_sharding())
    out[tag] = {"y_mv": mv(ms), "y_rmv": rmv(ds),
                "t_matvec": tmin(mv, ms), "t_rmatvec": tmin(rmv, ds)}
    # chunked-launch instrumentation (trace-time counts, un-jitted pass)
    with record_stages() as c:
        op.matvec(ms)
    res[tag] = {"t_matvec": out[tag]["t_matvec"],
                "t_rmatvec": out[tag]["t_rmatvec"],
                "chunked_launches": int(c.get(f"collective:pipelined:{K}", 0)),
                "psum_launches": int(c.get("psum", 0))}
res["chunks"] = K
res["parity_matvec"] = rel_l2(out["pipelined"]["y_mv"], out["serial"]["y_mv"])
res["parity_rmatvec"] = rel_l2(out["pipelined"]["y_rmv"], out["serial"]["y_rmv"])
res["speedup_matvec"] = res["serial"]["t_matvec"] / res["pipelined"]["t_matvec"]
res["speedup_rmatvec"] = res["serial"]["t_rmatvec"] / res["pipelined"]["t_rmatvec"]
assert res["pipelined"]["chunked_launches"] == 1, res
assert res["serial"]["chunked_launches"] == 0, res
assert res["parity_matvec"] < 1e-12 and res["parity_rmatvec"] < 1e-12, res
# pipelined >= serial within smoke noise on BOTH directions
assert res["speedup_matvec"] >= 0.9, res["speedup_matvec"]
assert res["speedup_rmatvec"] >= 0.9, res["speedup_rmatvec"]
print(json.dumps(res))
"""

_RING_CODE = r"""
import jax, json
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp, time
from repro.core import (FFTMatvec, random_block_column, record_stages,
                        rel_l2)
from repro.jax_compat import make_mesh
res = {"device_count": jax.device_count()}
Nt, Nd, Nm = %(shape)s
K = %(chunks)d
F_col = random_block_column(jax.random.PRNGKey(0), Nt, Nd, Nm, dtype=jnp.float64)
m = jax.random.normal(jax.random.PRNGKey(1), (Nm, Nt), dtype=jnp.float64)
d = jax.random.normal(jax.random.PRNGKey(2), (Nd, Nt), dtype=jnp.float64)

def tmin(fn, x, reps=%(reps)d):
    jax.block_until_ready(fn(x))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        ts.append(time.perf_counter() - t0)
    return min(ts)

mesh = make_mesh((2, 4), ("row", "col"))
base = FFTMatvec.from_block_column(F_col, mesh=mesh)
out = {}
# ring = explicit software-pipelined ppermute ring (DESIGN.md $10);
# pipelined = the PR-8 schedule relying on XLA's async all-reduce
for tag, op in [("ring", base.with_comm(None, "ring").with_overlap(K)),
                ("pipelined", base.with_overlap(K)),
                ("ring_serial", base.with_comm(None, "ring").with_overlap(None))]:
    mv = jax.jit(op.matvec, in_shardings=op.m_sharding())
    rmv = jax.jit(op.rmatvec, in_shardings=op.d_sharding())
    ms, ds = jax.device_put(m, op.m_sharding()), jax.device_put(d, op.d_sharding())
    out[tag] = {"y_mv": mv(ms), "y_rmv": rmv(ds)}
    res[tag] = {"t_matvec": tmin(mv, ms), "t_rmatvec": tmin(rmv, ds)}
    with record_stages() as c:
        op.matvec(ms)
    res[tag]["chunked_launches"] = int(c.get(f"collective:ring:{K}", 0)
                                       + c.get(f"collective:pipelined:{K}", 0))
    res[tag]["ring_hops"] = int(c.get("collective:ring", 0))
res["chunks"] = K
# bit-exact: ring chunked == ring serial (canonical origin-rank order)
res["bit_vs_serial"] = bool(jnp.array_equal(out["ring"]["y_mv"],
                                            out["ring_serial"]["y_mv"]))
res["parity_vs_pipelined"] = rel_l2(out["ring"]["y_mv"],
                                    out["pipelined"]["y_mv"])
res["parity_rmatvec"] = rel_l2(out["ring"]["y_rmv"],
                               out["pipelined"]["y_rmv"])
res["speedup_vs_pipelined"] = (res["pipelined"]["t_matvec"]
                               / res["ring"]["t_matvec"])
res["speedup_rmatvec"] = (res["pipelined"]["t_rmatvec"]
                          / res["ring"]["t_rmatvec"])
assert res["ring"]["chunked_launches"] == 1, res
assert res["ring"]["ring_hops"] == K * 3, res   # K chunks x (g-1) hops
assert res["bit_vs_serial"], res
assert res["parity_vs_pipelined"] < 1e-12, res
# ring >= PR-8 pipelined within smoke noise (the acceptance bar)
assert res["speedup_vs_pipelined"] >= 0.9, res["speedup_vs_pipelined"]
assert res["speedup_rmatvec"] >= 0.9, res["speedup_rmatvec"]
print(json.dumps(res))
"""


def measured_8dev(results, smoke=False):
    shape = (32, 4, 8 * 32) if smoke else (128, 16, 8 * 200)
    res = _run_measured(_MEASURED_CODE % {"shape": repr(shape)}, results,
                        "measured_8dev")
    if res is None:
        return
    res["shape"] = list(shape)
    row("fig4/measured_8dev_f64", res["f64"]["t"],
        f"rel_err={res['f64']['err']:.1e}")
    row("fig4/measured_8dev_mixed", res["mixed"]["t"],
        f"rel_err={res['mixed']['err']:.1e};"
        f"speedup={res['f64']['t'] / res['mixed']['t']:.2f}")


def measured_grid_vs_flat(results, smoke=False):
    """Hierarchical 2x4 vs flat 1x8, measured — with the rmatvec
    regression assertion (direction-aware collective selection).  N_d is
    sized so the per-device output rows can actually chunk (the default
    ``overlap="auto"`` pipelines both grids identically — this leg
    compares grids under the schedule they would really run)."""
    shape = (32, 256, 8 * 64) if smoke else (128, 128, 8 * 200)
    res = _run_measured(
        _GRID_VS_FLAT_CODE % {"shape": repr(shape),
                              "reps": 10 if smoke else 20},
        results, "measured_grid_vs_flat")
    if res is None:
        return
    res["shape"] = list(shape)
    # the model's view of the same comparison, for the gap analysis
    net = NetworkModel()
    res["model_t_flat"] = matvec_comm_time(1, N_DEV, *shape, net=net)
    res["model_t_grid"] = matvec_comm_time(2, 4, *shape, net=net)
    for tag in ("flat_1x8", "hier_2x4"):
        r = res[tag]
        row(f"fig4/grid_{tag}", r["t_matvec"],
            f"collective={r['collective']};rmatvec={r['t_rmatvec']:.2e};"
            f"rel_err={r['err_matvec']:.1e}")
    row("fig4/grid_vs_flat", res["hier_2x4"]["t_matvec"],
        f"speedup={res['flat_1x8']['t_matvec'] / res['hier_2x4']['t_matvec']:.2f};"
        f"parity={res['parity_matvec']:.1e}")
    row("fig4/rmatvec_regression", res["hier_2x4"]["t_rmatvec"],
        f"flat_over_hier={res['rmatvec_flat_over_hier']:.2f};"
        f"adjoint_coll={res['hier_2x4']['collective_adjoint']}")


def measured_pipelined_vs_serial(results, smoke=False):
    """The tentpole leg: chunked gemv_psum super-stage (overlap=4) vs the
    serial plan on the 2x4 grid — parity to roundoff, chunked-launch
    instrumentation, speedup >= 1 within smoke noise on matvec AND
    rmatvec (asserted in the child)."""
    shape = (32, 256, 8 * 64) if smoke else (128, 128, 8 * 200)
    res = _run_measured(
        _PIPELINED_CODE % {"shape": repr(shape), "chunks": 4,
                           "reps": 10 if smoke else 20},
        results, "pipelined_vs_serial")
    if res is None:
        return
    res["shape"] = list(shape)
    row("fig4/pipelined_matvec", res["pipelined"]["t_matvec"],
        f"speedup={res['speedup_matvec']:.2f};"
        f"chunks={res['chunks']};parity={res['parity_matvec']:.1e}")
    row("fig4/pipelined_rmatvec", res["pipelined"]["t_rmatvec"],
        f"speedup={res['speedup_rmatvec']:.2f}")


def measured_ring_vs_pipelined(results, smoke=False):
    """The explicit software-pipelined ring schedule (collective="ring",
    DESIGN.md §10) against the PR-8 XLA-scheduled pipelined form on the
    2x4 grid: bit-exact vs its serial plan, parity-to-roundoff vs
    pipelined, ring hops instrumented, and no slower than pipelined
    within smoke noise (asserted in the child).  ``gate_ratio`` is what
    the smoke-regression gate tracks: the speedup clipped at 1.0, so a
    lucky fast baseline run can never fail honest later runs — the
    binding perf floor is the in-child 0.9 assertion."""
    shape = (32, 256, 8 * 64) if smoke else (128, 128, 8 * 200)
    res = _run_measured(
        _RING_CODE % {"shape": repr(shape), "chunks": 4,
                      "reps": 10 if smoke else 20},
        results, "ring_vs_pipelined")
    if res is None:
        return
    res["shape"] = list(shape)
    res["gate_ratio"] = min(1.0, res["speedup_vs_pipelined"])
    row("fig4/ring_matvec", res["ring"]["t_matvec"],
        f"speedup_vs_pipelined={res['speedup_vs_pipelined']:.2f};"
        f"chunks={res['chunks']};bit_vs_serial={res['bit_vs_serial']}")
    row("fig4/ring_rmatvec", res["ring"]["t_rmatvec"],
        f"speedup_vs_pipelined={res['speedup_rmatvec']:.2f}")


def modeled_scaling(results, smoke=False):
    net = NetworkModel()
    for p in (8, 64) if smoke else (8, 64, 512, 1024, 2048, 4096):
        Nm = NM_PER * p
        grid = choose_grid(p, N_T, N_D, Nm, net=net)
        assert grid == paper_grid(p) or p not in (8, 512, 1024, 2048, 4096)
        t_flat = matvec_comm_time(1, p, N_T, N_D, Nm, net=net)
        t_grid = matvec_comm_time(*grid, N_T, N_D, Nm, net=net)
        total_f64 = T_COMPUTE + t_grid
        total_mix = T_COMPUTE_MIXED + t_grid   # comm stays f64 (latency-bound)
        row(f"fig4/model_p{p}", total_mix,
            f"grid={grid[0]}x{grid[1]};comm_aware_speedup="
            f"{(T_COMPUTE + t_flat) / total_f64:.2f};"
            f"comm_only_speedup={t_flat / max(t_grid, 1e-12):.2f};"
            f"mixed_speedup={total_f64 / total_mix:.2f}")
        results["model"][f"p{p}"] = {
            "grid": list(grid), "time_s": total_mix,
            "comm_aware_speedup": (T_COMPUTE + t_flat) / total_f64,
            "mixed_speedup": total_f64 / total_mix,
        }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU shapes for the CI smoke job")
    ap.add_argument("--out", default="BENCH_fig4.json",
                    help="JSON artifact path")
    args = ap.parse_args(argv)
    results = {"smoke": bool(args.smoke), "model": {}}
    measured_8dev(results, smoke=args.smoke)
    measured_grid_vs_flat(results, smoke=args.smoke)
    measured_pipelined_vs_serial(results, smoke=args.smoke)
    measured_ring_vs_pipelined(results, smoke=args.smoke)
    modeled_scaling(results, smoke=args.smoke)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1, sort_keys=True)
    print(f"# wrote {args.out}")
    failed = sorted(k for k, v in results.items()
                    if isinstance(v, dict) and "error" in v)
    if failed:
        raise SystemExit(f"fig4: measured legs failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
