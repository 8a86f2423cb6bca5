"""Multi-pod dry-run: lower + compile every (architecture x input shape x
mesh) cell against 512 placeholder devices and extract memory / cost /
collective statistics for the roofline analysis.

MUST be executed as its own process (``python -m repro.launch.dryrun``):
the module sets ``XLA_FLAGS`` before any jax import, which pins the device
count for the whole process, and pins that process to the CPU.  Importing
it from another module raises, so no program that holds a chip can pick up
the flag by accident.

Methodology (two compiles per cell):

1. PRODUCTION compile — the real config (lax.scan over layers, chunked
   attention): proves the (arch x shape x mesh) cell lowers and compiles,
   and provides ``memory_analysis()`` (true per-device allocation).

2. ANALYSIS compiles — XLA's HloCostAnalysis counts a while-loop body
   ONCE, so scanned models hide ~L x flops/bytes/collectives.  We lower
   python-unrolled variants (``analysis_mode=True``) at reduced layer
   counts and extrapolate linearly:
       dense/moe/vlm/ssm:  cost(L) = c1 + (L-1) * (c2 - c1)
       hybrid:             base + L*mamba_per + n_shared*shared_per
       enc-dec:            base + Le*enc_per + Ld*dec_per
   (validated in tests/test_roofline.py against a fully-unrolled model).

Usage:
  python -m repro.launch.dryrun --arch all --shape all --mesh both \
      --out experiments/dryrun
  python -m repro.launch.dryrun --arch fftmatvec --mesh single
"""

import os

if __name__ != "__main__":
    raise ImportError("repro.launch.dryrun runs as its own process: "
                      "python -m repro.launch.dryrun")
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ARCH_IDS, SHAPES, get_config, input_shard_specs,
                           input_specs, shape_applicable)
from repro.configs.fftmatvec_paper import PAPER_SINGLE
from repro.backend import DispatchTable
from repro.core import ExecOpts, FFTMatvec, PrecisionConfig
from repro.models import api
from repro.models.sharding_ctx import DEFAULT_RULES, axis_rules
from repro.optim import AdamW, constant_schedule
from .mesh import dp_axes, fftmatvec_grid, make_production_mesh, mesh_shape_dict
from repro.jax_compat import set_mesh
from .roofline import (cost_analysis_dict, hbm_floor_bytes, model_flops,
                       parse_collectives, roofline_fraction, roofline_terms,
                       useful_ratio)


# ---------------------------------------------------------------------------
# lowering helpers
# ---------------------------------------------------------------------------

def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _lower_step(cfg, shape, mesh, *, fsdp="data", opt_state_dtype="float32"):
    """Lower+compile one step of the given kind for ``cfg`` on ``mesh``.

    Lowered inside ``jax.set_mesh`` + logical axis rules so the models'
    activation sharding constraints resolve (sharding_ctx.py)."""
    with set_mesh(mesh), axis_rules(DEFAULT_RULES, mesh_shape_dict(mesh)):
        return _lower_step_inner(cfg, shape, mesh, fsdp=fsdp,
                                 opt_state_dtype=opt_state_dtype)


def _lower_step_inner(cfg, shape, mesh, *, fsdp="data",
                      opt_state_dtype="float32"):
    msd = mesh_shape_dict(mesh)
    dp = dp_axes(mesh)
    dp = dp[0] if len(dp) == 1 else dp
    opt = AdamW(schedule=constant_schedule(1e-4), state_dtype=opt_state_dtype)
    batch_specs = input_specs(cfg, shape)
    batch_shards = input_shard_specs(cfg, shape, dp=dp, mesh_shape=msd)

    if shape.kind == "train":
        state_specs = api.train_state_specs(cfg, opt, msd, fsdp=fsdp)
        abstract_state = jax.eval_shape(
            lambda: api.init_train_state(cfg, opt, jax.random.PRNGKey(0)))
        from repro.models.transformer import _shard
        lsh = NamedSharding(mesh, P(_shard(shape.batch, dp, msd), None,
                                    _shard(cfg.vocab, "model", msd)))
        step = api.make_train_step(cfg, opt, logit_sharding=lsh)
        lowered = jax.jit(step,
                          in_shardings=(_ns(mesh, state_specs),
                                        _ns(mesh, batch_shards)),
                          out_shardings=(_ns(mesh, state_specs), None),
                          donate_argnums=0).lower(abstract_state, batch_specs)
    elif shape.kind == "prefill":
        pspecs = api.param_specs(cfg, msd, fsdp=fsdp)
        abstract_params = jax.eval_shape(
            lambda: api.init_params(cfg, jax.random.PRNGKey(0)))
        dspecs = api.decode_state_specs(cfg, shape.batch, shape.seq, msd, dp=dp)
        fn = lambda p, b: api.prefill_step(cfg, p, b, shape.seq)
        lowered = jax.jit(fn,
                          in_shardings=(_ns(mesh, pspecs),
                                        _ns(mesh, batch_shards)),
                          out_shardings=(None, _ns(mesh, dspecs))).lower(
            abstract_params, batch_specs)
    else:  # decode: one new token against a filled cache of length shape.seq
        pspecs = api.param_specs(cfg, msd, fsdp=fsdp)
        abstract_params = jax.eval_shape(
            lambda: api.init_params(cfg, jax.random.PRNGKey(0)))
        dspecs = api.decode_state_specs(cfg, shape.batch, shape.seq, msd, dp=dp)
        abstract_state = jax.eval_shape(
            lambda: api.init_decode_state(cfg, shape.batch, shape.seq))
        fn = lambda p, s, t: api.decode_step(cfg, p, s, t)
        lowered = jax.jit(fn,
                          in_shardings=(_ns(mesh, pspecs), _ns(mesh, dspecs),
                                        _ns(mesh, batch_shards["tokens"])),
                          out_shardings=(None, _ns(mesh, dspecs)),
                          donate_argnums=1).lower(
            abstract_params, abstract_state, batch_specs["tokens"])
    return lowered.compile()


def _cost_vector(compiled):
    """(flops, bytes, collective_bytes, counts, bytes_by_type) per device."""
    cost = cost_analysis_dict(compiled)
    txt = compiled.as_text()
    coll = parse_collectives(txt)
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "bytes_floor": float(hbm_floor_bytes(txt)),
            "coll_bytes": float(coll.total_bytes),
            "coll_counts": coll.counts,
            "coll_bytes_by_type": coll.bytes_by_type}


def _combine(c0, deltas_and_mults):
    """c0 + sum_i mult_i * delta_i over the scalar fields + count dicts."""
    out = {k: (dict(v) if isinstance(v, dict) else v) for k, v in c0.items()}
    for delta, mult in deltas_and_mults:
        for k in ("flops", "bytes", "bytes_floor", "coll_bytes"):
            out[k] += mult * delta[k]
        for dk in ("coll_counts", "coll_bytes_by_type"):
            for t, v in delta[dk].items():
                out[dk][t] = out[dk].get(t, 0) + mult * v
    return out


def _diff(c2, c1):
    d = {k: c2[k] - c1[k] for k in ("flops", "bytes", "bytes_floor",
                                    "coll_bytes")}
    for dk in ("coll_counts", "coll_bytes_by_type"):
        d[dk] = {t: c2[dk].get(t, 0) - c1[dk].get(t, 0)
                 for t in set(c2[dk]) | set(c1[dk])}
    return d


def _analysis_overrides(cfg, shape):
    """Analysis-mode knobs: unrolled loops with bounded unroll counts.

    Attention: chunk sizes raised to seq/8 — chunked-attention flops do
    NOT depend on the chunking, so this is exact (block_causal gains a
    small diagonal-granularity term, matching production behaviour).

    SSMs: flops DO depend on the chunk.  Mamba-1's associative scan is a
    ~2% share with only a log(c) dependence -> cap at 32 unrolled chunks.
    SSD's intra-chunk term scales linearly with c (~6% share at c=128) ->
    cap at 128 unrolled chunks (<=+6% layer-flop overcount at 32k,
    documented in EXPERIMENTS.md §Roofline)."""
    seq = shape.seq if shape.kind != "decode" else 1
    ov = dict(
        analysis_mode=True, scan_layers=False,
        attn_q_chunk=max(cfg.attn_q_chunk, shape.seq // 8),
        attn_kv_chunk=max(cfg.attn_kv_chunk, shape.seq // 8),
    )
    if cfg.mamba_version == 1 or cfg.family == "ssm":
        ov["ssm_chunk"] = max(cfg.ssm_chunk, seq // 32)
    elif cfg.family == "hybrid":
        ov["ssm_chunk"] = max(cfg.ssm_chunk, seq // 64)
    return ov


def analysis_cost(arch_cfg, shape, mesh, *, fsdp="data",
                  opt_state_dtype="float32"):
    """Per-step cost vector via reduced-layer unrolled lowerings."""
    import functools
    global _lower_step
    base_lower = _lower_step
    _lower_step = functools.partial(base_lower, fsdp=fsdp,
                                    opt_state_dtype=opt_state_dtype)
    try:
        return _analysis_cost_inner(arch_cfg, shape, mesh)
    finally:
        _lower_step = base_lower


def _analysis_cost_inner(arch_cfg, shape, mesh):
    ov = _analysis_overrides(arch_cfg, shape)
    if arch_cfg.family == "hybrid":
        v0 = _cost_vector(_lower_step(
            arch_cfg.replace(n_layers=1, shared_attn_every=2, **ov), shape, mesh))
        v1 = _cost_vector(_lower_step(
            arch_cfg.replace(n_layers=2, shared_attn_every=3, **ov), shape, mesh))
        v2 = _cost_vector(_lower_step(
            arch_cfg.replace(n_layers=1, shared_attn_every=1, **ov), shape, mesh))
        mamba_per = _diff(v1, v0)
        shared_per = _diff(v2, v0)
        n_shared = arch_cfg.n_layers // arch_cfg.shared_attn_every
        # v0 = base + 1 * mamba_per  ->  total = v0 + (L-1)*mamba + n_sh*shared
        return _combine(v0, [(mamba_per, arch_cfg.n_layers - 1),
                             (shared_per, n_shared)])
    if arch_cfg.family == "encdec":
        v0 = _cost_vector(_lower_step(
            arch_cfg.replace(n_layers=1, enc_layers=1, **ov), shape, mesh))
        v1 = _cost_vector(_lower_step(
            arch_cfg.replace(n_layers=1, enc_layers=2, **ov), shape, mesh))
        v2 = _cost_vector(_lower_step(
            arch_cfg.replace(n_layers=2, enc_layers=1, **ov), shape, mesh))
        enc_per = _diff(v1, v0)
        dec_per = _diff(v2, v0)
        if shape.kind == "decode":   # encoder not run at decode
            enc_mult = 0
        else:
            enc_mult = arch_cfg.enc_layers - 1
        return _combine(v0, [(enc_per, enc_mult),
                             (dec_per, arch_cfg.n_layers - 1)])
    v1 = _cost_vector(_lower_step(arch_cfg.replace(n_layers=1, **ov), shape, mesh))
    v2 = _cost_vector(_lower_step(arch_cfg.replace(n_layers=2, **ov), shape, mesh))
    return _combine(v1, [(_diff(v2, v1), arch_cfg.n_layers - 1)])


def _param_counts(cfg, abstract_params):
    total = sum(p.size for p in jax.tree.leaves(abstract_params))
    active = total
    if cfg.n_experts and cfg.top_k:
        expert = sum(p.size for k, p in abstract_params["layers"].items()
                     if k in ("wg", "wu", "wd"))
        active = total - expert + expert * cfg.top_k // cfg.n_experts
    return total, active


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------

SPECIAL_OVERRIDES = ("fsdp", "opt_state_dtype", "precision", "use_pallas")


def lower_lm_cell(arch: str, shape_name: str, mesh, *, overrides=None,
                  skip_analysis=False):
    cfg = get_config(arch)
    overrides = dict(overrides or {})
    special = {k: overrides.pop(k) for k in list(overrides)
               if k in SPECIAL_OVERRIDES}
    fsdp = special.get("fsdp", "data")
    fsdp = None if fsdp in (None, "none") else fsdp
    osd = special.get("opt_state_dtype", "float32")
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {"skipped": reason}
    n_devices = mesh.devices.size

    abstract_params = jax.eval_shape(
        lambda: api.init_params(cfg, jax.random.PRNGKey(0)))
    n_params, n_active = _param_counts(cfg, abstract_params)
    tokens = (shape.batch * shape.seq if shape.kind in ("train", "prefill")
              else shape.batch)

    # 1. production compile (scan over layers) — compilability + memory
    t0 = time.time()
    compiled = _lower_step(cfg, shape, mesh, fsdp=fsdp, opt_state_dtype=osd)
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    prod_coll = parse_collectives(compiled.as_text())

    rec = {
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": mem.peak_memory_in_bytes,
        },
        "production_collectives": prod_coll.to_dict(),
        "compile_s": compile_s,
        "n_devices": n_devices,
        "n_params": n_params,
        "n_active_params": n_active,
        "tokens_per_step": tokens,
    }

    # 2. analysis compiles — per-step flops/bytes/collectives
    if not skip_analysis:
        t1 = time.time()
        cost = analysis_cost(cfg, shape, mesh, fsdp=fsdp, opt_state_dtype=osd)
        rec["analysis_compile_s"] = time.time() - t1
        from .roofline import CollectiveStats
        coll = CollectiveStats(cost["coll_counts"], cost["coll_bytes_by_type"],
                               int(cost["coll_bytes"]))
        terms = roofline_terms({"flops": cost["flops"],
                                "bytes accessed": cost["bytes"]}, coll,
                               bytes_floor=cost["bytes_floor"])
        mf = model_flops(n_params, n_active, tokens, shape.kind)
        rec.update({
            "collectives": coll.to_dict(),
            "roofline": terms,
            "model_flops": mf,
            "useful_flop_ratio": useful_ratio(mf, terms["flops_per_device"],
                                              n_devices),
            "roofline_fraction": roofline_fraction(
                mf, terms["step_time_bound_s"], n_devices),
        })
    return rec


# ---------------------------------------------------------------------------
# FFTMatvec cells (the paper's own workload, weak-scaled to the mesh)
# ---------------------------------------------------------------------------

def lower_fftmatvec_cell(mesh, *, precision="sssss", adjoint=False,
                         weak_scale=True, use_pallas=False):
    row_axes, col_axes = fftmatvec_grid(mesh)
    p = mesh.devices.size
    fc = PAPER_SINGLE.weak_scaled(p) if weak_scale else PAPER_SINGLE
    row = (row_axes if len(row_axes) > 1 else
           (row_axes[0] if row_axes else None))
    col = col_axes if len(col_axes) > 1 else col_axes[0]
    cfgp = PrecisionConfig.from_string(precision)
    opts = ExecOpts(dispatch=DispatchTable(force="pallas")) if use_pallas \
        else ExecOpts()
    K = fc.N_t + 1
    dt_of = {"d": jnp.float64, "s": jnp.float32, "h": jnp.bfloat16}
    F_hat = jax.ShapeDtypeStruct((K, fc.N_d, fc.N_m), dt_of[cfgp.gemv])
    io_dt = dt_of[cfgp.highest()]

    t0 = time.time()
    if adjoint:
        vec = jax.ShapeDtypeStruct((fc.N_d, fc.N_t), io_dt)
        vec_spec = P(row, None) if row is not None else P(None, None)
        fn = lambda fr, fi, d: FFTMatvec(
            fr, fi, fc.N_t, cfgp, opts, mesh, row, col).rmatvec(d)
    else:
        vec = jax.ShapeDtypeStruct((fc.N_m, fc.N_t), io_dt)
        vec_spec = P(col, None)
        fn = lambda fr, fi, m: FFTMatvec(
            fr, fi, fc.N_t, cfgp, opts, mesh, row, col).matvec(m)
    in_sh = (NamedSharding(mesh, P(None, row, col)),
             NamedSharding(mesh, P(None, row, col)),
             NamedSharding(mesh, vec_spec))
    compiled = jax.jit(fn, in_shardings=in_sh).lower(F_hat, F_hat, vec).compile()
    compile_s = time.time() - t0

    cost = _cost_vector(compiled)      # no scans in the pipeline -> exact
    mem = compiled.memory_analysis()
    from .roofline import CollectiveStats
    coll = CollectiveStats(cost["coll_counts"], cost["coll_bytes_by_type"],
                           int(cost["coll_bytes"]))
    terms = roofline_terms({"flops": cost["flops"],
                            "bytes accessed": cost["bytes"]}, coll,
                           bytes_floor=cost["bytes_floor"])
    mf = 8.0 * K * fc.N_d * fc.N_m     # complex block-diag matvec real flops
    rec = {
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": mem.peak_memory_in_bytes,
        },
        "collectives": coll.to_dict(),
        "roofline": terms,
        "model_flops": mf,
        "useful_flop_ratio": useful_ratio(mf, terms["flops_per_device"], p),
        "roofline_fraction": roofline_fraction(
            mf, terms["step_time_bound_s"], p),
        "n_devices": p,
        "problem": {"N_t": fc.N_t, "N_d": fc.N_d, "N_m": fc.N_m,
                    "precision": precision, "adjoint": adjoint},
        "compile_s": compile_s,
    }
    return rec


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id, 'all', or 'fftmatvec'")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (perf iterations)")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--skip-analysis", action="store_true")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    if "d" in str(overrides.get("precision", "")):
        # paper-faithful FP64 ladder needs x64 (CPU validation only; the
        # TPU-native ladder is f32/bf16)
        jax.config.update("jax_enable_x64", True)

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    for multi_pod in meshes:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
        for arch in archs:
            cells = ([("fftmatvec", "F"), ("fftmatvec", "Fstar")]
                     if arch == "fftmatvec" else
                     [(arch, s) for s in shapes])
            for a, s in cells:
                name = f"{a}__{s}__{mesh_name}__{args.tag}"
                path = os.path.join(args.out, name + ".json")
                print(f"=== {name} ===", flush=True)
                try:
                    t0 = time.time()
                    if a == "fftmatvec":
                        rec = lower_fftmatvec_cell(
                            mesh,
                            precision=overrides.get("precision", "sssss"),
                            adjoint=(s == "Fstar"),
                            use_pallas=overrides.get("use_pallas", False))
                    else:
                        rec = lower_lm_cell(a, s, mesh, overrides=overrides,
                                            skip_analysis=args.skip_analysis)
                    rec["cell"] = {"arch": a, "shape": s, "mesh": mesh_name,
                                   "tag": args.tag, "overrides": overrides}
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                    if "skipped" in rec:
                        print(f"  SKIP: {rec['skipped']}")
                    elif "roofline" in rec:
                        r = rec["roofline"]
                        print(f"  ok total={time.time() - t0:.0f}s "
                              f"compute={r['compute_s'] * 1e3:.2f}ms "
                              f"memory={r['memory_s'] * 1e3:.2f}ms "
                              f"coll={r['collective_s'] * 1e3:.2f}ms "
                              f"dom={r['dominant']} "
                              f"useful={rec.get('useful_flop_ratio', 0):.2f} "
                              f"peak={rec['memory']['peak_bytes'] / 2 ** 30:.2f}GiB",
                              flush=True)
                    else:
                        print(f"  ok (production only) "
                              f"peak={rec['memory']['peak_bytes'] / 2 ** 30:.2f}GiB")
                except Exception as e:
                    with open(path, "w") as f:
                        json.dump({"error": str(e),
                                   "traceback": traceback.format_exc(),
                                   "cell": {"arch": a, "shape": s,
                                            "mesh": mesh_name}}, f, indent=1)
                    print(f"  FAIL: {e}")


if __name__ == "__main__":
    main()
