"""Multi-device integration tests.

These spawn a subprocess with ``--xla_force_host_platform_device_count=8``
(the main pytest process keeps the real single device, per the dry-run
contract) and validate the 2-D-grid FFTMatvec, the comm-aware partitioner,
and a sharded train step against their single-device references.
"""

import json
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from repro.core import (NetworkModel, TPU_POD_NETWORK, choose_grid,
                        matvec_comm_time, paper_grid)
from repro.jax_compat import forced_host_devices_env


def _run(code: str) -> dict:
    out = subprocess.run([sys.executable, "-c", code],
                         env=forced_host_devices_env(8),
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.splitlines()[-1])


def test_forced_host_devices_env_pins_cpu_and_extends():
    """The child simulates its devices on the CPU (on a chip machine the
    parent holds the chip), and inherited flags and paths survive."""
    env = forced_host_devices_env(
        4, {"XLA_FLAGS": "--xla_foo=1", "PYTHONPATH": "/elsewhere",
            "JAX_PLATFORMS": "tpu"})
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"] == ("--xla_foo=1 "
                                "--xla_force_host_platform_device_count=4")
    assert env["PYTHONPATH"].endswith("/elsewhere")
    assert env["PYTHONPATH"].split(":")[0].endswith("src")


def test_fftmatvec_2d_grid_subprocess():
    res = _run(r"""
import jax, json
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from repro.core import (FFTMatvec, PrecisionConfig, dense_matvec,
                        dense_rmatvec, random_block_column, rel_l2)
from repro.jax_compat import make_mesh
mesh = make_mesh((2, 4), ("row", "col"))
Nt, Nd, Nm, S = 16, 6, 32, 3
F_col = random_block_column(jax.random.PRNGKey(0), Nt, Nd, Nm, dtype=jnp.float64)
m = jax.random.normal(jax.random.PRNGKey(1), (Nm, Nt), dtype=jnp.float64)
d = jax.random.normal(jax.random.PRNGKey(2), (Nd, Nt), dtype=jnp.float64)
op = FFTMatvec.from_block_column(F_col, mesh=mesh)
e1 = rel_l2(op.matvec(jax.device_put(m, op.m_sharding())), dense_matvec(F_col, m))
e2 = rel_l2(op.rmatvec(jax.device_put(d, op.d_sharding())), dense_rmatvec(F_col, d))
# multi-RHS: sharded matmat/rmatmat vs stacked dense references
M = jax.random.normal(jax.random.PRNGKey(3), (Nm, Nt, S), dtype=jnp.float64)
D = jax.random.normal(jax.random.PRNGKey(4), (Nd, Nt, S), dtype=jnp.float64)
e3 = rel_l2(op.matmat(jax.device_put(M, op.m_sharding(stacked=True))),
            jnp.stack([dense_matvec(F_col, M[:, :, s]) for s in range(S)], axis=-1))
e4 = rel_l2(op.rmatmat(jax.device_put(D, op.d_sharding(stacked=True))),
            jnp.stack([dense_rmatvec(F_col, D[:, :, s]) for s in range(S)], axis=-1))
# fused Gram pipelines on the mesh (exact mode) vs composed dense references
gp, gd = op.gram(space="parameter"), op.gram(space="data")
e5 = rel_l2(gp.apply(jax.device_put(m, gp.v_sharding())),
            dense_rmatvec(F_col, dense_matvec(F_col, m)))
e6 = rel_l2(gd.apply(jax.device_put(D, gd.v_sharding(stacked=True))),
            jnp.stack([dense_matvec(F_col, dense_rmatvec(F_col, D[:, :, s]))
                       for s in range(S)], axis=-1))
# collective structure of the F matvec: ONLY the phase-5 reduce
lo = jax.jit(op.matvec, in_shardings=op.m_sharding()).lower(
    jax.ShapeDtypeStruct(m.shape, m.dtype)).compile()
import re
colls = sorted(set(re.findall(
    r'(all-reduce|all-gather|reduce-scatter|all-to-all)', lo.as_text())))
print(json.dumps({"e1": e1, "e2": e2, "e3": e3, "e4": e4, "e5": e5,
                  "e6": e6, "colls": colls}))
""")
    assert res["e1"] < 1e-13 and res["e2"] < 1e-13
    assert res["e3"] < 1e-13 and res["e4"] < 1e-13
    assert res["e5"] < 1e-12 and res["e6"] < 1e-12
    assert res["colls"] == ["all-reduce"]


def test_tile_padded_planes_on_a_grid_subprocess():
    """A backend that tile-pads the planes (``tpu-pallas``) has each device
    of a 2x4 grid store its block zero-padded to whole tiles; the
    operator reads the unpadded planes back, and its answers (through the
    CPU's XLA path) equal those of planes stored unpadded, bit for bit."""
    res = _run(r"""
import jax, json
import jax.numpy as jnp
import numpy as np
from repro.core import FFTMatvec, PrecisionConfig, random_block_column
from repro.jax_compat import make_mesh
mesh = make_mesh((2, 4), ("row", "col"))
Nt, Nd, Nm = 16, 6, 128
cfg = PrecisionConfig.from_string("sssss")
F_col = random_block_column(jax.random.PRNGKey(0), Nt, Nd, Nm)
pad = FFTMatvec.from_block_column(F_col, cfg, mesh=mesh, backend="tpu-pallas")
plain = FFTMatvec.from_block_column(F_col, cfg, mesh=mesh, backend="cpu-xla")
shards = {s.data.shape for s in pad.F_hat_re.addressable_shards}
blocks = np.asarray(pad.F_hat_re).reshape(Nt + 1, 2, 8, 4, 128)
zeros = not blocks[:, :, 3:].any() and not blocks[:, :, :, :, 32:].any()
same = all(np.array_equal(np.asarray(a), np.asarray(b))
           for a, b in zip(pad.planes, (plain.F_hat_re, plain.F_hat_im)))
run = pad.with_backend("cpu-xla")
m = jax.device_put(jax.random.normal(jax.random.PRNGKey(1), (Nm, Nt)),
                   pad.m_sharding())
d = jax.device_put(jax.random.normal(jax.random.PRNGKey(2), (Nd, Nt)),
                   pad.d_sharding())
g = run.gram(space="parameter")
answers = [np.array_equal(np.asarray(a), np.asarray(b)) for a, b in (
    (run.matvec(m), plain.matvec(m)), (run.rmatvec(d), plain.rmatvec(d)),
    (g.apply(m), plain.gram(space="parameter").apply(m)))]
print(json.dumps({"shards": sorted(shards), "dims": [pad.N_d, pad.N_m],
                  "zeros": bool(zeros), "same": same, "answers": answers}))
""")
    assert res["shards"] == [[17, 8, 128]]
    assert res["dims"] == [6, 128] and res["zeros"] and res["same"]
    assert res["answers"] == [True, True, True]


def test_sharded_train_step_matches_single_device():
    res = _run(r"""
import jax, json
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.models import api
from repro.models.sharding_ctx import DEFAULT_RULES, axis_rules
from repro.optim import AdamW, constant_schedule

cfg = get_smoke_config("llama3_405b")
opt = AdamW(schedule=constant_schedule(1e-3))
key = jax.random.PRNGKey(0)
batch = {"tokens": jax.random.randint(key, (4, 32), 0, cfg.vocab)}
batch["labels"] = batch["tokens"]

# single device
state1 = api.init_train_state(cfg, opt, key)
s1, m1 = jax.jit(api.make_train_step(cfg, opt))(state1, batch)

# 2x4 mesh
from repro.jax_compat import make_mesh, set_mesh
mesh = make_mesh((2, 4), ("data", "model"))
msd = {"data": 2, "model": 4}
specs = api.train_state_specs(cfg, opt, msd, fsdp="data")
ns = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                  is_leaf=lambda x: isinstance(x, P))
state2 = api.init_train_state(cfg, opt, key)
state2 = jax.tree.map(lambda x, sh: jax.device_put(x, sh), state2, ns)
with set_mesh(mesh), axis_rules(DEFAULT_RULES, msd):
    step2 = jax.jit(api.make_train_step(cfg, opt),
                    in_shardings=(ns, None), out_shardings=(ns, None))
    s2, m2 = step2(state2, batch)
l1, l2 = float(m1["loss"]), float(m2["loss"])
diff = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
           for a, b in zip(jax.tree.leaves(s1["params"]),
                           jax.tree.leaves(s2["params"])))
print(json.dumps({"l1": l1, "l2": l2, "pdiff": diff}))
""")
    assert abs(res["l1"] - res["l2"]) < 5e-3
    assert res["pdiff"] < 5e-2


def test_flash_decoding_sequence_sharded_cache():
    """Decode with the KV-cache sequence axis sharded over 'model' must
    equal the unsharded decode (GSPMD partial-softmax reductions)."""
    res = _run(r"""
import jax, json
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.models import api

cfg = get_smoke_config("llama3_405b")  # kv=2 heads, not divisible by model=4
key = jax.random.PRNGKey(0)
params = api.init_params(cfg, key)
B, S, max_seq = 2, 16, 32
batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}
logits, state = api.prefill_step(cfg, params, batch, max_seq)
tok = jnp.ones((B, 1), jnp.int32)
ref_logits, _ = api.decode_step(cfg, params, state, tok)

from repro.jax_compat import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
msd = {"data": 2, "model": 4}
dspecs = api.decode_state_specs(cfg, B, max_seq, msd, dp="data")
assert dspecs["k"][2] is not None, "seq axis must be sharded"
ns = jax.tree.map(lambda s: NamedSharding(mesh, s), dspecs,
                  is_leaf=lambda x: isinstance(x, P))
state_sh = jax.tree.map(lambda x, sh: jax.device_put(x, sh), state, ns)
dec = jax.jit(lambda p, s, t: api.decode_step(cfg, p, s, t),
              in_shardings=(None, ns, None), out_shardings=(None, ns))
got_logits, _ = dec(params, state_sh, tok)
err = float(jnp.max(jnp.abs(got_logits - ref_logits)))
print(json.dumps({"err": err, "seq_spec": str(dspecs["k"])}))
""")
    assert res["err"] < 2e-3, res


# ---------------------------------------------------------------------------
# hierarchical collectives: 2x4 grid vs flat 1x8 on 8 simulated devices
# ---------------------------------------------------------------------------

def test_hierarchical_grid_matches_flat_subprocess():
    """The executed comm-aware grid: the 2x4 hierarchical path must match
    the flat 1x8 path (and the dense truth) to the precision-config
    tolerance for matvec, rmatvec, and the exact Gram's mid-psum; the
    mesh='auto' constructor must be reachable end to end; a reduced comm
    level must round at the comm precision while preserving the carrier
    dtype; and the reduce_scatter lowering must stay numerically exact."""
    res = _run(r"""
import jax, json
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from repro.core import (FFTMatvec, PrecisionConfig, dense_matvec,
                        dense_rmatvec, random_block_column, rel_l2)
from repro.jax_compat import make_mesh
Nt, Nd, Nm = 16, 8, 32
F_col = random_block_column(jax.random.PRNGKey(0), Nt, Nd, Nm, dtype=jnp.float64)
m = jax.random.normal(jax.random.PRNGKey(1), (Nm, Nt), dtype=jnp.float64)
d = jax.random.normal(jax.random.PRNGKey(2), (Nd, Nt), dtype=jnp.float64)
flat = FFTMatvec.from_block_column(F_col, mesh=make_mesh((1, 8), ("row", "col")))
hier = FFTMatvec.from_block_column(F_col, mesh=make_mesh((2, 4), ("row", "col")))
res = {"flat_grid": list(flat.grid_shape()), "hier_grid": list(hier.grid_shape()),
       "flat_coll": flat._collective_kind(("col",)),
       "hier_coll": hier._collective_kind(("col",))}
mv = lambda op, v: op.matvec(jax.device_put(v, op.m_sharding()))
rmv = lambda op, v: op.rmatvec(jax.device_put(v, op.d_sharding()))
res["e_mv"] = rel_l2(mv(hier, m), mv(flat, m))
res["e_rmv"] = rel_l2(rmv(hier, d), rmv(flat, d))
res["e_mv_dense"] = rel_l2(mv(hier, m), dense_matvec(F_col, m))
# exact Gram with the mid psum on the hierarchical grid
gp = hier.gram(space="parameter")
res["e_gram"] = rel_l2(gp.apply(jax.device_put(m, gp.v_sharding())),
                       dense_rmatvec(F_col, dense_matvec(F_col, m)))
gd = hier.gram(space="data")
res["e_gram_data"] = rel_l2(gd.apply(jax.device_put(d, gd.v_sharding())),
                            dense_matvec(F_col, dense_rmatvec(F_col, d)))
# mesh="auto" reaches choose_grid end to end (8 devices -> flat regime)
auto = FFTMatvec.from_block_column(F_col, mesh="auto")
res["auto_grid"] = list(auto.grid_shape())
res["e_auto"] = rel_l2(mv(auto, m), dense_matvec(F_col, m))
# reduced-precision comm: f32 rounding, f64 carrier preserved
lo = hier.with_comm("s")
out = mv(lo, m)
res["comm_dtype_f64"] = str(out.dtype) == "float64"
res["e_comm"] = rel_l2(out, dense_matvec(F_col, m))
# reduce_scatter + all_gather lowering is the same all-reduce numerically
rs = FFTMatvec.from_block_column(
    F_col, mesh=make_mesh((1, 8), ("row", "col")), collective="reduce_scatter")
res["e_rs"] = rel_l2(mv(rs, m), dense_matvec(F_col, m))
print(json.dumps(res))
""")
    assert res["flat_grid"] == [1, 8] and res["hier_grid"] == [2, 4]
    assert res["flat_coll"] == "psum" and res["hier_coll"] == "hierarchical"
    assert res["e_mv"] < 1e-13 and res["e_rmv"] < 1e-13
    assert res["e_mv_dense"] < 1e-13 and res["e_auto"] < 1e-13
    assert res["e_gram"] < 1e-12 and res["e_gram_data"] < 1e-12
    assert res["auto_grid"] == [1, 8]           # flat regime at p = 8
    assert res["comm_dtype_f64"]
    assert 1e-10 < res["e_comm"] < 1e-6         # f32 comm rounding, no more
    assert res["e_rs"] < 1e-13


def test_two_stage_reduction_instrumented_subprocess():
    """A col group spanning two mesh axes lowers to the two-stage
    (fast-tier-then-slow-tier) reduction — observable in the collective
    instrumentation, with output parity against the dense truth."""
    res = _run(r"""
import jax, json
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from repro.core import (FFTMatvec, dense_matvec, random_block_column,
                        record_stages, rel_l2)
from repro.jax_compat import make_mesh
Nt, Nd, Nm = 16, 8, 32
F_col = random_block_column(jax.random.PRNGKey(0), Nt, Nd, Nm, dtype=jnp.float64)
m = jax.random.normal(jax.random.PRNGKey(1), (Nm, Nt), dtype=jnp.float64)
mesh = make_mesh((2, 2, 2), ("row", "c1", "c2"))
op = FFTMatvec.from_block_column(F_col, mesh=mesh, row_axis="row",
                                 col_axis=("c1", "c2"))
with record_stages() as c:
    out = op.matvec(jax.device_put(m, op.m_sharding()))
print(json.dumps({"err": rel_l2(out, dense_matvec(F_col, m)),
                  "grid": list(op.grid_shape()), "counts": dict(c)}))
""")
    assert res["err"] < 1e-13
    assert res["grid"] == [2, 4]
    assert res["counts"]["psum"] == 1
    # the one psum stage launched TWO staged collectives (c2 then c1)
    assert res["counts"]["collective:hierarchical"] == 2


# ---------------------------------------------------------------------------
# psum stage semantics (single process, named axes via vmap)
# ---------------------------------------------------------------------------

def _run_psum_stage(stage, x):
    from repro.core import ExecOpts
    from repro.core.pipeline import run_stages
    opts = ExecOpts().resolve()
    f = lambda v: run_stages((stage,), v, {}, N_t=4, opts=opts)
    for ax in stage.axes:              # bind outer axes first
        f = jax.vmap(f, axis_name=ax)
    return f(x)


def test_pipelined_overlap_parity_subprocess():
    """The pipelined gemv_psum schedule (DESIGN.md §9) against its serial
    reference on a real 2x4 mesh: bit-level (row-partition-exact) parity
    for matvec/rmatvec/gram, single- and multi-RHS, with the chunked
    launches observable in the stage instrumentation."""
    res = _run(r"""
import jax, json
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from repro.core import (FFTMatvec, dense_matvec, dense_rmatvec,
                        random_block_column, record_stages, rel_l2)
from repro.jax_compat import make_mesh
Nt, Nd, Nm, S = 16, 64, 128, 3
F_col = random_block_column(jax.random.PRNGKey(0), Nt, Nd, Nm, dtype=jnp.float64)
m = jax.random.normal(jax.random.PRNGKey(1), (Nm, Nt), dtype=jnp.float64)
d = jax.random.normal(jax.random.PRNGKey(2), (Nd, Nt), dtype=jnp.float64)
M = jax.random.normal(jax.random.PRNGKey(3), (Nm, Nt, S), dtype=jnp.float64)
D = jax.random.normal(jax.random.PRNGKey(4), (Nd, Nt, S), dtype=jnp.float64)
base = FFTMatvec.from_block_column(F_col, mesh=make_mesh((2, 4), ("row", "col")))
pipe, ser = base.with_overlap(4), base.with_overlap(None)
def counts_of(fn, v, sh):
    with record_stages() as c:
        out = fn(jax.device_put(v, sh))
    return out, dict(c)
y_p, c_p = counts_of(pipe.matvec, m, pipe.m_sharding())
y_s, c_s = counts_of(ser.matvec, m, ser.m_sharding())
res = {"c_pipe": c_p, "c_ser": c_s,
       "par_mv": rel_l2(y_p, y_s),
       "e_dense": rel_l2(y_p, dense_matvec(F_col, m))}
res["par_rmv"] = rel_l2(pipe.rmatvec(jax.device_put(d, pipe.d_sharding())),
                        ser.rmatvec(jax.device_put(d, ser.d_sharding())))
res["par_mm"] = rel_l2(
    pipe.matmat(jax.device_put(M, pipe.m_sharding(stacked=True))),
    ser.matmat(jax.device_put(M, ser.m_sharding(stacked=True))))
res["par_rmm"] = rel_l2(
    pipe.rmatmat(jax.device_put(D, pipe.d_sharding(stacked=True))),
    ser.rmatmat(jax.device_put(D, ser.d_sharding(stacked=True))))
gp, gs = pipe.gram(space="parameter"), ser.gram(space="parameter")
with record_stages() as cg:
    g_out = gp.apply(jax.device_put(m, gp.v_sharding()))
res["c_gram"] = dict(cg)
res["par_gram"] = rel_l2(g_out, gs.apply(jax.device_put(m, gs.v_sharding())))
res["e_gram_dense"] = rel_l2(g_out,
                             dense_rmatvec(F_col, dense_matvec(F_col, m)))
# auto mode consults the dispatch table: 32 local output rows / sublane 8
# -> the backend's chunk depth, observable in the counter key
with record_stages() as ca:
    base.matvec(jax.device_put(m, base.m_sharding()))
res["auto_keys"] = sorted(k for k in dict(ca) if k.startswith("collective:pipelined"))
print(json.dumps(res))
""")
    # pinned K=4: one super-stage launching four chunk reductions
    assert res["c_pipe"]["gemv_psum"] == 1
    assert res["c_pipe"]["collective:pipelined:4"] == 1
    assert res["c_pipe"]["psum"] == 4 and res["c_pipe"]["gemv"] == 4
    # serial: same plan shape, one reduction, no pipelined counter
    assert res["c_ser"]["gemv_psum"] == 1 and res["c_ser"]["psum"] == 1
    assert not any(k.startswith("collective:pipelined")
                   for k in res["c_ser"])
    # row-partition-exact parity (not merely tolerance-level agreement)
    for key in ("par_mv", "par_rmv", "par_mm", "par_rmm", "par_gram"):
        assert res[key] < 1e-15, (key, res[key])
    assert res["e_dense"] < 1e-13 and res["e_gram_dense"] < 1e-12
    # the exact Gram chunks BOTH reductions (mid + final)
    assert res["c_gram"]["collective:pipelined:4"] == 2
    # auto engaged on its own at this shape
    assert res["auto_keys"] and res["auto_keys"][0].split(":")[-1] != "1"


def test_ring_overlap_parity_subprocess():
    """The explicit software-pipelined ring schedule (DESIGN.md §10) on a
    real 2x4 mesh: BITWISE parity against its serial plan in both
    directions (canonical-origin-order invariant), exact agreement with
    the PR-8 pipelined schedule, and the ring hops observable in the
    instrumentation."""
    res = _run(r"""
import jax, json
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from repro.core import (FFTMatvec, dense_matvec, dense_rmatvec,
                        random_block_column, record_stages, rel_l2)
from repro.jax_compat import make_mesh
Nt, Nd, Nm, S = 16, 64, 128, 3
F_col = random_block_column(jax.random.PRNGKey(0), Nt, Nd, Nm, dtype=jnp.float64)
m = jax.random.normal(jax.random.PRNGKey(1), (Nm, Nt), dtype=jnp.float64)
d = jax.random.normal(jax.random.PRNGKey(2), (Nd, Nt), dtype=jnp.float64)
M = jax.random.normal(jax.random.PRNGKey(3), (Nm, Nt, S), dtype=jnp.float64)
mesh = make_mesh((2, 4), ("row", "col"))
base = FFTMatvec.from_block_column(F_col, mesh=mesh, collective="ring")
ring, ser = base.with_overlap(4), base.with_overlap(None)
def counts_of(fn, v, sh):
    with record_stages() as c:
        out = fn(jax.device_put(v, sh))
    return out, dict(c)
y_r, c_r = counts_of(ring.matvec, m, ring.m_sharding())
y_s, c_s = counts_of(ser.matvec, m, ser.m_sharding())
res = {"c_ring": c_r, "c_ser": c_s,
       "bit_mv": bool(jnp.array_equal(y_r, y_s)),
       "e_dense": rel_l2(y_r, dense_matvec(F_col, m))}
r_r = ring.rmatvec(jax.device_put(d, ring.d_sharding()))
r_s = ser.rmatvec(jax.device_put(d, ser.d_sharding()))
res["bit_rmv"] = bool(jnp.array_equal(r_r, r_s))
res["e_rmv"] = rel_l2(r_r, dense_rmatvec(F_col, d))
res["bit_mm"] = bool(jnp.array_equal(
    ring.matmat(jax.device_put(M, ring.m_sharding(stacked=True))),
    ser.matmat(jax.device_put(M, ser.m_sharding(stacked=True)))))
# vs the PR-8 pipelined (XLA-scheduled) form: same chunking, same math
pipe = FFTMatvec.from_block_column(F_col, mesh=mesh).with_overlap(4)
res["par_vs_pipelined"] = rel_l2(
    y_r, pipe.matvec(jax.device_put(m, pipe.m_sharding())))
# auto overlap keeps the ring schedule: the counter key carries the kind
with record_stages() as ca:
    base.matvec(jax.device_put(m, base.m_sharding()))
res["auto_keys"] = sorted(k for k in dict(ca)
                          if k.startswith("collective:ring:"))
print(json.dumps(res))
""")
    # K=4 chunks x (g-1)=3 ppermute hops over the 4-device col group; the
    # explicit schedule defers each chunk's reduction behind the next gemv
    assert res["c_ring"]["gemv_psum"] == 1
    assert res["c_ring"]["collective:ring:4"] == 1
    assert res["c_ring"]["collective:ring"] == 12
    assert res["c_ring"]["psum"] == 4 and res["c_ring"]["gemv"] == 4
    # serial ring: one reduction, 3 hops, no pipeline counter
    assert res["c_ser"]["psum"] == 1
    assert res["c_ser"]["collective:ring"] == 3
    assert not any(k.startswith("collective:ring:4") for k in res["c_ser"])
    assert not any(k.endswith(":fallback") for k in res["c_ring"])
    # bit-exact against serial (not merely roundoff agreement)
    assert res["bit_mv"] and res["bit_rmv"] and res["bit_mm"]
    assert res["e_dense"] < 1e-13 and res["e_rmv"] < 1e-13
    assert res["par_vs_pipelined"] < 1e-15
    # auto mode engaged the ring schedule at depth > 1 on its own
    assert res["auto_keys"] and res["auto_keys"][0].split(":")[-1] != "1"


def test_calibrate_overlap_real_measure_roundtrip(tmp_path):
    """The real calibration path end to end: the forced-host-devices
    measurement child runs the four ring legs, the efficiency lands in
    the cache under the backend fingerprint, a fresh cache instance
    reloads it without re-measuring, and the calibrated NetworkModel
    carries it."""
    from repro.backend import (calibrate_overlap, calibrated_network,
                               resolve_backend)
    from repro.tune import TuningCache

    spec = resolve_backend(None)
    cache = TuningCache(tmp_path / "tune.json")
    eff = calibrate_overlap(spec, cache=cache, chunks=4, devices=8,
                            repeats=3)
    assert 0.0 <= eff <= 1.0
    entry = cache.get_overlap(spec)
    assert entry["efficiency"] == eff and entry["chunks"] == 4
    assert set(entry["times"]) == {"t_serial", "t_pipelined",
                                   "t_collective", "t_chunk_collective"}

    def boom(chunks):
        raise AssertionError("persisted calibration must not re-measure")
    fresh = TuningCache(cache.path)
    assert calibrate_overlap(spec, measure=boom, cache=fresh) == eff
    net = calibrated_network(spec, fresh)
    assert net.overlap_calibrated and net.overlap_efficiency == eff


def test_pipelined_declines_at_thin_shapes_subprocess():
    """Auto overlap must decline (K = 1, serial counters intact) when the
    local contraction is too thin to chunk — the existing distributed
    suite's tiny shapes keep their exact collective censuses."""
    res = _run(r"""
import jax, json
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from repro.core import (FFTMatvec, dense_matvec, random_block_column,
                        record_stages, rel_l2)
from repro.jax_compat import make_mesh
Nt, Nd, Nm = 16, 6, 32
F_col = random_block_column(jax.random.PRNGKey(0), Nt, Nd, Nm, dtype=jnp.float64)
m = jax.random.normal(jax.random.PRNGKey(1), (Nm, Nt), dtype=jnp.float64)
op = FFTMatvec.from_block_column(F_col, mesh=make_mesh((2, 4), ("row", "col")))
with record_stages() as c:
    out = op.matvec(jax.device_put(m, op.m_sharding()))
print(json.dumps({"err": rel_l2(out, dense_matvec(F_col, m)),
                  "counts": dict(c)}))
""")
    assert res["err"] < 1e-13
    # 3 local rows < 2 sublanes: the super-stage ran its serial expansion
    assert res["counts"]["gemv_psum"] == 1
    assert res["counts"]["psum"] == 1 and res["counts"]["gemv"] == 1
    assert not any(k.startswith("collective:pipelined")
                   for k in res["counts"])


def test_psum_restores_carrier_dtype():
    """Regression: a psum at a low comm level must reduce at that level
    but hand the next stage the *incoming* carrier dtype — the old code
    left the carrier downgraded."""
    from repro.core.pipeline import Stage
    st = Stage("psum", "s", axis="col")
    # 1 + 2^-40 is exact in f64, rounds to 1 in f32: the comm rounding is
    # visible in the value while the carrier dtype survives
    x = jnp.array([[1.0 + 2.0 ** -40], [1.0]], jnp.float64)[:, :, None]
    out = _run_psum_stage(st, x)
    assert out.dtype == jnp.float64
    assert float(out[0, 0, 0]) == 2.0            # f32 comm dropped the bit
    hi = _run_psum_stage(Stage("psum", "d", axis="col"), x)
    assert float(hi[0, 0, 0]) == 2.0 + 2.0 ** -40   # d comm keeps it


def test_psum_plane_pair_carrier():
    """A (re, im) plane-pair carrier reduces plane-wise with dtypes
    preserved (the Gram mid-psum case)."""
    from repro.core.pipeline import Stage
    st = Stage("psum", "s", axis="col")
    re = jnp.ones((2, 1, 3), jnp.float64)
    im = 2.0 * jnp.ones((2, 1, 3), jnp.float64)
    from repro.core import ExecOpts
    from repro.core.pipeline import run_stages
    opts = ExecOpts().resolve()
    out = jax.vmap(lambda p: run_stages((st,), p, {}, N_t=4, opts=opts),
                   axis_name="col")((re, im))
    assert out[0].dtype == out[1].dtype == jnp.float64
    assert float(out[0][0, 0, 0]) == 2.0 and float(out[1][0, 0, 0]) == 4.0


def test_hierarchical_collective_counts():
    """Stage-count instrumentation for the two-stage reduction, and the
    collective-kind validation."""
    from repro.core import record_stages
    from repro.core.pipeline import Stage
    st = Stage("psum", "d", axis=("row", "col"), collective="hierarchical",
               groups=(2, 2))
    x = jnp.ones((2, 2, 1, 4), jnp.float64)
    with record_stages() as c:
        out = _run_psum_stage(st, x)
    assert float(out[0, 0, 0, 0]) == 4.0
    assert c["psum"] == 1 and c["collective:hierarchical"] == 2
    with record_stages() as c:
        _run_psum_stage(Stage("psum", "d", axis=("row", "col")), x)
    assert c["collective:psum"] == 1             # flat: ONE fused all-reduce
    with pytest.raises(ValueError, match="collective"):
        Stage("psum", "d", axis="col", collective="bogus")
    with pytest.raises(ValueError, match="groups"):
        Stage("psum", "d", axis="col", groups=(2, 4))


# ---------------------------------------------------------------------------
# communication-aware partitioning (pure host-side model)
# ---------------------------------------------------------------------------

def test_paper_grid_shapes():
    assert paper_grid(8) == (1, 8)
    assert paper_grid(512) == (1, 512)
    assert paper_grid(1024) == (8, 128)
    assert paper_grid(2048) == (8, 256)
    assert paper_grid(4096) == (16, 256)


def test_choose_grid_small_is_single_row():
    """Paper: p_r = 1 is optimal up to ~512 devices."""
    for p in (8, 64, 256, 512):
        p_r, p_c = choose_grid(p, N_t=1000, N_d=100, N_m=5000 * p)
        assert p_r == 1, (p, p_r)


def test_choose_grid_large_uses_rows():
    """Beyond one network tier, multi-row grids win (paper: 8-16 rows)."""
    for p in (1024, 2048, 4096):
        p_r, p_c = choose_grid(p, N_t=1000, N_d=100, N_m=5000 * p)
        assert p_r > 1, (p, p_r)
        assert p_r * p_c == p
    # and the modeled time at the paper's grid beats single-row
    t_paper = matvec_comm_time(16, 256, 1000, 100, 5000 * 4096)
    t_flat = matvec_comm_time(1, 4096, 1000, 100, 5000 * 4096)
    assert t_paper < t_flat


def test_network_model_monotonic_in_latency():
    slow = NetworkModel(alpha_inter=1e-3)
    fast = NetworkModel(alpha_inter=1e-6)
    t_s = matvec_comm_time(1, 4096, 1000, 100, 5000 * 4096, net=slow)
    t_f = matvec_comm_time(1, 4096, 1000, 100, 5000 * 4096, net=fast)
    assert t_s > t_f


def test_choose_grid_agrees_with_paper_grid_at_published_counts():
    """Acceptance: under the default NetworkModel the modeled optimum IS
    the published Frontier grid at every device count the paper reports
    (§4.2.2) — the model and the measured grids no longer disagree."""
    for p in (8, 512, 1024, 2048, 4096):
        assert choose_grid(p, N_t=1000, N_d=100, N_m=5000 * p) \
            == paper_grid(p), p


def _fake_mesh(shape, axes):
    return SimpleNamespace(devices=SimpleNamespace(shape=shape),
                           axis_names=axes)


def test_fftmatvec_grid_consistent_with_choose_grid():
    """launch.mesh.fftmatvec_grid is the same cost model restricted to
    the splits a mesh can realize: flat within one pod, rows = ('pod',)
    across pods — and the chosen split minimizes matvec_comm_time among
    the realizable ones."""
    from repro.launch.mesh import fftmatvec_grid

    single = _fake_mesh((16, 16), ("data", "model"))
    assert fftmatvec_grid(single) == ((), ("data", "model"))

    multi = _fake_mesh((2, 16, 16), ("pod", "data", "model"))
    rows, cols = fftmatvec_grid(multi)
    assert rows == ("pod",) and cols == ("data", "model")
    # optimality among realizable prefix splits under the same model
    p = 512
    costs = {p_r: matvec_comm_time(p_r, p // p_r, 1000, 100, 5000 * p,
                                   net=TPU_POD_NETWORK)
             for p_r in (1, 2, 32)}          # prefix products of (2,16,16)
    assert min(costs, key=costs.get) == 2
    # the flat regime threshold mirrors choose_grid's
    assert choose_grid(256, 1000, 100, 5000 * 256,
                       net=TPU_POD_NETWORK) == (1, 256)


# ---------------------------------------------------------------------------
# pipelined-collective cost term (DESIGN.md §9) — pure host-side model
# ---------------------------------------------------------------------------

def test_overlap_term_zero_efficiency_never_wins():
    """With nothing hidden, chunking only multiplies latency trees: the
    pipelined cost must dominate the flat collective at every depth —
    this is what keeps the model honest about small messages."""
    net = NetworkModel(overlap_efficiency=0.0)
    for spans in (False, True):
        for nbytes in (8 * 1024, 8 * 10 ** 6):
            serial = net.collective_cost(8, nbytes, spans)
            for k in (2, 4, 16):
                assert net.collective_cost(8, nbytes, spans, chunks=k) \
                    >= serial


def test_overlap_term_hides_bandwidth_not_latency():
    """Default efficiency: a bandwidth-dominated collective gets cheaper
    under chunking (most of each chunk's wire time hides under the next
    chunk's compute), a latency-bound one gets strictly worse (the log2
    tree replicates per chunk and cannot be divided)."""
    net = NetworkModel()
    big, small = 512 * 10 ** 6, 64
    assert net.collective_cost(8, big, True, chunks=4) \
        < net.collective_cost(8, big, True)
    assert net.collective_cost(8, small, True, chunks=4) \
        > net.collective_cost(8, small, True)
    # perfect overlap floors at ONE chunk's cost, never below the final
    # chunk's exposed reduction
    perfect = NetworkModel(overlap_efficiency=1.0)
    t4 = perfect.collective_cost(8, big, True, chunks=4)
    assert t4 == pytest.approx(
        perfect.collective_cost(8, big / 4, True), rel=1e-12)


def test_choose_grid_overlap_consistency():
    """The serial-schedule contract is pinned: ``chunks=1`` reproduces
    the paper grids everywhere.  A chunked schedule re-costs every
    candidate and must still return a valid divisor grid no worse (under
    its own schedule) than both the serial optimum and the flat grid."""
    for p in (8, 512, 1024, 2048, 4096):
        assert choose_grid(p, 1000, 100, 5000 * p, chunks=1) \
            == paper_grid(p), p
    p = 1024
    for k in (2, 4):
        p_r, p_c = choose_grid(p, 1000, 100, 5000 * p, chunks=k)
        assert p_r * p_c == p and p % p_r == 0
        t_best = matvec_comm_time(p_r, p_c, 1000, 100, 5000 * p, chunks=k)
        for other in (paper_grid(p), (1, p)):
            assert t_best <= matvec_comm_time(*other, 1000, 100, 5000 * p,
                                              chunks=k) + 1e-15


def test_fftmatvec_grid_threads_chunks():
    """launch.mesh.fftmatvec_grid prices realizable splits under the
    schedule the run will execute: the chunks argument reaches the cost
    model (same splits at this scale, but the call path is exercised)."""
    from repro.launch.mesh import fftmatvec_grid
    multi = _fake_mesh((2, 16, 16), ("pod", "data", "model"))
    rows, cols = fftmatvec_grid(multi, chunks=4)
    assert tuple(rows) + tuple(cols) == ("pod", "data", "model")


def test_fftmatvec_grid_consumes_calibrated_overlap(tmp_path):
    """The launch-layer end of the calibration loop: handing
    fftmatvec_grid a TuningCache routes the persisted measured efficiency
    into the network model it prices splits with — equivalent to passing
    the calibrated model explicitly, and distinct from the stale default
    at constants where the bounded overlap term flips the split."""
    from repro.backend import XLA_REF, calibrated_network
    from repro.launch.mesh import fftmatvec_grid
    from repro.tune import TuningCache

    cache = TuningCache(tmp_path / "tune.json")
    cache.put_overlap(XLA_REF, 0.95, chunks=2)
    cache.save()
    # constants where eff 0.7 vs 0.95 picks a different row split under
    # the compute-bounded overlap term (mirrors the choose_grid flip
    # test in tests/test_overlap.py, restricted to mesh-realizable grids)
    net = NetworkModel(devices_per_tier=256, flat_grid_max=256,
                       alpha_intra=8e-7, alpha_inter=1.3e-5,
                       bw_intra=2.7e10, bw_inter=2.7e9)
    mesh = _fake_mesh((4, 2, 128), ("outer", "pod", "model"))
    kw = dict(N_t=1000, N_d=100, n_m_per_device=5000, chunks=2,
              hide_s=9e-5)
    stale = fftmatvec_grid(mesh, net=net, **kw)
    cal = fftmatvec_grid(mesh, net=net, spec=XLA_REF, cache=cache, **kw)
    assert cal == fftmatvec_grid(
        mesh, net=calibrated_network(XLA_REF, cache, base=net), **kw)
    assert stale == (("outer", "pod"), ("model",))
    assert cal == (("outer",), ("pod", "model"))
