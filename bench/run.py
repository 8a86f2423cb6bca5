#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is a traffic file ``bench/workloads/<cell>.json``, which names its
configuration ``bench/configs/<config>.json``; per-layer metrics are read
by ``bench/metrics/<metric>.py``.  All three are found by name, so a new
cell, configuration or metric is a new file.

A run makes the block column and the inputs on the device from the seed,
builds the operator through the program's own set-up, warms the cell's
programs (all of it is ``setup_s``), then runs the cell's calls back to
back for ``--seconds``: closed loop, each call waited for, as a Krylov
iteration waits for it.  After the window it reads the peak device
memory, frees the operator, and compares a sample of the window's own
answers, drawn from the seed, with the float64 host reference
(``reference.py``).  ``--trace 1`` runs the same window under the
profiler and prints the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``breakdown`` with
``--trace 1``) and, last, ``check``: each number compared with its
limit.  The same numbers end standard error.  Without a TPU, with fewer
chips than the cell asks for, or on a chip kind missing from
``peaks.json``, the run exits 3 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import os  # noqa: E402

# the float64 reference splits its products over threads itself
os.environ.setdefault("OPENBLAS_NUM_THREADS", "1")

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import cost  # noqa: E402
import reference  # noqa: E402
import tracing  # noqa: E402


def _json(path):
    with open(path) as f:
        return json.load(f)


PEAKS = _json(os.path.join(BENCH, "peaks.json"))["kinds"]


# ---------------------------------------------------------------------------
# cells, configurations and metrics, found by name
# ---------------------------------------------------------------------------

def load_cell(name: str, bench_dir: str = BENCH) -> dict:
    """The cell ``name``: its traffic, its configuration, and the metrics
    ``BENCHMARK.json`` (beside ``bench_dir``) gives it."""
    traffic = _json(os.path.join(bench_dir, "workloads", f"{name}.json"))
    config = _json(os.path.join(bench_dir, "configs",
                                f"{traffic['config']}.json"))
    spec = _json(os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json"))

    def mine(metric):
        return name in metric.get("workloads", (name,))

    return {"name": name, "traffic": traffic, "config": config,
            "chips": math.prod(config["grid"]),
            "end_to_end": {m["name"]: m["unit"] for m in spec["end_to_end"]
                           if mine(m)},
            "per_layer": {m["name"]: m["unit"] for m in spec["per_layer"]
                          if mine(m)}}


def load_metric(name: str, bench_dir: str = BENCH):
    """The reader ``bench/metrics/<name>.py``: ``read(ctx)`` gives the
    metric's value, or None where its cell has nothing to read."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def fail_setup(msg: str):
    print(f"bench: {msg}; nothing was measured", file=sys.stderr)
    raise SystemExit(3)


def require_chip(cell: dict):
    """The cell's devices, or exit 3: the benchmark never falls back."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail_setup(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < cell["chips"]:
        fail_setup(f"the cell needs {cell['chips']} chips, JAX sees "
                   f"{len(devs)}")
    if devs[0].device_kind not in PEAKS:
        fail_setup(f"no peaks for device kind {devs[0].device_kind!r} in "
                   f"bench/peaks.json")
    return devs[:cell["chips"]]


def device_info(devs) -> dict:
    peak = [d.memory_stats() or {} for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(int(p.get("peak_bytes_in_use", 0))
                                     for p in peak)}


# ---------------------------------------------------------------------------
# inputs from the seed, made on the device
# ---------------------------------------------------------------------------

def seed_key(seed: int, stream: int):
    """A threefry key for one stream of the seed (any non-negative seed,
    64 bits and more)."""
    import jax
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(words.astype(np.uint32))


def block_column(config: dict, seed: int):
    """The first block column (N_t, N_d, N_m), float32, made on the
    device in one call: normal blocks with a geometrically decaying
    impulse response (a physical p2o map decays in time)."""
    import jax
    import jax.numpy as jnp
    N_t, N_d, N_m = config["N_t"], config["N_d"], config["N_m"]

    def make(key):
        blocks = jax.random.normal(key, (N_t, N_d, N_m), jnp.float32)
        scale = config["decay"] ** jnp.arange(N_t, dtype=jnp.float32)
        return blocks * (scale[:, None, None] / np.float32(np.sqrt(N_m)))

    return jax.jit(make)(seed_key(seed, 0))


def vectors(shape, count: int, seed: int, stream: int):
    """``count`` standard normal float32 arrays of ``shape``, made on the
    device."""
    import jax
    import jax.numpy as jnp
    make = jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32))
    key = seed_key(seed, stream)
    return [make(jax.random.fold_in(key, i)) for i in range(count)]


def build(config: dict, seed: int, precision: str | None = None):
    """The operator on one chip, through the program's own set-up, at the
    configuration's precision (or at ``precision``)."""
    import jax
    from repro.core import FFTMatvec, PrecisionConfig
    if math.prod(config["grid"]) != 1:
        raise ValueError("the harness drives one chip; a mesh cell needs "
                         "its inputs placed under the operator's shardings")
    op = FFTMatvec.from_block_column(
        block_column(config, seed), precision=PrecisionConfig.from_string(
            precision or config["precision"]))
    jax.block_until_ready((op.F_hat_re, op.F_hat_im))
    return op


# ---------------------------------------------------------------------------
# the calls a cell's traffic makes
# ---------------------------------------------------------------------------

def matvec(op, m):
    return op.matvec(m)


def m_shape(config):
    """One parameter vector (N_m, N_t)."""
    return (config["N_m"], config["N_t"])


class Reservoir:
    """A uniform sample of ``size`` items of a stream of unknown length,
    drawn with ``rng``: what is compared after the window."""

    def __init__(self, size: int, rng):
        self.size, self.rng, self.items, self.seen = size, rng, [], 0

    def offer(self, item):
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(item)
        else:
            j = int(self.rng.integers(self.seen))
            if j < self.size:
                self.items[j] = item


def _count_compiles():
    """{"compile_events": n, "cache_loads": k} from now on: JAX's compile
    event fires for a compile and for a load from the persistent cache
    alike; a load fires its own retrieval event too."""
    import jax
    seen = {"compile_events": 0, "cache_loads": 0}

    def listener(event, duration, **_):
        if event.endswith("backend_compile_duration"):
            seen["compile_events"] += 1
        elif event.endswith("cache_retrieval_time_sec"):
            seen["cache_loads"] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return seen


class ApplyTraffic:
    """Back-to-back operator applications on a pool of inputs made once on
    the device, used in turn."""

    def __init__(self, cell, op, seed):
        import jax
        self.pool = vectors(m_shape(cell["config"]), cell["traffic"]["pool"],
                            seed, 1)
        self.compiled = jax.jit(matvec).lower(op, self.pool[0]).compile()
        self.hlo = [self.compiled.as_text()]
        for x in self.pool:                 # warm every input once
            jax.block_until_ready(self.compiled(op, x))

    def window(self, op, seconds, keep: Reservoir):
        import jax
        times, n = [], 0
        t_begin = t_end = time.perf_counter()
        while t_end - t_begin < seconds:
            i = n % len(self.pool)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("apply"):
                y = self.compiled(op, self.pool[i])
            with jax.profiler.TraceAnnotation("host_sync"):
                y.block_until_ready()
            t_end = time.perf_counter()
            times.append(t_end - t0)
            keep.offer((i, y))
            n += 1
        return {"span": "apply", "window_s": t_end - t_begin, "n": n,
                "failed": 0, "times": times}

    def metrics(self, run):
        return {"apply_ms": run["window_s"] / run["n"] * 1e3,
                "apply_p95_ms": float(np.percentile(run["times"], 95)) * 1e3}


class SolveTraffic:
    """Back-to-back CGNR solves to a stated tolerance, one data vector
    d = F m_true after another from a pool made in set-up.

    The solver gets the operator as a caller that solves again and again
    holds it: its adjoint (``rmatmat``) and its exact Gram, each compiled
    once in set-up and passed through ``cg_normal_equations``'s
    ``gram`` argument, so that nothing compiles in the window."""

    def __init__(self, cell, op, seed):
        import jax
        from repro.solvers import cg_normal_equations
        cfg, tr = cell["config"], cell["traffic"]
        self.tr = tr
        self.solve = cg_normal_equations
        self.m_true = vectors(m_shape(cfg), tr["pool"], seed, 1)
        fwd = jax.jit(matvec)
        self.pool = [fwd(op, m) for m in self.m_true]
        gram = op.gram(space="parameter", mode="exact")
        adjoint = jax.jit(type(op).rmatmat)
        normal = jax.jit(type(gram).apply)
        self.hlo = [adjoint.lower(op, self.pool[0]).compile().as_text(),
                    normal.lower(gram, self.m_true[0]).compile().as_text()]
        self.op = types.SimpleNamespace(
            rmatmat=functools.partial(adjoint, op))
        self.gram = types.SimpleNamespace(
            apply=functools.partial(normal, gram))
        for d in self.pool:                 # warm every data vector once
            self._one(d)

    def _one(self, d):
        import jax
        tr = self.tr
        with jax.profiler.TraceAnnotation("solve"):
            res = self.solve(self.op, d, gram=self.gram, tol=tr["tol"],
                             maxiter=tr["maxiter"],
                             precision=tr["precision"])
        with jax.profiler.TraceAnnotation("host_sync"):
            jax.block_until_ready(res.x)
        return res

    def window(self, op, seconds, keep: Reservoir):
        n, failed, iters = 0, 0, []
        t_begin = t_end = time.perf_counter()
        while t_end - t_begin < seconds:
            i = n % len(self.pool)
            res = self._one(self.pool[i])
            t_end = time.perf_counter()
            failed += not res.converged
            iters.append(res.n_iters)
            keep.offer((i, res))
            n += 1
        return {"span": "solve", "window_s": t_end - t_begin, "n": n,
                "failed": failed, "iters": iters}

    def metrics(self, run):
        return {"solve_s": run["window_s"] / run["n"]}


TRAFFIC = {"matvec": ApplyTraffic, "cgnr": SolveTraffic}


# ---------------------------------------------------------------------------
# correctness: the window's own answers against the float64 reference
# ---------------------------------------------------------------------------

def host_column(config, seed):
    """The block column on the host, made again from the seed."""
    return np.asarray(block_column(config, seed))


def apply_references(cell, seed, inputs) -> dict:
    """{number: float64 answers (N_d, N_t, S) to the pooled inputs} for
    each number the cell's limits name: ``rel_err`` against the exact
    operator, ``stated_gap`` against the operator with the rounding
    points that the configuration's precision states."""
    cfg = cell["config"]
    F = host_column(cfg, seed)
    X = np.stack([np.asarray(x, np.float64) for x in inputs], axis=-1)
    precision = {"rel_err": None, "stated_gap": cfg["precision"]}
    return {name: reference.HostOperator(F, precision[name]).matvec(X)
            for name in cell["traffic"]["limits"]}


def apply_numbers(refs: dict, outputs) -> dict:
    """Each number: the largest relative gap, over the sampled answers
    ``(input index, answer)``, between the answer and its reference, at
    every time step."""
    numbers = {}
    for name, ref in refs.items():
        gaps = [reference.rel_err(y, ref[..., i]) for i, y in outputs]
        numbers[name] = max(g if math.isfinite(g) else math.inf
                            for g in gaps)
    return numbers


def check_apply(cell, seed, answers) -> dict:
    return apply_numbers(apply_references(cell, seed, answers["inputs"]),
                         answers["outputs"])


def check_solve(cell, seed, answers) -> dict:
    """Each sampled solve against float64 CGNR on the same data, run for
    as many iterations as the program took: ``x_gap``, the relative gap
    of the iterates, and ``relres``, the reference's relative residual
    there, which must be under the cell's tolerance."""
    cfg = cell["config"]
    F = host_column(cfg, seed)
    op = reference.HostOperator(F)
    del F
    M = np.stack([np.asarray(m, np.float64) for m in answers["inputs"]],
                 axis=-1)
    d = op.matvec(M)
    n_max = max(res.n_iters for _, res in answers["outputs"])
    iterates, relres = reference.cgnr(op, d, n_max)
    gaps, rr = [], []
    for i, res in answers["outputs"]:
        x = np.asarray(res.x, np.float64)
        ok = np.isfinite(x).all()
        gaps.append(reference.rel_err(x, iterates[res.n_iters][..., i])
                    if ok else math.inf)
        rr.append(float(relres[res.n_iters, i]))
    return {"x_gap": max(gaps), "relres": max(rr)}


def answers_of(traffic, keep: Reservoir):
    """The sampled answers and the host copy of the inputs they came from."""
    inputs = getattr(traffic, "m_true", traffic.pool)
    return {"inputs": [np.asarray(x) for x in inputs],
            "outputs": list(keep.items)}


CHECKS = {"matvec": check_apply, "cgnr": check_solve}


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {value, limit}}): every number within its limit."""
    table = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    ok = all(math.isfinite(v) and v <= limits[k] for k, v in numbers.items())
    return ok, table


class _Solved:
    def __init__(self, x, n_iters):
        self.x, self.n_iters = x, n_iters


def control_answers(cell, seed, rung: str) -> dict:
    """The answers to the cell's own inputs with a control in the
    program's place: ``high`` or ``fp8``, the plain operator of
    ``control.py`` one rung below; or a precision string, the program's
    own path at that precision (a lower one than the configuration
    states).  Not part of a benchmark run; ``control.py`` and the tests
    call it."""
    import jax
    import control
    cfg, tr = cell["config"], cell["traffic"]
    if len(rung) == 5:
        op = build(cfg, seed, precision=rung)
        fn = jax.jit(matvec)
    else:
        op = control.fourier_column(block_column(cfg, seed))
        fn = jax.jit(lambda p, v: control.apply(p, v, rung))
    pool = vectors(m_shape(cfg), tr["pool"], seed, 1)
    if tr["call"] == "cgnr":
        if len(rung) == 5:
            raise ValueError("the solve's control is the plain operator")
        outputs = [(i, _Solved(*control.cgnr(op, fn(op, m), rung,
                                             tol=tr["tol"],
                                             maxiter=tr["maxiter"])))
                   for i, m in enumerate(pool)]
    else:
        outputs = [(i, np.asarray(fn(op, x))) for i, x in enumerate(pool)]
    del op, fn
    gc.collect()
    return {"inputs": [np.asarray(x) for x in pool], "outputs": outputs}


def control_check(cell, seed, rung: str) -> dict:
    """The cell's numbers with the control ``rung`` in the program's
    place: the same inputs, the same comparison."""
    return CHECKS[cell["traffic"]["call"]](
        cell, seed, control_answers(cell, seed, rung))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(cell, seed, seconds, *, trace: bool, devices,
             t_start: float | None = None) -> dict:
    """Set-up, the measured window and the check of one cell; returns the
    result's fields (and ``check``, the numbers compared)."""
    import jax
    t_start = time.perf_counter() if t_start is None else t_start
    tr = cell["traffic"]
    op = build(cell["config"], seed)
    traffic = TRAFFIC[tr["call"]](cell, op, seed)
    keep = Reservoir(tr["sample"], np.random.default_rng([seed, 2]))
    compiles = _count_compiles()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    setup_s = time.perf_counter() - t_start
    if trace:
        tracing.start(trace_dir)
    try:
        run = traffic.window(op, seconds, keep)
    finally:
        if trace:
            jax.profiler.stop_trace()
    in_window = dict(compiles)
    device = device_info(devices)
    result = {"attempted": run["n"], "failed": run["failed"],
              "device": device, "compiles_in_window": in_window}
    if trace:
        try:
            result.update(read_trace(cell, traffic, run, trace_dir, device))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics = dict(traffic.metrics(run), setup_s=setup_s)
        result["metrics"] = {k: {"value": metrics[k], "unit": u}
                             for k, u in cell["end_to_end"].items()}
    answers = answers_of(traffic, keep)
    del op, traffic, keep
    gc.collect()
    numbers = CHECKS[tr["call"]](cell, seed, answers)
    result["correct"], result["check"] = judge(numbers, tr["limits"])
    return result


def read_trace(cell, traffic, run, trace_dir, device) -> dict:
    """Per-layer metrics, busy and window seconds, and the breakdown of
    the traced window."""
    cfg = cell["config"]
    trace = tracing.load(trace_dir)
    layer_of = tracing.classifier(traffic.hlo, cfg["N_t"] + 1)
    summary = tracing.reduce(trace, layer_of, run["span"])
    ctx = {"cell": cell, "run": run, "trace": summary,
           "peak": PEAKS[device["kind"]], "cost": cost}
    metrics = {}
    for name, unit in cell["per_layer"].items():
        value = load_metric(name).read(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}
    busy = summary.get("busy_s", {})
    device["busy_s"] = sum(busy.values()) / max(len(busy), 1)
    device["window_s"] = summary.get("window_s", run["window_s"])
    return {"metrics": metrics, "breakdown": {
        "device_ops": [[n, s] for n, s in summary.get("top_ops", [])],
        "idle_gaps": summary.get("idle_gaps", [])}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.jax_compat import use_compile_cache
    use_compile_cache()
    devices = require_chip(cell)

    result = run_cell(cell, args.seed, args.seconds, trace=bool(args.trace),
                      devices=devices, t_start=T_START)
    check = result.pop("check")
    seen = result.pop("compiles_in_window")
    print(f"in the window: {seen['compile_events']} compile events, "
          f"{seen['cache_loads']} of them loads from the persistent cache",
          file=sys.stderr)
    for name, c in check.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": result["metrics"],
            "device": result["device"]}
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    line["check"] = check
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
