"""Oracle tests for the dynamic mixed-precision tuner (`repro.tune`).

The headline oracle: on the Fig.-3-scale problem, `autotune` must select
exactly the configuration the exhaustive `optimal_config` sweep selects
while timing fewer than half of the lattice.  Timing is made
deterministic by injecting a synthetic cost model (strictly monotone in
per-phase precision, injective over configs) into BOTH paths through the
shared `TimingHarness` — measured errors are real and identical between
paths, so agreement is exact, not statistical.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FFTMatvec, PrecisionConfig, all_configs, config_lt,
                        measure_configs, optimal_config, pareto_front,
                        random_unrepresentable, rel_l2, time_callable)
from repro.core.error_model import phase_factors
from repro.core.pareto import ConfigRecord
from repro.core.precision import machine_eps
from repro.solvers import SolverPrecision, cg_normal_equations, resolve_precision
from repro.tune import (CacheKey, TimingHarness, TuningCache, autotune,
                        calibrate_constants, minimal_elements, probe_configs,
                        prune_lattice)

# Deterministic synthetic cost model: strictly monotone under raising any
# phase's precision, injective over the full 3-level lattice.
_LEVEL_COST = {"h": 1.0, "s": 2.0, "d": 4.0}
_ALL_STRINGS = sorted(c.to_string() for c in all_configs(("d", "s", "h")))


def fake_timer(cfg, fn, arg):
    s = cfg.to_string()
    return (sum(_LEVEL_COST[ch] for ch in s) * 1e-3
            + _ALL_STRINGS.index(s) * 1e-9)


def small_problem(Nt=16, Nd=3, Nm=24, seed=0):
    F_col = random_unrepresentable(jax.random.PRNGKey(seed),
                                   (Nt, Nd, Nm)) / np.sqrt(Nm)
    m = random_unrepresentable(jax.random.PRNGKey(seed + 1), (Nm, Nt))
    return FFTMatvec.from_block_column(F_col), F_col, m


# ---------------------------------------------------------------------------
# The acceptance oracle: autotune == exhaustive at fig3 scale, < 50% timed.
# ---------------------------------------------------------------------------

def test_autotune_matches_exhaustive_fig3_scale():
    Nt, Nd, Nm = 128, 25, 625
    tol = 1e-7
    F_col = random_unrepresentable(jax.random.PRNGKey(0),
                                   (Nt, Nd, Nm)) / np.sqrt(Nm)
    m = random_unrepresentable(jax.random.PRNGKey(1), (Nm, Nt))
    # pinned backend: the oracle compares tuner logic, not lowerings, and
    # its non-degeneracy assertion (some config above tol) holds for the
    # fused-XLA error profile — keep it fixed across CI backend legs
    op = FFTMatvec.from_block_column(F_col, backend="cpu-xla")
    harness = TimingHarness(timer=fake_timer)

    records = measure_configs(
        lambda cfg: FFTMatvec.from_block_column(F_col, precision=cfg,
                                                backend="cpu-xla"),
        m, list(all_configs(("d", "s"))), harness=harness)
    exhaustive_best = optimal_config(records, tol)

    res = autotune(op, tol=tol, v=m, ladder=("d", "s"), harness=harness)

    assert res.config == exhaustive_best.config
    assert res.n_timed < res.n_lattice // 2          # < 50% of the lattice
    assert res.record.rel_error <= tol
    # the tuner's measured errors agree with the exhaustive sweep's
    exhaustive_errs = {r.prec: r.rel_error for r in records}
    for prec, err in res.errors.items():
        assert err == pytest.approx(exhaustive_errs[prec], rel=1e-12, abs=0)
    # tolerance actually splits the lattice here (non-degenerate oracle)
    assert any(r.rel_error > tol for r in records)
    assert sum(r.rel_error <= tol for r in records) > 1


def test_autotune_small_real_timing():
    """End-to-end with real wall-clock timing: selection is feasible and
    the pruning accounting holds (agreement with a second exhaustive
    timing run would be noise-dependent, so only invariants are checked).
    """
    op, _, m = small_problem()
    res = op.autotune(3e-6, v=m, ladder=("d", "s"), repeats=1,
                      full_result=True)
    assert res.record.rel_error <= 3e-6
    assert res.n_timed < res.n_lattice // 2
    assert res.op.precision == res.config
    # retuned operator really runs at the chosen precision
    out = res.op.matvec(m)
    assert out.shape == (op.N_d, op.N_t)
    # timed configs other than the baseline form an antichain: nothing
    # timed is precision-dominated by another timed non-baseline config
    timed = [r.config for r in res.records[1:]]
    for a in timed:
        for b in timed:
            assert not config_lt(a, b)


def test_autotune_returns_retuned_operator():
    op, _, m = small_problem()
    tuned = op.autotune(3e-6, v=m, ladder=("d", "s"), timer=fake_timer)
    assert isinstance(tuned, FFTMatvec)
    assert tuned.precision in list(all_configs(("d", "s")))
    assert tuned.F_hat_re.dtype == tuned.precision.phase_dtype("gemv")


# ---------------------------------------------------------------------------
# Pruner oracles
# ---------------------------------------------------------------------------

def test_prune_lattice_partitions_and_frontier():
    lattice = list(all_configs(("d", "s")))
    report = prune_lattice(lattice, 1e-7, 128, 25, 625)
    assert len(report.model_feasible) + len(report.infeasible) == 32
    assert set(report.frontier) | set(report.dominated) \
        == set(report.model_feasible)
    # frontier is an antichain
    for a in report.frontier:
        for b in report.frontier:
            assert not config_lt(a, b)
    # every infeasible config's bound really exceeds the cutoff
    for cfg in report.infeasible:
        assert report.bounds[cfg.to_string()] > report.cutoff
    # raw eq.-(6) constants at tol 1e-7: any gemv=s config is certified
    # infeasible (e_s * 625 >> tol), so over half the lattice is discarded
    assert len(report.infeasible) >= 16


def test_prune_lattice_always_keeps_best_bound_config():
    lattice = list(all_configs(("d", "s")))
    report = prune_lattice(lattice, 1e-30, 128, 25, 625)   # nothing can meet
    assert report.model_feasible == [PrecisionConfig.from_string("ddddd")]


def test_probe_configs_counts():
    assert len(probe_configs(("d", "s"))) == 5
    assert len(probe_configs(("d", "s", "h"))) == 10
    for phase, lvl, cfg in probe_configs(("d", "s")):
        assert getattr(cfg, phase) == lvl == "s"
        assert sum(ch == "d" for ch in cfg.to_string()) == 4


def test_calibrate_constants_recovers_synthetic():
    """Probe errors manufactured from known constants are recovered."""
    Nt, Nd, Nm = 64, 8, 100
    f = phase_factors(Nt, Nd, Nm)
    truth = {"c1": 0.5, "c2": 2.0, "c3": 0.01, "c4": 1.5, "c5": 3.0}
    probe_errs = {
        phase: {"s": truth[name] * machine_eps("s") * f[phase]}
        for phase, name in zip(("pad", "fft", "gemv", "ifft", "reduce"),
                               ("c1", "c2", "c3", "c4", "c5"))
        if f[phase] > 0}
    fitted = calibrate_constants(probe_errs, Nt, Nd, Nm)
    # all five phases calibratable at p=1: the reduce factor includes the
    # always-present phase-5 storage cast (1 + log2 p), never 0
    for name in ("c1", "c2", "c3", "c4", "c5"):
        assert fitted[name] == pytest.approx(truth[name])


def test_minimal_elements():
    cfgs = [PrecisionConfig.from_string(s)
            for s in ("ddddd", "dssdd", "sssss", "dsddd")]
    mins = minimal_elements(cfgs)
    assert set(mins) == {PrecisionConfig.from_string("sssss")}
    # an antichain is its own minimal set
    anti = [PrecisionConfig.from_string(s) for s in ("sdddd", "dsddd")]
    assert set(minimal_elements(anti)) == set(anti)


# ---------------------------------------------------------------------------
# Pareto machinery edge cases
# ---------------------------------------------------------------------------

def _rec(t, e):
    return ConfigRecord(PrecisionConfig(), e, t)


def test_pareto_front_single_record():
    r = _rec(1.0, 1e-3)
    assert pareto_front([r]) == [r]


def test_pareto_front_duplicate_points_all_kept():
    a, b = _rec(1.0, 1e-3), _rec(1.0, 1e-3)
    front = pareto_front([a, b])
    assert len(front) == 2            # strict domination: ties never eliminate


def test_pareto_front_all_dominated_ties():
    winner = _rec(1.0, 1e-5)
    recs = [winner, _rec(1.0, 1e-3), _rec(2.0, 1e-5), _rec(2.0, 1e-3)]
    front = pareto_front(recs)
    assert front == [winner]


def test_optimal_config_no_feasible_raises():
    with pytest.raises(ValueError):
        optimal_config([_rec(1.0, 1e-2)], 1e-6)


def test_time_callable_guards():
    fn = jax.jit(lambda x: x + 1)
    v = jnp.ones((4,))
    with pytest.raises(ValueError):
        time_callable(fn, v, repeats=0)
    with pytest.raises(ValueError):
        time_callable(fn, v, repeats=3, mode="bogus")
    assert time_callable(fn, v, repeats=2, warmup=1, mode="latency") > 0
    assert time_callable(fn, v, repeats=2, warmup=1, mode="throughput") > 0


def test_harness_reuses_jitted_callable():
    op, _, m = small_problem()
    h = TimingHarness(repeats=1, warmup=0)
    cfg = PrecisionConfig.from_string("dssdd")
    out1 = h.run_once(op.with_precision(cfg), m)
    out2 = h.run_once(op.with_precision(cfg), m)   # second op instance
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    # one shared applier serves the whole vec family, not one per config
    h.run_once(op.with_precision(PrecisionConfig.from_string("sssss")), m)
    assert set(h._jitted) == {"vec"}
    assert h.n_timed == 0                           # run_once is error-only
    h.time(op, m)
    assert h.n_timed == 1 and h.timed_configs() == [op.precision]
    with pytest.raises(ValueError):
        TimingHarness(repeats=0)
    with pytest.raises(ValueError):
        h.callable_for(op, "bogus")


def test_harness_matmat_promotes_2d_like_operator():
    """FFTMatvec.matmat treats a 2-D input as S=1; the harness's shared
    applier must honor the same convention."""
    op, _, m = small_problem()
    h = TimingHarness(repeats=1, warmup=0)
    out = h.run_once(op, m, "matmat")
    np.testing.assert_allclose(np.asarray(out), np.asarray(op.matmat(m)),
                               rtol=1e-12, atol=0)
    M = jnp.stack([m, 2.0 * m], axis=-1)
    out3 = h.run_once(op, M, "matmat")
    np.testing.assert_allclose(np.asarray(out3), np.asarray(op.matmat(M)),
                               rtol=1e-12, atol=0)


# ---------------------------------------------------------------------------
# Tuning cache
# ---------------------------------------------------------------------------

def test_cache_roundtrip_identical_selection(tmp_path):
    path = tmp_path / "tune.json"
    op, _, m = small_problem()
    res1 = autotune(op, tol=3e-6, v=m, ladder=("d", "s"), timer=fake_timer,
                    cache_path=path)
    assert not res1.from_cache
    assert path.exists()
    json.loads(path.read_text())                    # valid JSON on disk

    # a fresh cache object (fresh process stand-in) answers from disk
    res2 = autotune(op, tol=3e-6, v=m, ladder=("d", "s"), timer=fake_timer,
                    cache=TuningCache(path))
    assert res2.from_cache
    assert res2.n_timed == 0
    assert res2.config == res1.config
    assert res2.record.time_s == pytest.approx(res1.record.time_s)
    assert res2.record.rel_error == pytest.approx(res1.record.rel_error)


def test_cache_answers_new_tolerance_from_records(tmp_path):
    path = tmp_path / "tune.json"
    op, _, m = small_problem()
    res1 = autotune(op, tol=3e-6, v=m, ladder=("d", "s"), timer=fake_timer,
                    cache_path=path)
    # looser tolerance: stored records still answer it (no re-tune needed)
    res2 = autotune(op, tol=1e-2, v=m, ladder=("d", "s"), timer=fake_timer,
                    cache=TuningCache(path))
    assert res2.from_cache
    # tighter than anything measured except the baseline: the cached
    # baseline record (error 0) still answers
    res3 = autotune(op, tol=1e-30, v=m, ladder=("d", "s"), timer=fake_timer,
                    cache=TuningCache(path))
    assert res3.from_cache
    assert res3.config == res1.records[0].config


def test_cache_corrupted_file_falls_back_to_retune(tmp_path):
    path = tmp_path / "tune.json"
    path.write_text("{ this is not json !!!")
    op, _, m = small_problem()
    with pytest.warns(UserWarning, match="re-tuning"):
        res = autotune(op, tol=3e-6, v=m, ladder=("d", "s"),
                       timer=fake_timer, cache=TuningCache(path))
    assert not res.from_cache
    assert res.record.rel_error <= 3e-6
    # the corrupt file was replaced by a valid entry
    assert TuningCache(path).get(res.cache_key) is not None


def test_cache_stale_entry_is_miss(tmp_path):
    path = tmp_path / "tune.json"
    op, _, m = small_problem()
    res = autotune(op, tol=3e-6, v=m, ladder=("d", "s"), timer=fake_timer,
                   cache_path=path)
    key = res.cache_key
    # version bump and a mangled precision string must both read as a miss
    data = json.loads(path.read_text())
    entry = data[key.to_string()]
    stale = dict(entry, version=entry["version"] + 1)
    cache = TuningCache(path)
    cache._load()[key.to_string()] = stale
    assert cache.get(key) is None and cache.lookup_config(key, 1.0) is None

    mangled = json.loads(json.dumps(entry))
    mangled["times"]["zzzzz"] = 1.0
    cache2 = TuningCache(path)
    cache2._load()[key.to_string()] = mangled
    assert cache2.get(key) is None
    # and autotune on a stale cache silently re-tunes
    path.write_text(json.dumps({key.to_string(): stale}))
    res2 = autotune(op, tol=3e-6, v=m, ladder=("d", "s"), timer=fake_timer,
                    cache=TuningCache(path))
    assert not res2.from_cache
    assert res2.config == res.config


@pytest.mark.parametrize("stale_version", [1, 2, 3])
def test_cache_stale_schema_entry_is_stale_and_migrates(tmp_path,
                                                        stale_version):
    """Entries from older schemata — v1 (pre-``variant="gram"``), v2
    (pre-backend-fingerprint keys), and v3 (pre-tile-map codec) — must
    read as misses, and a re-tune must overwrite them in place with
    current-version records."""
    from repro.tune.cache import SCHEMA_VERSION
    assert SCHEMA_VERSION == 4
    path = tmp_path / "tune.json"
    op, _, m = small_problem()
    res = autotune(op, tol=3e-6, v=m, ladder=("d", "s"), timer=fake_timer,
                   cache_path=path)
    key = res.cache_key
    data = json.loads(path.read_text())
    stale = dict(data[key.to_string()], version=stale_version)
    path.write_text(json.dumps({key.to_string(): stale}))

    cache = TuningCache(path)
    assert cache.get(key) is None                       # stale -> miss
    res2 = autotune(op, tol=3e-6, v=m, ladder=("d", "s"), timer=fake_timer,
                    cache=cache)
    assert not res2.from_cache
    assert res2.config == res.config
    stored = json.loads(path.read_text())[key.to_string()]
    assert stored["version"] == SCHEMA_VERSION          # migrated in place
    # and the migrated entry now answers
    res3 = autotune(op, tol=3e-6, v=m, ladder=("d", "s"), timer=fake_timer,
                    cache=TuningCache(path))
    assert res3.from_cache


def test_cache_key_carries_backend_fingerprint():
    """v3 keys embed the backend identity: the same problem tuned through
    one backend must never answer another backend's query.  (Explicit
    backends are compared so the test holds in every CI matrix leg,
    including REPRO_BACKEND=xla-ref where the probed default IS xla-ref.)"""
    from repro.backend import current_backend
    op, _, _ = small_problem()
    key_auto = CacheKey.for_operator(op, ("d", "s"))
    assert current_backend().fingerprint() in key_auto.to_string()
    key_ref = CacheKey.for_operator(op.with_backend("xla-ref"), ("d", "s"))
    key_int = CacheKey.for_operator(op.with_backend("cpu-interpret"),
                                    ("d", "s"))
    assert key_ref.to_string() != key_int.to_string()
    assert "xla-ref@" in key_ref.to_string()
    assert "cpu-interpret@" in key_int.to_string()


def test_cache_key_identity():
    k1 = CacheKey(128, 25, 625, ("d", "s"), "matvec", "cpu:")
    k2 = CacheKey(128, 25, 625, ("d", "s"), "rmatvec", "cpu:")
    assert k1.to_string() != k2.to_string()
    assert "128x25x625" in k1.to_string()


def test_cache_key_reflects_workload_details():
    """Entries must not be shared across materially different
    measurement setups: kernel path, timing mode, RHS count, probe
    input, synthetic-vs-real timer all enter the key."""
    op, _, m = small_problem()
    base = CacheKey.for_operator(op, ("d", "s"))
    assert base.to_string() \
        != CacheKey.for_operator(op, ("d", "s"), mode="latency").to_string()
    assert base.to_string() != CacheKey.for_operator(
        op, ("d", "s"), input_tag="v123", ).to_string()
    assert base.to_string() != CacheKey.for_operator(
        op, ("d", "s"), synthetic_timer=True).to_string()
    k4 = CacheKey.for_operator(op, ("d", "s"), "matmat", n_rhs=4)
    k64 = CacheKey.for_operator(op, ("d", "s"), "matmat", n_rhs=64)
    assert k4.to_string() != k64.to_string()


def _entry_kw(cfg, t=1.0):
    rec = ConfigRecord(cfg, 0.0, t, 1.0)
    return dict(records=[rec], front=[rec], chosen=cfg, tol=1e-6,
                baseline=cfg, n_lattice=32)


def test_cache_save_merges_concurrent_writers(tmp_path):
    """Two processes tuning DIFFERENT keys against the same file must both
    survive: save() re-reads the file and merges valid entries instead of
    dumping the dict loaded at first _load() (last-writer-wins lost the
    slower writer's work)."""
    path = tmp_path / "tune.json"
    cfg = PrecisionConfig.from_string("ddddd")
    key1 = CacheKey(8, 2, 4, ("d", "s"), "matvec", "cpu:x", "a", "fp1")
    key2 = CacheKey(16, 3, 8, ("d", "s"), "gram", "cpu:x", "b", "fp1")

    c1, c2 = TuningCache(path), TuningCache(path)
    c1._load(), c2._load()            # both snapshot the (empty) file
    c1.put(key1, **_entry_kw(cfg))
    c1.save()
    c2.put(key2, **_entry_kw(cfg, t=2.0))
    c2.save()                          # used to clobber key1

    fresh = TuningCache(path)
    assert fresh.get(key1) is not None
    assert fresh.get(key2) is not None
    # and the merge never resurrects invalid on-disk entries
    raw = json.loads(path.read_text())
    raw["zombie"] = {"version": -1}
    path.write_text(json.dumps(raw))
    c3 = TuningCache(path)
    c3._load()
    c3.put(key1, **_entry_kw(cfg, t=3.0))
    c3.save()
    assert "zombie" not in json.loads(path.read_text())
    # same-key writers degrade to per-key last-writer-wins, never loss
    final = TuningCache(path)
    assert final.get(key1)["times"][cfg.to_string()] == 3.0
    assert final.get(key2) is not None


def test_cache_save_merge_preserves_dispatch_entries(tmp_path):
    """Dispatch tables written by another process survive a merge-on-write
    save from a cache object that never loaded them."""
    from repro.backend import DispatchTable, current_backend
    path = tmp_path / "tune.json"
    spec = current_backend()
    c1 = TuningCache(path)
    c1.put_dispatch(spec, DispatchTable())
    c1.save()

    cfg = PrecisionConfig.from_string("ddddd")
    key = CacheKey(8, 2, 4, ("d", "s"), "matvec", "cpu:x", "a", "fp1")
    c2 = TuningCache(path)     # fresh snapshot happens inside save()
    c2._data = {}              # simulate a writer that loaded pre-dispatch
    c2.put(key, **_entry_kw(cfg))
    c2.save()

    fresh = TuningCache(path)
    assert fresh.get_dispatch(spec) is not None
    assert fresh.get(key) is not None


def test_cache_synthetic_timer_never_answers_real_runs(tmp_path):
    path = tmp_path / "tune.json"
    op, _, m = small_problem()
    res1 = autotune(op, tol=3e-6, v=m, ladder=("d", "s"), timer=fake_timer,
                    cache_path=path)
    # same problem, real timing: the synthetic entry must not be reused
    res2 = autotune(op, tol=3e-6, v=m, ladder=("d", "s"), repeats=1,
                    cache=TuningCache(path))
    assert not res2.from_cache
    assert res1.cache_key.to_string() != res2.cache_key.to_string()


def test_autotune_ladder_on_tpu_backend_is_s_h():
    """A TPU has no f64 datapath: even a double-built operator is tuned
    over ("s", "h") there, and no candidate runs a "d" phase."""
    from repro.backend import DispatchTable
    from repro.core import ExecOpts
    _, F_col, m = small_problem()
    # XLA lowerings throughout: the Mosaic kernels cannot run on the CPU
    opts = ExecOpts(backend="tpu-pallas", dispatch=DispatchTable(force="xla"),
                    fuse_pad_cast=False)
    op = FFTMatvec.from_block_column(F_col, opts=opts)
    assert op.precision.highest() == "d"
    res = autotune(op, tol=1e-3, v=m.astype(jnp.float32), timer=fake_timer)
    assert res.records and all("d" not in r.config.to_string()
                               for r in res.records)
    assert "d" not in res.config.to_string()


# ---------------------------------------------------------------------------
# Solver integration
# ---------------------------------------------------------------------------

def test_solver_precision_from_tolerance():
    assert SolverPrecision.from_tolerance(1e-4).to_string() == "hss"
    assert SolverPrecision.from_tolerance(1e-10).to_string() == "ddd"
    assert SolverPrecision.from_tolerance(1e-6).to_string() == "sss"
    # restricted ladder clamps to its highest level
    assert SolverPrecision.from_tolerance(1e-10,
                                          ladder=("h", "s")).to_string() == "sss"
    with pytest.raises(ValueError):
        SolverPrecision.from_tolerance(0.0)


def test_solver_precision_from_tolerance_respects_error_floor():
    """A low-precision operator floors the target: legs are not
    over-provisioned below what the operator can deliver."""
    op, _, _ = small_problem()
    op_low = op.with_precision(PrecisionConfig.from_string("hhhhh"))
    p = SolverPrecision.from_tolerance(1e-12, op=op_low)
    assert p != SolverPrecision.from_tolerance(1e-12)
    assert p.orthogonalize != "d" or p.recurrence != "d"


def test_resolve_precision_forms():
    p = SolverPrecision.from_string("sds")
    assert resolve_precision(p, 1e-8) is p
    assert resolve_precision("sds", 1e-8) == p
    assert resolve_precision("auto", 1e-4).to_string() == "hss"
    assert resolve_precision("auto", 1e-10).to_string() == "ddd"
    # without x64 a "d" leg would silently run in f32: auto stays on h/s
    with jax.enable_x64(False):
        assert resolve_precision("auto", 1e-10).to_string() == "sss"
    with pytest.raises(TypeError):
        resolve_precision(42, 1e-8)
    with pytest.raises(ValueError):
        resolve_precision("bogus", 1e-8)


def test_cgnr_accepts_auto_precision():
    op, _, m_true = small_problem(Nt=8, Nd=3, Nm=6)
    d_obs = op.matvec(m_true)
    res = cg_normal_equations(op, d_obs, damp=1e-8, tol=1e-8,
                              maxiter=400, precision="auto")
    assert rel_l2(op.matvec(res.x), d_obs) < 1e-4


# ---------------------------------------------------------------------------
# Communication-precision knob (reduced-precision reductions)
# ---------------------------------------------------------------------------

def test_error_bound_comm_level_term():
    """The reduction-tree term prices the comm level: comm=None reproduces
    the old single-level bound exactly, a lower comm level can only raise
    the bound, and on one device (log2 p = 0) the knob is free."""
    from repro.core.error_model import relative_error_bound
    cfg = PrecisionConfig.from_string("ddddd")
    kw = dict(p_r=1, p_c=64)
    base = relative_error_bound(cfg, 128, 25, 625, **kw)
    same = relative_error_bound(cfg, 128, 25, 625, comm_level="d", **kw)
    low = relative_error_bound(cfg, 128, 25, 625, comm_level="s", **kw)
    lower = relative_error_bound(cfg, 128, 25, 625, comm_level="h", **kw)
    assert same == base and base < low < lower
    # the split factors still sum to the old 1 + log2(p) at one level
    f = phase_factors(128, 25, 625, 1, 64)
    assert f["reduce"] + f["comm"] == pytest.approx(1.0 + np.log2(64))
    # single device: the tree term vanishes
    assert relative_error_bound(cfg, 128, 25, 625, comm_level="h") \
        == pytest.approx(relative_error_bound(cfg, 128, 25, 625))


def test_prune_lattice_comm_level_pass_through():
    """A low comm level tightens feasibility through the same pruner."""
    lattice = list(all_configs(("d", "s")))
    hi = prune_lattice(lattice, 1e-10, 128, 25, 625, p_c=4096)
    lo = prune_lattice(lattice, 1e-10, 128, 25, 625, p_c=4096,
                       comm_level="h")
    assert len(lo.model_feasible) <= len(hi.model_feasible)
    for cfg in lattice:
        assert lo.bounds[cfg.to_string()] >= hi.bounds[cfg.to_string()]


def test_cache_key_carries_comm_level(tmp_path):
    """TuningCache entries are keyed on the comm knob: a reduced-comm tune
    never answers a full-precision query (and vice versa)."""
    op, _, _ = small_problem(Nt=8, Nd=3, Nm=6)
    k_hi = CacheKey.for_operator(op, ("d", "s"), "matvec")
    k_lo = CacheKey.for_operator(op, ("d", "s"), "matvec", comm_level="s")
    assert k_hi.to_string() != k_lo.to_string()
    assert ";comm=s" in k_lo.to_string()


def test_autotune_reads_operator_comm_level(tmp_path):
    """autotune keys the cache on op.comm_level and still selects a
    feasible config under the synthetic timer."""
    op, _, _ = small_problem(Nt=8, Nd=3, Nm=6)
    lo_op = op.with_comm("s")
    assert lo_op.comm_level == "s"
    cache = TuningCache(tmp_path / "tune.json")
    res = autotune(lo_op, tol=1e-6, timer=fake_timer, cache=cache)
    assert res.record.rel_error <= 1e-6
    assert ";comm=s" in res.cache_key.to_string()
    # the full-precision operator misses that entry and re-tunes
    res_hi = autotune(op, tol=1e-6, timer=fake_timer, cache=cache)
    assert not res_hi.from_cache


def test_calibrate_constants_no_double_count_at_scale():
    """The reduce probe's error covers the storage cast AND the log2(p)
    comm tree at the probed level; c5 must be fitted against their summed
    factor — dividing by the storage term alone would inflate c5 by
    (1 + log2 p) and the bound would double-count the tree."""
    Nt, Nd, Nm, p_c = 64, 8, 100 * 64, 64
    f = phase_factors(Nt, Nd, Nm, 1, p_c)
    err = 1.0 * machine_eps("s") * (f["reduce"] + f["comm"])
    fitted = calibrate_constants({"reduce": {"s": err}}, Nt, Nd, Nm, p_c=p_c)
    assert fitted["c5"] == pytest.approx(1.0)


def test_error_floor_explicit_grid_override():
    """An explicit (1, 1) must price the single-device floor even for a
    meshed operator (None means 'read the grid off the mesh')."""
    from repro.solvers import error_floor
    op, _, _ = small_problem(Nt=8, Nd=3, Nm=6)
    assert error_floor(op, p_r=1, p_c=1) == error_floor(op)  # no mesh
    # once the gemv term is fully sharded away (n_m = 1), the remaining
    # grid dependence is the comm tree — the floor must grow with it
    assert error_floor(op, p_r=1, p_c=4096) \
        > error_floor(op, p_r=1, p_c=6)
