"""Backend portability layer: specs, registry probe/override, the
calibrated dispatch table, and end-to-end numerical parity between the
forced ``xla-ref`` reference backend and the capability-probed one."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backend import (BUILTIN_SPECS, CPU_INTERPRET, CPU_XLA,
                           DispatchTable, GPU_PALLAS, TPU_PALLAS,
                           UnsupportedOnBackend, XLA_REF, calibrate_dispatch,
                           calibrate_short_wide_ratio, current_backend,
                           default_table, register_backend, resolve_backend,
                           use_backend)
from repro.backend import registry as breg
from repro.backend.spec import BackendSpec
from repro.configs.fftmatvec_paper import SMOKE as PAPER_SMOKE
from repro.core import (ExecOpts, FFTMatvec, PrecisionConfig,
                        dense_matvec, random_block_column, rel_l2)
from repro.kernels import ops
from repro.tune import TuningCache

F32, F64 = jnp.float32, jnp.float64


# ---------------------------------------------------------------------------
# Specs + registry
# ---------------------------------------------------------------------------

def test_builtin_specs_are_distinct_and_capability_consistent():
    prints = [s.fingerprint() for s in BUILTIN_SPECS.values()]
    assert len(set(prints)) == len(prints)
    assert TPU_PALLAS.pallas and not TPU_PALLAS.pallas_f64
    assert not CPU_XLA.pallas
    # the kernels lower through the TPU Mosaic pipeline only — GPU
    # auto-dispatch must take the XLA path, never crash in lowering
    assert not GPU_PALLAS.pallas
    assert CPU_INTERPRET.pallas and CPU_INTERPRET.pallas_interpret
    assert XLA_REF.reference
    # capability queries
    assert TPU_PALLAS.pallas_supports(F32)
    assert not TPU_PALLAS.pallas_supports(F64)
    assert not CPU_XLA.pallas_supports(F32)


def test_probe_binds_live_device_and_env_overrides(monkeypatch):
    breg._reset_probe_cache()
    spec = current_backend()
    assert spec.platform == jax.devices()[0].platform   # cpu in CI
    monkeypatch.setenv(breg.BACKEND_ENV, "xla-ref")
    breg._reset_probe_cache()
    try:
        forced = current_backend()
        assert forced.name == "xla-ref" and forced.reference
        assert forced.platform == spec.platform          # bound at resolve
    finally:
        monkeypatch.delenv(breg.BACKEND_ENV)
        breg._reset_probe_cache()
    assert current_backend().name != "xla-ref"
    # the assert above cached a probe taken WITHOUT the (possibly
    # monkeypatched-away) env var; drop it so later tests re-probe under
    # the real process environment (e.g. the REPRO_BACKEND=xla-ref CI leg)
    breg._reset_probe_cache()


def test_resolve_unknown_name_lists_known():
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("definitely-not-a-backend")


def test_tpu_peaks_keyed_by_device_kind(monkeypatch):
    """Roofline peaks come from the device kind JAX reports; a TPU kind
    with no published entry binds no peaks, never a borrowed table, and
    its roofline is refused."""
    from types import SimpleNamespace
    from repro.backend import TPU_PEAKS, UnknownDevice
    from repro.launch.roofline import CollectiveStats, roofline_terms
    monkeypatch.delenv(breg.BACKEND_ENV, raising=False)
    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    bound = breg.probe_backend(v5e)
    assert bound.name == "tpu-pallas" and bound.device_kind == "TPU v5 lite"
    assert bound.hbm_bandwidth == TPU_PEAKS["TPU v5 lite"]["hbm_bandwidth"]
    coll = CollectiveStats({}, {}, 0)
    assert roofline_terms({"flops": 1.0}, coll, spec=bound)["compute_s"] > 0
    unknown = breg.probe_backend(SimpleNamespace(
        platform="tpu", device_kind="TPU v0 imaginary"))
    assert unknown.name == "tpu-pallas"
    with pytest.raises(UnknownDevice, match="TPU v0 imaginary"):
        roofline_terms({"flops": 1.0}, coll, spec=unknown)
    # a platform with no spec is refused, not run on the CPU defaults
    with pytest.raises(ValueError, match="no backend spec"):
        breg.probe_backend(SimpleNamespace(platform="metal",
                                           device_kind="M9"))


def test_unknown_tpu_kind_runs_the_operator(monkeypatch):
    """A TPU kind missing from TPU_PEAKS still builds and applies an
    operator (the Pallas kernels in interpret mode here); only its
    roofline share is refused."""
    from types import SimpleNamespace
    from repro.backend import UnknownDevice
    from repro.launch.roofline import roofline_fraction
    monkeypatch.delenv(breg.BACKEND_ENV, raising=False)
    unknown = breg.probe_backend(SimpleNamespace(
        platform="tpu", device_kind="TPU v0 imaginary"))
    spec = dataclasses.replace(unknown, pallas_interpret=True)
    N_t, N_d, N_m = 6, 3, 20
    F_col = random_block_column(jax.random.PRNGKey(3), N_t, N_d, N_m)
    x = jax.random.normal(jax.random.PRNGKey(4), (N_m, N_t))
    op = FFTMatvec.from_block_column(
        F_col, precision=PrecisionConfig.from_string("sssss"), backend=spec)
    assert op.F_hat_re.dtype == F32        # set-up at the top of s/h
    assert rel_l2(op.matvec(x), dense_matvec(F_col, x)) < 1e-5
    with pytest.raises(UnknownDevice):
        roofline_fraction(1.0, 1.0, 1, spec=spec)


def test_use_compile_cache_respects_env(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, where set, places the cache and the
    helper changes nothing; otherwise the cache sits at one fixed path in
    the checkout."""
    import os
    from repro.jax_compat import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = use_compile_cache()
        assert path == use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
        assert os.path.basename(path) == ".jax_cache"
        assert os.path.isdir(os.path.join(os.path.dirname(path), "src",
                                          "repro"))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_use_backend_scopes_override_and_custom_registration():
    custom = register_backend(dataclasses.replace(
        CPU_XLA, name="test-custom", sublane=16))
    with use_backend("test-custom") as spec:
        assert spec.sublane == 16
        assert current_backend().name == "test-custom"
    assert current_backend().name != "test-custom"
    assert resolve_backend(custom).name == "test-custom"


# ---------------------------------------------------------------------------
# Dispatch table: shape -> path across specs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec,m,n,dtype,want", [
    (TPU_PALLAS, 100, 5000, F32, "pallas"),   # the paper's short-wide case
    (TPU_PALLAS, 1000, 1000, F32, "xla"),     # squarish -> stock lowering
    (TPU_PALLAS, 100, 5000, F64, "xla"),      # auto f64 falls back
    (CPU_XLA, 100, 5000, F32, "xla"),         # no Pallas at all
    (CPU_INTERPRET, 100, 5000, F32, "pallas"),
    (XLA_REF, 100, 5000, F32, "ref"),         # reference forces oracles
])
def test_gemv_path_across_specs(spec, m, n, dtype, want):
    assert DispatchTable().gemv_path(m, n, "H", dtype, spec) == want


def test_default_table_of_reference_backend_forces_ref():
    assert default_table(XLA_REF).force == "ref"
    assert default_table(TPU_PALLAS).force is None


def test_transition_point_is_honored():
    t = DispatchTable(short_wide_ratio=8)
    assert t.gemv_path(16, 16 * 8, "H", F32, TPU_PALLAS) == "pallas"
    assert t.gemv_path(16, 16 * 7, "H", F32, TPU_PALLAS) == "xla"


def test_forced_pallas_raises_where_unsupported():
    force = DispatchTable(force="pallas")
    with pytest.raises(UnsupportedOnBackend, match="has none"):
        force.gemv_path(8, 64, "H", F32, CPU_XLA)
    with pytest.raises(UnsupportedOnBackend, match="f64"):
        force.gemv_path(8, 64, "H", F64, TPU_PALLAS)
    # a reference backend must not silently satisfy an explicit Pallas
    # demand through the oracle lowering
    with pytest.raises(UnsupportedOnBackend, match="has none"):
        force.gemv_path(8, 64, "H", F32, XLA_REF)
    # stage-level view relaxes the *dtype* capability only (pipeline
    # semantics: d stages of a forced-Pallas ladder run via XLA) ...
    relaxed = force.for_dtype(F64, TPU_PALLAS)
    assert relaxed.force is None
    assert force.for_dtype(F32, TPU_PALLAS).force == "pallas"
    # ... but never the Pallas capability itself: on a backend with no
    # Pallas the force survives so the kernel layer raises
    assert force.for_dtype(F32, CPU_XLA).force == "pallas"
    assert force.for_dtype(F64, CPU_XLA).force == "pallas"


def test_fuse_pad_cast_policy():
    t = DispatchTable()
    assert t.fuse_pad_cast(1000, F32, jnp.bfloat16, TPU_PALLAS)
    assert not t.fuse_pad_cast(1000, F64, F32, TPU_PALLAS)   # no f64 Pallas
    assert not t.fuse_pad_cast(1000, F32, F32, XLA_REF, prefer=True)
    # interpret mode fuses only on explicit preference
    assert not t.fuse_pad_cast(1000, F32, F32, CPU_INTERPRET)
    assert t.fuse_pad_cast(1000, F32, F32, CPU_INTERPRET, prefer=True)
    # cutover
    t2 = DispatchTable(pad_cast_min_cols=512)
    assert not t2.fuse_pad_cast(100, F32, F32, TPU_PALLAS)
    assert t2.fuse_pad_cast(512, F32, F32, TPU_PALLAS)


# ---------------------------------------------------------------------------
# The f64 explicit-vs-auto regression (the old silent downgrade)
# ---------------------------------------------------------------------------

def test_ops_explicit_pallas_f64_raises_auto_falls_back():
    B, m, n, S = 2, 4, 64, 3
    A = jnp.ones((B, m, n), F64)
    x = jnp.ones((B, m), F64)
    X = jnp.ones((B, m, S), F64)
    force = DispatchTable(force="pallas")
    for call in (lambda **kw: ops.sbgemv(A, A, x, x, "H", **kw),
                 lambda **kw: ops.sbgemm(A, A, X, X, "H", **kw),
                 lambda **kw: ops.sbgemv_real(A, x, "T", **kw),
                 lambda **kw: ops.sbgemm_gram(A, A, **kw)):
        with pytest.raises(UnsupportedOnBackend):
            call(backend=CPU_INTERPRET, dispatch=force)
        # auto dispatch silently falls back and keeps f64
        out = call(backend=CPU_INTERPRET)
        leaf = out[0] if isinstance(out, tuple) else out
        assert leaf.dtype == F64


# ---------------------------------------------------------------------------
# Calibration + TuningCache round-trip (rocBLAS-style persisted thresholds)
# ---------------------------------------------------------------------------

def _synthetic_measure(crossover):
    """Pallas wins exactly from `crossover` skew upward."""
    def measure(path, m, n):
        if path == "xla":
            return 1.0
        return 0.5 if n / m >= crossover else 2.0
    return measure


def test_calibrated_threshold_roundtrips_through_tuning_cache(tmp_path):
    cache = TuningCache(tmp_path / "tune.json")
    table = calibrate_dispatch(TPU_PALLAS, measure=_synthetic_measure(8),
                               cache=cache)
    assert table.calibrated and table.short_wide_ratio == 8
    # the calibrated transition moves auto dispatch
    assert table.gemv_path(16, 16 * 8, "H", F32, TPU_PALLAS) == "pallas"
    assert table.gemv_path(16, 16 * 4, "H", F32, TPU_PALLAS) == "xla"

    def boom(path, m, n):
        raise AssertionError("re-measured despite a cached table")

    reloaded = calibrate_dispatch(TPU_PALLAS,
                                  measure=boom,
                                  cache=TuningCache(tmp_path / "tune.json"))
    assert reloaded == table

    # corrupting the stored table reads as a miss -> re-calibrates
    import json
    path = tmp_path / "tune.json"
    data = json.loads(path.read_text())
    key = next(k for k in data if k.startswith("dispatch/"))
    data[key]["table"] = "garbage"
    path.write_text(json.dumps(data))
    re_cal = calibrate_dispatch(TPU_PALLAS, measure=_synthetic_measure(16),
                                cache=TuningCache(path))
    assert re_cal.short_wide_ratio == 16


def test_calibration_never_wins_pushes_ratio_out_of_range():
    table = calibrate_dispatch(TPU_PALLAS,
                               measure=lambda path, m, n:
                               0.1 if path == "xla" else 1.0)
    assert table.short_wide_ratio == float("inf")
    assert table.gemv_path(1, 10 ** 6, "H", F32, TPU_PALLAS) == "xla"


def test_calibration_without_pallas_keeps_xla():
    ratio = calibrate_short_wide_ratio(CPU_XLA,
                                       measure=_synthetic_measure(2))
    assert DispatchTable(short_wide_ratio=ratio).gemv_path(
        1, 10 ** 6, "H", F32, CPU_XLA) == "xla"


# ---------------------------------------------------------------------------
# ExecOpts + the retired deprecation shim
# ---------------------------------------------------------------------------

def test_exec_opts_resolution_and_hashability():
    r = ExecOpts().resolve()
    assert r.spec == current_backend()
    assert r.block_n == r.spec.default_block_n
    assert hash(ExecOpts(backend="xla-ref")) != hash(ExecOpts())
    r2 = ExecOpts(backend="cpu-interpret", block_n=128).resolve()
    assert r2.spec.pallas_interpret and r2.block_n == 128


def test_legacy_kwargs_are_gone():
    """The one-release shim promised in the backend-layer PR is retired:
    the old use_pallas/interpret/xla_fused kwargs are hard TypeErrors, and
    MatvecOptions is no longer exported — no DeprecationWarning path
    survives in kernels.ops."""
    import warnings
    import repro.core
    A = jnp.ones((2, 4, 64), F32)
    x = jnp.ones((2, 4), F32)
    for kw in ({"use_pallas": True}, {"interpret": True},
               {"xla_fused": False}):
        with pytest.raises(TypeError):
            ops.sbgemv(A, A, x, x, "H", **kw)
    with pytest.raises(TypeError):
        ops.pad_cast(x, 8, F32, use_pallas=True)
    assert not hasattr(repro.core, "MatvecOptions")
    # the new spelling never warns
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        ops.sbgemv(A, A, x, x, "H", backend=CPU_INTERPRET,
                   dispatch=DispatchTable(force="pallas"))


# ---------------------------------------------------------------------------
# End-to-end parity: xla-ref vs the probed backend on the paper config
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prec,tol", [("ddddd", 1e-13), ("dssdd", 1e-6)])
def test_xla_ref_parity_with_probed_backend_paper_config(prec, tol):
    """Acceptance: matvec under REPRO_BACKEND=xla-ref and under the
    auto-probed backend agree to roundoff on the (scaled) paper config."""
    n_t, n_d, n_m = PAPER_SMOKE.N_t, PAPER_SMOKE.N_d, PAPER_SMOKE.N_m
    F_col = random_block_column(jax.random.PRNGKey(0), n_t, n_d, n_m,
                                dtype=F64)
    m = jax.random.normal(jax.random.PRNGKey(1), (n_m, n_t), F64)
    op = FFTMatvec.from_block_column(
        F_col, precision=PrecisionConfig.from_string(prec))
    assert op.opts.resolve().spec == current_backend()
    ref_op = op.with_backend("xla-ref")
    assert ref_op.opts.resolve().spec.reference
    d_auto, d_ref = op.matvec(m), ref_op.matvec(m)
    assert rel_l2(d_auto, d_ref) < tol
    # and both sit on the dense truth
    dense = dense_matvec(F_col, m)
    assert rel_l2(d_ref, dense) < max(tol, 1e-13)


def test_env_forced_reference_backend_drives_default_operator(monkeypatch):
    """REPRO_BACKEND=xla-ref reroutes operators built with default opts —
    the CI matrix leg in miniature."""
    monkeypatch.setenv(breg.BACKEND_ENV, "xla-ref")
    breg._reset_probe_cache()
    try:
        op = FFTMatvec.from_block_column(random_block_column(
            jax.random.PRNGKey(2), 8, 2, 12, dtype=F64))
        assert op.opts.resolve().spec.reference
        m = jax.random.normal(jax.random.PRNGKey(3), (12, 8), F64)
        assert op.matvec(m).shape == (2, 8)
    finally:
        monkeypatch.delenv(breg.BACKEND_ENV)
        breg._reset_probe_cache()


def test_pipeline_new_api_pallas_backend_matches_xla():
    """The new-API spelling of the old use_pallas/interpret pipeline test."""
    n_t, n_d, n_m = 16, 4, 64
    F_col = random_block_column(jax.random.PRNGKey(7), n_t, n_d, n_m)
    m = jax.random.normal(jax.random.PRNGKey(8), (n_m, n_t), F32)
    prec = PrecisionConfig.from_string("sssss")
    base = FFTMatvec.from_block_column(F_col, precision=prec)
    pal = FFTMatvec.from_block_column(
        F_col, precision=prec,
        opts=ExecOpts(backend="cpu-interpret",
                      dispatch=DispatchTable(force="pallas"),
                      block_n=128, fuse_pad_cast=True))
    assert rel_l2(pal.matvec(m), base.matvec(m)) < 1e-5


def test_pipeline_forced_pallas_relaxes_for_f64_stages():
    """A forced-Pallas preference must not error out of the paper's d
    stages — stage-level dispatch relaxes to auto exactly where the
    backend has no f64 Pallas (the documented pipeline semantics)."""
    F_col = random_block_column(jax.random.PRNGKey(9), 12, 3, 24,
                                dtype=F64)
    m = jax.random.normal(jax.random.PRNGKey(10), (24, 12), F64)
    op = FFTMatvec.from_block_column(
        F_col, precision=PrecisionConfig.from_string("ddddd"),
        opts=ExecOpts(backend="cpu-interpret",
                      dispatch=DispatchTable(force="pallas"), block_n=128))
    assert rel_l2(op.matvec(m), dense_matvec(F_col, m)) < 1e-13
