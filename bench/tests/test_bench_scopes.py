"""The readers of the program's own scopes and spans (``scopes.py``)."""

import jax
import pytest

from bench_tiny import SEED, harness, tiny_cell

run = harness()
import scopes  # noqa: E402  (on the path once the harness is)

HLO = """HloModule jit_matvec, entry_computation_layout={()}

%fused_dft (p: f32[5000,2000]) -> f32[5000,2000] {
  %p = f32[5000,2000]{1,0} parameter(0)
  ROOT %convolution.3 = f32[5000,2000]{1,0} convolution(%p, %p), metadata={op_name="jit(matvec)/fftmatvec/fft/jit(fft)"}
}

%fused_twiddle () -> f32[2000] {
  ROOT %iota.1 = f32[2000]{0} iota(), iota_dimension=0, metadata={op_name="jit(matvec)/fftmatvec/ifft/jit(fft)"}
}

ENTRY %main.1 (Arg_0.1: f32[1001,100,5000], Arg_1.2: f32[5000,1000]) -> f32[100,1000] {
  %Arg_0.1 = f32[1001,100,5000]{2,1,0} parameter(0)
  %Arg_1.2 = f32[5000,1000]{1,0} parameter(1)
  %copy.15 = f32[1001,100,5000]{2,1,0} copy(%Arg_0.1), metadata={op_name="o.F_hat_re"}
  %pad_cast.1 = f32[5000,2000]{1,0} custom-call(%Arg_1.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(matvec)/fftmatvec/pad/pad_cast/pallas_call"}
  %fusion.5 = f32[5000,2000]{1,0} fusion(%pad_cast.1), kind=kOutput, calls=%fused_dft
  %multiply_reduce_fusion = f32[2000]{0} fusion(), kind=kLoop, calls=%fused_twiddle
  %sbgemv_n_complex.4 = f32[1001,1,100]{2,1,0} custom-call(%copy.15, %fusion.5), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(matvec)/fftmatvec/gemv/sbgemv_n_complex/pallas_call"}
  %matvec.3 = f32[5000,2000]{1,0} custom-call(%Arg_1.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(matvec)/pallas_call"}
  %reshape.2 = f32[100,1001]{1,0} reshape(%sbgemv_n_complex.4), metadata={op_name="jit(matvec)/fftmatvec/reshape"}
  %transpose.3 = f32[100,1001]{1,0} transpose(%reshape.2), metadata={op_name="jit(matvec)/fftmatvec/gemv/pad"}
  %reverse.2 = f32[100,1001]{1,0} reverse(%transpose.3), dimensions={1}
  ROOT %convert.9 = f32[100,1000]{1,0} convert(%reverse.2), metadata={op_name="jit(matvec)/convert_element_type"}
}
"""


def test_scope_of_names_each_op_its_stage():
    kind_of = scopes.scope_of([HLO])
    assert kind_of("pad_cast.1") == "pad"               # its own op_name
    assert kind_of("fusion.5") == "fft"                 # what it calls
    assert kind_of("multiply_reduce_fusion") == "ifft"  # no metadata of its own
    assert kind_of("sbgemv_n_complex.4") == "gemv"      # a named kernel
    assert kind_of("reshape.2") == "fftmatvec"          # the executor's own
    # the first scope under the executor is the stage, not a primitive
    assert kind_of("transpose.3") == "gemv"
    # XLA's relayout of an argument, its own ops, the io cast outside the
    # executor and a kernel outside it have no stage
    for name in ("copy.15", "reverse.2", "convert.9", "Arg_0.1", "matvec.3"):
        assert kind_of(name) is None, name
    # a TPU event is named by its instruction's text
    assert kind_of("%fusion.5 = f32[5000,2000]{1,0:T(8,128)} fusion(...)") \
        == "fft"
    # an event the modules do not hold is judged by its own text
    assert kind_of('%add.7 = f32[8] add(%a, %b), metadata={op_name='
                   '"jit(apply)/fftmatvec/ifft/add"}') == "ifft"
    assert kind_of("%copy.9 = f32[5000,2000] copy(%a)") is None
    assert scopes.has_scopes([HLO])
    assert not scopes.has_scopes([HLO.replace("fftmatvec/", "")])


def _span(name, start, dur, **stats):
    return [name, start, dur, stats]


# two solves; device 0 idles 100-130 (in pcg.solve), 170-260 (a gap that
# straddles the end of a pcg.sync span at 200), 300-320 (host_sync),
# 320-330 (in no span), 400-410 (in solve, before pcg.solve) and 500-505
# (host_sync)
SOLVES = {
    "devices": {0: [["g", 90, 10], ["n", 130, 40], ["g", 260, 40],
                    ["g", 330, 70], ["n", 410, 90]]},
    "spans": sorted([
        _span("solve", 90, 210), _span("pcg.solve", 95, 200, S=1),
        _span("pcg.sync", 160, 40, k=0), _span("pcg.sync", 240, 20, k=1),
        _span("host_sync", 300, 20),
        _span("solve", 400, 100), _span("pcg.solve", 410, 90, S=1),
        _span("pcg.sync", 480, 10, k=0), _span("host_sync", 500, 5)],
        key=lambda s: s[1]),
}


def test_idle_by_span_splits_each_gap_at_span_boundaries():
    idle = scopes.idle_by_span(SOLVES, "solve")
    # 170-200 is in pcg.sync, 200-240 in pcg.solve alone, 240-260 in
    # pcg.sync again; the midpoint rule would give all 90 to pcg.solve
    assert idle == pytest.approx({"pcg.solve": 70e-9, "pcg.sync": 50e-9,
                                  "host_sync": 25e-9, "solve": 10e-9,
                                  "none": 10e-9})
    # the pieces sum to the window's idle time, as tracing.reduce counts it
    plain = {"devices": SOLVES["devices"],
             "spans": [sp[:3] for sp in SOLVES["spans"]]}
    s = run.tracing.reduce(plain, lambda n: "other", "solve")
    assert sum(idle.values()) == pytest.approx(
        s["window_s"] - s["busy_s"][0])
    assert scopes.idle_by_span(SOLVES, "apply") is None
    assert scopes.count(SOLVES, "pcg.sync", "solve") == 3


APPLIES = {
    "devices": {0: [["%copy.15 = f32[1001,100,5000] copy(...)", 100, 30],
                    ["%sbgemv_n_complex.4 = f32[1001,1,100] custom-call(...)",
                     130, 20],
                    ["%fusion.5 = f32[5000,2000] fusion(...)", 150, 10],
                    ["%copy.15 = f32[1001,100,5000] copy(...)", 200, 30],
                    ["%reverse.2 = f32[100,1001] reverse(...)", 230, 4]]},
    "spans": [_span("apply", 95, 5), _span("host_sync", 100, 90),
              _span("apply", 195, 5), _span("host_sync", 200, 40)],
}


def _ctx(trace, span, hlo=(HLO,), monkeypatch=None):
    """A reader's ``ctx`` whose raw window is ``trace``."""
    monkeypatch.setattr(scopes, "load", lambda d: trace)
    return {"run": {"span": span}, "trace_dir": f"synthetic-{id(trace)}",
            "hlo": list(hlo)}


def read(name, ctx):
    return run.load_metric(name).read(ctx)


def test_readers_on_a_window_of_applications(monkeypatch):
    ctx = _ctx(APPLIES, "apply", monkeypatch=monkeypatch)
    assert read("unscoped_ms", ctx) == pytest.approx((30 + 30 + 4) / 2 * 1e-6)
    for name in ("loop_idle_ms", "sync_idle_ms", "syncs_per_solve"):
        assert read(name, ctx) is None, name
    # a program without stage scopes reads nothing
    bare = _ctx(APPLIES, "apply", hlo=[HLO.replace("fftmatvec/", "")],
                monkeypatch=monkeypatch)
    assert read("unscoped_ms", bare) is None


def test_readers_on_a_window_of_solves(monkeypatch):
    ctx = _ctx(SOLVES, "solve", monkeypatch=monkeypatch)
    assert read("loop_idle_ms", ctx) == pytest.approx(70e-9 * 1e3 / 2)
    assert read("sync_idle_ms", ctx) == pytest.approx(50e-9 * 1e3 / 2)
    assert read("syncs_per_solve", ctx) == 1.5
    assert read("unscoped_ms", ctx) is None
    # a program without the spans reads nothing
    bare = {"devices": SOLVES["devices"],
            "spans": [s for s in SOLVES["spans"] if s[0] in ("solve",
                                                             "host_sync")]}
    ctx = _ctx(bare, "solve", monkeypatch=monkeypatch)
    for name in ("loop_idle_ms", "sync_idle_ms", "syncs_per_solve"):
        assert read(name, ctx) is None, name


def test_window_is_found_in_the_frame_that_built_ctx(monkeypatch):
    monkeypatch.setattr(scopes, "load", lambda d: SOLVES if d == "here"
                        else None)

    class Traffic:
        hlo = [HLO]

    def read_trace(traffic, trace_dir):
        ctx = {"run": {"span": "solve"}, "trace": {}}
        return scopes.window(ctx), read("syncs_per_solve", ctx)

    (trace, hlo), value = read_trace(Traffic(), "here")
    assert trace is SOLVES and hlo == [HLO] and value == 1.5
    assert scopes.window({"run": {"span": "solve"}}) is None


def test_traced_tiny_solve_counts_its_round_trips(monkeypatch):
    """A whole traced run of the CGNR cell at a test's size: the harness's
    own ``read_trace`` finds the program's spans (the CPU has no device
    plane, so the idle readers read nothing)."""
    monkeypatch.setitem(run.PEAKS, jax.devices()[0].device_kind,
                        run.PEAKS["TPU v5 lite"])
    cell = tiny_cell("paper_sssss.cgnr")
    # the idle shares divide by the devices' planes, which the CPU lacks
    cell["per_layer"] = {k: u for k, u in cell["per_layer"].items()
                         if not k.startswith("idle_share")}
    res = run.run_cell(cell, SEED, 0.3, trace=True,
                       devices=jax.devices()[:1])
    assert res["correct"], res["check"]
    syncs = res["metrics"]["syncs_per_solve"]["value"]
    iters = res["metrics"]["iters_per_solve"]["value"]
    assert syncs == pytest.approx(iters + 2)
    assert "loop_idle_ms" not in res["metrics"]
