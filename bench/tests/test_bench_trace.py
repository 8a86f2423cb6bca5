"""The reduction from a profiler trace to per-layer numbers."""

import gzip
import json
import os

import pytest

from bench_tiny import BENCH, harness

tracing = harness().tracing
RECORDED = os.path.join(BENCH, "tests", "data", "trace_paper_sssss_matvec.json.gz")

HLO = """HloModule jit_matvec, entry_computation_layout={()}

%fused_fft (p: f32[1001,5000]) -> c64[1001,5000] {
  %p = f32[1001,5000]{1,0} parameter(0)
  ROOT %fft.1 = c64[1001,5000]{1,0} fft(%p), fft_type=RFFT, fft_length={2000}
}

%region_add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b)
}

ENTRY %main.1 (Arg_0.1: f32[1001,100,5000], Arg_1.2: f32[5000,2000]) -> f32[100,1000] {
  %Arg_0.1 = f32[1001,100,5000]{2,1,0} parameter(0)
  %Arg_1.2 = f32[5000,2000]{1,0} parameter(1)
  %pad_kernel = f32[5000,2000]{1,0} custom-call(f32[5000,1000]{1,0} %Arg_1.2), custom_call_target="tpu_custom_call"
  %fusion.3 = c64[1001,5000]{1,0} fusion(%pad_kernel), kind=kCustom, calls=%fused_fft
  %custom-call.7 = f32[1001,1,100]{2,1,0} custom-call(f32[1001,100,5000]{2,1,0} %Arg_0.1, f32[1001,1,5000]{2,1,0} %fusion.3), custom_call_target="tpu_custom_call"
  %all-reduce-start.2 = f32[100,2000]{1,0} all-reduce-start(%custom-call.7), replica_groups={{0,1,2,3}}, to_apply=%region_add
  %all-reduce-done.2 = f32[100,2000]{1,0} all-reduce-done(%all-reduce-start.2)
  %copy.4 = f32[100,2000]{1,0} copy(%all-reduce-done.2)
  ROOT %fft.5 = f32[100,1000]{1,0} fft(%copy.4), fft_type=IRFFT, fft_length={2000}
}
"""


def test_classify_by_opcode_and_operand_shape():
    layer_of = tracing.classifier([HLO], n_bins=1001)
    assert layer_of("custom-call.7") == "phase3"
    assert layer_of("pad_kernel") == "other"          # a 2-D kernel
    assert layer_of("fusion.3") == "fft"              # takes what it calls
    assert layer_of("fft.5") == "fft"
    assert layer_of("all-reduce-start.2") == "collective"
    assert layer_of("all-reduce-done.2") == "collective"
    assert layer_of("copy.4") == "other"
    # a TPU event is named by its instruction's text
    assert layer_of("%fusion.3 = c64[1001,5000]{1,0:T(8,128)} fusion(...)") \
        == "fft"
    # an eager op outside the modules is judged by its own text
    assert layer_of('%tpu_custom_call.1 = (f32[1001,1,100]) custom-call('
                    'f32[1001,100,5000]{2,1,0} %copy), custom_call_target='
                    '"tpu_custom_call"') == "phase3"
    assert layer_of('%convolution.8 = f32[5000,2000] convolution(%a, %b), '
                    'metadata={op_name="jit(<lambda>)/jit(fft)"}') == "fft"
    assert layer_of("%copy.9 = f32[5000,2000] copy(%a)") == "other"


def test_union_of_intervals():
    assert tracing._union([(0, 10), (5, 15), (20, 25), (21, 22)]) == 20
    assert tracing._union([]) == 0


SYNTH = {
    "devices": {0: [["k", 100, 50], ["f", 150, 20], ["f", 400, 30],
                    ["k", 1000, 10]],
                1: [["k", 110, 40], ["c", 300, 100]]},
    "spans": [["apply", 90, 5], ["host_sync", 95, 200],
              ["apply", 350, 5], ["host_sync", 355, 100]],
}


def test_reduce_synthetic_window():
    layers = {"k": "phase3", "f": "fft", "c": "collective"}
    s = tracing.reduce(SYNTH, lambda n: layers.get(n, "other"), "apply")
    # window: first apply (90) to the end of the last host span (455)
    assert s["count"] == 2 and s["window_s"] == pytest.approx(365e-9)
    assert s["busy_s"][0] == pytest.approx(100e-9)  # 100-170, 400-430
    assert s["busy_s"][1] == pytest.approx(140e-9)
    assert s["layer_s"][0] == pytest.approx({"phase3": 50e-9, "fft": 50e-9})
    assert s["layer_s"][1] == pytest.approx({"phase3": 40e-9,
                                             "collective": 100e-9})
    assert [n for n, _ in s["top_ops"]] == ["k", "f"]
    # device 0 idles 90-100, 170-400 and 430-455; each gap is named by
    # the host span at its middle
    gaps = s["idle_gaps"]
    assert gaps[0] == ["host_sync", pytest.approx(230e-9)]
    assert sorted(g[1] for g in gaps) == pytest.approx([10e-9, 25e-9,
                                                        230e-9])
    assert tracing.per_call_s(s, "phase3") == pytest.approx(45e-9 / 2)
    assert tracing.per_call_s(s, "collective", first_only=True) is None
    assert tracing.idle_share(s, "apply") == pytest.approx(
        100 * (1 - 120 / 365))
    assert tracing.idle_share(s, "solve") is None


def test_reduce_without_the_span_reads_nothing():
    s = tracing.reduce(SYNTH, lambda n: "other", "solve")
    assert s == {"count": 0}
    assert tracing.per_call_s(s, "fft") is None


def _brute(trace, layer_of, span):
    """The same numbers by a sweep over every nanosecond of the window."""
    spans = [s for s in trace["spans"] if s[0] == span]
    w0 = spans[0][1]
    w1 = max(s[1] + s[2] for s in trace["spans"])
    out = {}
    for dev, ops in trace["devices"].items():
        busy = bytearray(w1 - w0)
        per = {}
        for name, s, d in ops:
            if w0 <= s < w1:
                busy[s - w0:min(s + d, w1) - w0] = b"\1" * (min(s + d, w1) - s)
                lay = layer_of(name)
                per[lay] = per.get(lay, 0) + d
        out[dev] = (sum(busy), per)
    return (w1 - w0), out


def test_recorded_chip_trace():
    """Six applications of ``paper_sssss.matvec`` traced on a TPU v5e."""
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    trace = {"devices": {int(k): v for k, v in rec["devices"].items()},
             "spans": rec["spans"]}
    layer_of = tracing.classifier([rec["hlo"]], rec["n_bins"])
    s = tracing.reduce(trace, layer_of, "apply")
    window, brute = _brute(trace, layer_of, "apply")
    assert s["count"] == rec["applications"] == 6
    assert s["window_s"] == pytest.approx(window * 1e-9)
    for dev, (busy, per) in brute.items():
        assert s["busy_s"][dev] == pytest.approx(busy * 1e-9)
        assert s["layer_s"][dev] == pytest.approx(
            {k: v * 1e-9 for k, v in per.items()})
    # what the chip showed, per application: the Phase-3 kernel 9.98 ms,
    # the DFT-matmul FFTs 4.11 ms, and 12.94 ms of other ops, nearly all
    # of it two relayout copies of the F_hat planes
    per_app = {k: tracing.per_call_s(s, k) * 1e3 for k in s["layer_s"][0]}
    assert per_app == pytest.approx({"phase3": 9.977, "fft": 4.108,
                                     "other": 12.943}, abs=1e-3)
    copies = [n for n, _, _ in trace["devices"][0]
              if n.startswith("%copy.") and "[1001,100,5000]" in n]
    assert copies and all(layer_of(n) == "other" for n in copies)
    assert 0 < tracing.idle_share(s, "apply") < 10
