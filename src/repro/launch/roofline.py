"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), all in seconds-per-step on the
TARGET hardware (TPU v5e-class constants):

    compute    = HLO_FLOPs_per_device   / peak_FLOPs      (197 TFLOP/s bf16)
    memory     = HLO_bytes_per_device   / HBM_bw          (819 GB/s)
    collective = collective_bytes_per_device / link_bw    (~50 GB/s/link)

``compiled.cost_analysis()`` reports per-device flops/bytes (the SPMD
module is the per-device program — verified).  collective_bytes is parsed
from the optimized HLO: every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute op's *operand* sizes are summed (operand
shapes resolved from the op-definition lines).
"""

from __future__ import annotations

import dataclasses
import math
import re

from repro.backend import TPU_PALLAS, BackendSpec

# Roofline peaks come from the backend capability spec (repro.backend);
# the TPU-v5e-class target spec keeps the historical constants.  The
# module-level names remain for callers that model the TPU target from
# other hosts (benchmarks on CPU).
TARGET_SPEC = TPU_PALLAS
PEAK_FLOPS = TARGET_SPEC.peak_flops      # bf16 per chip
HBM_BW = TARGET_SPEC.hbm_bandwidth       # B/s per chip
LINK_BW = TARGET_SPEC.link_bandwidth     # B/s per ICI link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2,
    "f32": 4, "f64": 8, "c64": 8, "c128": 16, "token": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+)$")


def cost_analysis_dict(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a plain dict (an empty one when the
    backend reports nothing).  Every roofline consumer goes through this."""
    return dict(compiled.cost_analysis() or {})


def _shape_bytes(type_str: str) -> int:
    """Bytes of an HLO type string, incl. tuples: 'f32[64,256]{1,0}'."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    counts: dict
    bytes_by_type: dict
    total_bytes: int

    def to_dict(self):
        return {"counts": self.counts, "bytes_by_type": self.bytes_by_type,
                "total_bytes": self.total_bytes}


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum operand bytes of every collective op in the optimized HLO.

    HLO definition lines look like ``%name = f32[64,256]{1,0}
    all-gather(%operand), channel_id=...``; operand shapes are resolved
    from a first pass over all definitions.  Async pairs (``-start`` /
    ``-done``) are counted once (at the -start)."""
    sizes: dict[str, int] = {}
    defs: list[tuple[str, str, str]] = []   # (result_type, opcode, argslist)
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        parts = rhs.split(None, 1)
        if len(parts) < 2:
            continue
        result_type, rest = parts
        opcode = rest.split("(")[0].strip()
        args = rest[rest.index("("):].split(")")[0] if "(" in rest else ""
        sizes[name] = _shape_bytes(result_type)
        defs.append((opcode, args, name))

    counts: dict[str, int] = {}
    bts: dict[str, int] = {}
    total = 0
    for opcode, args, _ in defs:
        coll = next((c for c in _COLLECTIVES
                     if opcode == c or opcode == f"{c}-start"), None)
        if coll is None:
            continue
        ops = re.findall(r"%([\w.\-]+)", args)
        b = sum(sizes.get(o, 0) for o in ops)
        counts[coll] = counts.get(coll, 0) + 1
        bts[coll] = bts.get(coll, 0) + b
        total += b
    return CollectiveStats(counts, bts, total)


_MATERIALIZE_OPS = {
    "dot", "fft", "convolution", "scatter", "gather", "dynamic-slice",
    "dynamic-update-slice", "reduce", "sort", "parameter",
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "all-gather-start", "all-reduce-start",
    "collective-permute-start", "pad", "concatenate", "iota",
}


def hbm_floor_bytes(hlo_text: str) -> int:
    """Perfect-fusion HBM-traffic floor.

    ``cost_analysis()['bytes accessed']`` counts every top-level op's
    operands+outputs — on the CPU backend, long elementwise chains stay
    unfused, inflating it far beyond what a TPU (which fuses converts /
    masks / softmax chains into matmul epilogues) would move.  The floor
    counts only ops that MUST materialize on any backend: matmuls/FFTs/
    convolutions (operands+results), gathers/scatters/dynamic slices,
    reductions, collectives, parameter reads and the ROOT outputs.  The
    true HBM traffic lies between this floor and the raw number; both are
    reported (EXPERIMENTS.md §Roofline discusses the gap)."""
    sizes: dict[str, int] = {}
    total = 0
    in_skippable = False
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and ") -> " in stripped:
            name = stripped.split(" ", 2)[1] if stripped.startswith("ENTRY") \
                else stripped.split(" ", 1)[0]
            name = name.lstrip("%")
            in_skippable = any(t in name for t in
                               ("fused", "region", "wrapped"))
            continue
        if stripped == "}":
            in_skippable = False
            continue
        m = _DEF_RE.match(line)
        if m is None:
            continue
        name, rhs = m.group(1), m.group(2)
        parts = rhs.split(None, 1)
        if len(parts) < 2:
            continue
        result_type, rest = parts
        b = _shape_bytes(result_type)
        sizes[name] = b
        if in_skippable:
            continue
        opcode = rest.split("(")[0].strip()
        is_root = line.lstrip().startswith("ROOT")
        if opcode in _MATERIALIZE_OPS or is_root:
            operands = re.findall(r"%([\w.\-]+)", rest[rest.index("("):]
                                  .split(")")[0]) if "(" in rest else []
            total += b + sum(sizes.get(o, 0) for o in operands)
    return total


def roofline_terms(cost: dict, coll: CollectiveStats,
                   bytes_floor: float | None = None,
                   spec: BackendSpec | None = None) -> dict:
    """Three roofline terms against ``spec``'s peaks (default: the TPU
    target spec).  The memory term uses the perfect-fusion floor when
    provided (raw cost-analysis bytes kept as ``memory_raw_s``)."""
    peak_flops, hbm_bw, link_bw = (spec or TARGET_SPEC).roofline_peaks()
    flops = float(cost.get("flops", 0.0))
    bytes_raw = float(cost.get("bytes accessed", 0.0))
    bytes_mem = float(bytes_floor) if bytes_floor is not None else bytes_raw
    t_compute = flops / peak_flops
    t_memory = bytes_mem / hbm_bw
    t_coll = coll.total_bytes / link_bw
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dom = max(terms, key=terms.get)
    bound = max(terms.values())
    terms.update({
        "dominant": dom.replace("_s", ""),
        "step_time_bound_s": bound,
        "memory_raw_s": bytes_raw / hbm_bw,
        "flops_per_device": flops,
        "bytes_per_device": bytes_mem,
        "bytes_raw_per_device": bytes_raw,
        "collective_bytes_per_device": coll.total_bytes,
    })
    return terms


def model_flops(n_params: int, n_active_params: int, tokens: int,
                kind: str) -> float:
    """Reference MODEL_FLOPS: 6*N*D for training (fwd+bwd), 2*N*D forward
    (prefill/decode); MoE uses active params."""
    n = n_active_params or n_params
    per_tok = 6 * n if kind == "train" else 2 * n
    return float(per_tok) * tokens


def useful_ratio(mf: float, flops_per_device: float, n_devices: int) -> float:
    hlo_global = flops_per_device * n_devices
    return mf / hlo_global if hlo_global else float("nan")


def roofline_fraction(mf: float, bound_s: float, n_devices: int,
                      spec: BackendSpec | None = None) -> float:
    """Achieved fraction of compute roofline: useful FLOPs per second at the
    modeled step time vs peak."""
    peak_flops = (spec or TARGET_SPEC).roofline_peaks()[0]
    if bound_s <= 0:
        return float("nan")
    return (mf / n_devices / bound_s) / peak_flops
