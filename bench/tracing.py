"""From a profiler trace to per-layer numbers.

``load`` reads the ``.xplane.pb`` the JAX profiler writes into a plain
dict: each device's operations ``[name, start_ns, duration_ns]`` and the
benchmark's own host spans ``[name, start_ns, duration_ns]``, on one
clock.  ``classifier`` sorts the device's operations into the layers the
per-layer metrics read (Phase-3 kernel, FFT, collective, other) by
opcode, operand shape and JAX's primitive names alone, never by a name
the program chooses.
``reduce`` turns both into the numbers of one traced window.
"""

from __future__ import annotations

import glob
import os
import re

# the benchmark's own host spans (run.py wraps its calls in them)
SPANS = ("apply", "solve", "host_sync")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
# device planes and the line of their XLA operations
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


def start(trace_dir: str) -> None:
    """Start the profiler: device operations and host annotations, no
    Python function tracing (it would slow the host loop it measures)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def load(trace_dir: str) -> dict:
    """{"devices": {id: [[op, start_ns, dur_ns], ...]}, "spans": [[name,
    start_ns, dur_ns], ...]} from the one ``.xplane.pb`` under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    devices, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = [
                [e.name, int(e.start_ns), int(e.duration_ns)]
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            spans += [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for line in plane.lines for e in line.events
                      if e.name in SPANS]
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "spans": spans}


# ---------------------------------------------------------------------------
# HLO instructions -> layers
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s"
                    r"([a-z][\w\-]*)\(", re.M)
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$", re.M)
_FFT_OP = re.compile(r'op_name="[^"]*(?:jit\(fft\)|/fft)["/]')
_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_CALLS = re.compile(r"(?:calls|to_apply|called_computations)="
                    r"\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")


def _computations(hlo: str) -> dict:
    """{computation: [(instruction, opcode, line)]} of an HLO module."""
    comps, current = {}, None
    for line in hlo.splitlines():
        head = _COMP.match(line)
        if head and "=" not in line.split("{")[0]:
            current = comps.setdefault(head.group(1), [])
            continue
        m = _INSTR.match(line)
        if m and current is not None:
            current.append((m.group(1), m.group(3), line))
    return comps


def _layer(opcode: str, line: str, plane: re.Pattern) -> str | None:
    base = opcode.removesuffix("-start").removesuffix("-done")
    if base in COLLECTIVES:
        return "collective"
    # the TPU lowers a length-2000 FFT to a DFT matmul: JAX's ``fft``
    # primitive names it in the op's metadata
    if opcode == "fft" or _FFT_OP.search(line):
        return "fft"
    if (opcode == "custom-call" and "tpu_custom_call" in line
            and plane.search(line.split("custom-call(", 1)[-1])):
        return "phase3"
    return None


def classifier(hlo_texts, n_bins: int):
    """A function from a device event to its layer.

    On a TPU an event is named by its HLO instruction text
    (``%fusion.10 = f32[...] fusion(...), ...``); it is looked up by its
    instruction name in the given modules, and an event the modules do
    not hold (an eager op) is judged by its own text.  Phase 3 is a
    Pallas kernel (``tpu_custom_call``) with an operand of ``n_bins``
    leading planes ``[n_bins, rows, cols]``, whole or in the row chunks of
    the pipelined reduction; the pad/cast kernels take 2-D operands.  A
    fusion takes the layer of what it calls."""
    plane = re.compile(rf"\[{n_bins},\d+,\d+\]")
    table = {}
    for hlo in hlo_texts:
        comps = _computations(hlo)

        def inner(comp, seen):
            for _, opcode, line in comps.get(comp, ()):
                found = _layer(opcode, line, plane)
                for called in _called(line) if found is None else ():
                    if called not in seen:
                        seen.add(called)
                        found = inner(called, seen)
                        if found:
                            break
                if found:
                    return found
            return None

        for instrs in comps.values():
            for name, opcode, line in instrs:
                found = _layer(opcode, line, plane)
                for called in _called(line) if found is None else ():
                    found = inner(called, {called})
                    if found:
                        break
                table[name] = found or "other"

    def layer_of(event: str) -> str:
        m = _NAME.match(event)
        name = m.group(1) if m else event
        if name in table:
            return table[name]
        m = _INSTR.match(event)
        return (m and _layer(m.group(3), event, plane)) or "other"

    return layer_of


def _called(line: str):
    for m in _CALLS.finditer(line):
        yield from (c.strip().lstrip("%") for c in m.group(1).split(","))


# ---------------------------------------------------------------------------
# one traced window -> numbers
# ---------------------------------------------------------------------------

def _union(intervals):
    """Total length of the union of [start, end) intervals."""
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def reduce(trace: dict, layer_of, span: str, top: int = 10) -> dict:
    """The numbers of one traced window.

    The window runs from the start of the first ``span`` to the end of
    the last host span (the wait for the last call included).  Per device: busy seconds (the union of its operations'
    intervals, clipped to the window) and seconds per layer (summed
    durations of the operations that start in it, by ``layer_of``).  ``top_ops``: device 0's
    operations that took most time; ``idle_gaps``: its longest idle gaps,
    each named by the host span it fell in ("none" outside them)."""
    spans = [s for s in trace["spans"] if s[0] == span]
    if not spans:
        return {"count": 0}
    w0, w1 = spans[0][1], max(s[1] + s[2] for s in trace["spans"])
    out = {"span": span, "count": len(spans), "window_s": (w1 - w0) * 1e-9,
           "busy_s": {}, "layer_s": {}, "top_ops": [], "idle_gaps": []}
    for dev, ops in sorted(trace["devices"].items()):
        inside = [o for o in ops if w0 <= o[1] < w1]
        out["busy_s"][dev] = _union(
            (o[1], min(o[1] + o[2], w1)) for o in inside) * 1e-9
        per = out["layer_s"][dev] = {}
        for name, _, dur in inside:
            lay = layer_of(name)
            per[lay] = per.get(lay, 0.0) + dur * 1e-9
    dev0 = min(trace["devices"], default=None)
    if dev0 is not None:
        ops = sorted((o for o in trace["devices"][dev0] if w0 <= o[1] < w1),
                     key=lambda o: o[1])
        by_op = {}
        for name, _, dur in ops:
            by_op[name] = by_op.get(name, 0.0) + dur * 1e-9
        out["top_ops"] = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        gaps, end = [], w0
        for _, s, d in ops + [[None, w1, 0]]:
            if s > end:
                gaps.append((end, s))
            end = max(end, s + d)
        host = [s for s in trace["spans"] if s[0] != span] + spans
        out["idle_gaps"] = [
            [_span_at(host, (a + b) / 2), (b - a) * 1e-9]
            for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]]
    return out


def _span_at(spans, t) -> str:
    """The innermost host span covering time ``t``."""
    best = None
    for name, s, d in spans:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "none"


def per_call_s(summary: dict, layer: str, first_only: bool = False):
    """Seconds of ``layer`` per traced call, averaged over the devices
    (``first_only``: device 0 alone); None where the window traced no
    call or no operation of that layer."""
    if not summary.get("count"):
        return None
    per_dev = [summary["layer_s"][d].get(layer, 0.0)
               for d in sorted(summary["layer_s"])]
    if first_only:
        per_dev = per_dev[:1]
    if not per_dev or not any(per_dev):
        return None
    return sum(per_dev) / len(per_dev) / summary["count"]


def idle_share(summary: dict, span: str):
    """Percent of the window in which the devices ran nothing, averaged
    over them; None where the window traced no ``span``."""
    if not summary.get("count") or summary.get("span") != span:
        return None
    busy = list(summary["busy_s"].values())
    return 100.0 * (1.0 - sum(busy) / len(busy) / summary["window_s"])
