"""The program's own scopes and spans, read from a traced window.

``tracing.py`` sorts device operations by opcode, operand shape and JAX's
primitive names alone, and the six metrics it feeds (``sbgemv_roofline``,
``fft_ms``, ``other_ops_ms``, ``iters_per_solve``, ``idle_share.*``) still
do.  The four metrics this module feeds read what the program names
itself:

- stage scopes: the plan executor runs under ``jax.named_scope
  ("fftmatvec")`` and each stage under ``jax.named_scope(<kind>)``, so an
  op's ``op_name`` metadata reads ``.../fftmatvec/<kind>/...``, a Pallas
  kernel's too (``.../fftmatvec/gemv/sbgemv_n_complex/pallas_call``; the
  kernel's ``name`` is its instruction's name).  ``scope_of`` maps a
  device event to its stage kind (``unscoped_ms``).
- host spans: ``solvers/cg.pcg`` runs under ``pcg.solve`` and wraps each
  blocking host read of a device value in ``pcg.sync``
  (``jax.profiler.TraceAnnotation``, on the profiler's clock).  ``load``
  keeps them with their stats; ``idle_by_span`` splits device 0's idle
  time among the innermost host spans (``loop_idle_ms``,
  ``sync_idle_ms``, ``syncs_per_solve``).

``run.py`` hands a reader the reduced window (``ctx["trace"]``), not the
raw trace or the compiled programs.  ``window`` takes them from ``ctx``
where it holds ``trace_dir`` and ``hlo``, else from the frame of
``run.read_trace`` that built ``ctx`` (its ``trace_dir`` and
``traffic.hlo``).  A program without the scopes or spans reads None.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys

import tracing

STAGE_KINDS = ("pad", "fft", "reorder", "gemv", "ifft", "mask", "unpad",
               "psum", "gemv_psum")
# the executor's own scope: an op under it but in no stage (the stacking
# reshapes of a multi-RHS block) maps to it
EXECUTOR = "fftmatvec"
HOST_SPANS = ("pcg.solve", "pcg.sync")

_SCOPE = re.compile(r'op_name="[^"]*?/' + EXECUTOR + r'(?:/([^/"]+))?')


# ---------------------------------------------------------------------------
# HLO instructions -> stage kinds
# ---------------------------------------------------------------------------

def _own_scope(line: str) -> str | None:
    m = _SCOPE.search(line)
    if not m:
        return None
    return m.group(1) if m.group(1) in STAGE_KINDS else EXECUTOR


def has_scopes(hlo_texts) -> bool:
    """Whether the programs carry the executor's stage scopes at all (a
    program from before them does not)."""
    return any(_SCOPE.search(h) for h in hlo_texts)


def scope_of(hlo_texts):
    """A function from a device event to the stage kind that ran it, or
    None.

    An event is looked up by its instruction name in the given modules
    (on a TPU an event is named by its instruction's text, without its
    metadata).  The kind is the first scope under ``fftmatvec`` in the
    instruction's ``op_name``; for a fusion without one, that of the
    first scoped instruction it calls.  An event the modules do not hold
    is judged by its own text.  An op that XLA made (a relayout of an
    argument) or one outside the executor has no stage."""
    table = {}
    for hlo in hlo_texts:
        comps = tracing._computations(hlo)

        def scope(line, seen):
            found = _own_scope(line)
            for called in tracing._called(line) if found is None else ():
                if called not in seen:
                    seen.add(called)
                    found = next((k for _, _, inner in comps.get(called, ())
                                  if (k := scope(inner, seen))), None)
                    if found:
                        break
            return found

        for instrs in comps.values():
            for name, _, line in instrs:
                table[name] = scope(line, set())

    def kind_of(event: str) -> str | None:
        m = tracing._NAME.match(event)
        name = m.group(1) if m else event
        return table[name] if name in table else _own_scope(event)

    return kind_of


# ---------------------------------------------------------------------------
# the raw window
# ---------------------------------------------------------------------------

def load(trace_dir: str) -> dict:
    """{"devices": {id: [[op, start_ns, dur_ns], ...]}, "spans": [[name,
    start_ns, dur_ns, stats], ...]} from the one ``.xplane.pb`` under
    ``trace_dir``: the devices' operations as ``tracing.load`` reads them,
    and the host spans of the benchmark and of the program."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    names = set(tracing.SPANS) | set(HOST_SPANS)
    devices, spans = {}, []
    for plane in data.planes:
        m = tracing.DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = [
                [e.name, int(e.start_ns), int(e.duration_ns)]
                for line in plane.lines if line.name == tracing.OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            spans += [[e.name, int(e.start_ns), int(e.duration_ns),
                       dict(e.stats)]
                      for line in plane.lines for e in line.events
                      if e.name in names]
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "spans": spans}


def window(ctx):
    """(raw trace, HLO texts) of the window ``ctx`` reads, or None where
    they cannot be found.  Loaded once and kept in ``ctx``, which every
    reader of one window is given."""
    if "window" in ctx:
        return ctx["window"]
    if "trace_dir" in ctx:
        trace_dir, hlo = ctx["trace_dir"], ctx.get("hlo", [])
    else:
        frame = sys._getframe(1)
        while frame is not None and not (frame.f_locals.get("ctx") is ctx
                                         and "trace_dir" in frame.f_locals):
            frame = frame.f_back
        if frame is None:
            return None
        trace_dir = frame.f_locals["trace_dir"]
        hlo = getattr(frame.f_locals.get("traffic"), "hlo", [])
    ctx["window"] = (load(trace_dir), hlo)
    return ctx["window"]


# ---------------------------------------------------------------------------
# one window -> numbers
# ---------------------------------------------------------------------------

def _bounds(trace: dict, span: str):
    """The window of ``tracing.reduce``: from the first ``span`` to the end
    of the last of the benchmark's host spans; None without ``span``."""
    own = [s for s in trace["spans"] if s[0] in tracing.SPANS]
    first = [s for s in own if s[0] == span]
    if not first:
        return None
    return first[0][1], max(s[1] + s[2] for s in own)


def count(trace: dict, name: str, span: str) -> int:
    """Host spans ``name`` that start in the window of ``span``."""
    w = _bounds(trace, span)
    return 0 if w is None else sum(
        1 for s in trace["spans"] if s[0] == name and w[0] <= s[1] < w[1])


def _innermost(spans, w0: int, w1: int):
    """[(start, end, name)]: [w0, w1) cut at every span boundary, each
    piece named by the shortest host span covering it ("none" where none
    does).  Host spans of one thread nest, so the shortest is the
    innermost."""
    cuts = sorted({w0, w1} | {t for _, s, d, *_ in spans
                              for t in (s, s + d) if w0 < t < w1})
    pieces = []
    for a, b in zip(cuts, cuts[1:]):
        best = None
        for name, s, d, *_ in spans:
            if s <= a and b <= s + d and (best is None or d < best[1]):
                best = (name, d)
        pieces.append((a, b, best[0] if best else "none"))
    return pieces


def idle_by_span(trace: dict, span: str) -> dict | None:
    """{host span: seconds} of device 0's idle time in the window of
    ``span``, per innermost host span; None without ``span``.

    Idle is what ``tracing.reduce`` counts: the window less the union of
    the operations that start in it.  Each idle interval is split exactly
    at the host spans' boundaries, so the values sum to the window's idle
    time."""
    w = _bounds(trace, span)
    if w is None or not trace["devices"]:
        return None
    w0, w1 = w
    ops = sorted((o for o in trace["devices"][min(trace["devices"])]
                  if w0 <= o[1] < w1), key=lambda o: o[1])
    gaps, end = [], w0
    for _, s, d in ops + [[None, w1, 0]]:
        if s > end:
            gaps.append((end, s))
        end = max(end, s + d)
    spans = [s for s in trace["spans"] if s[1] < w1 and s[1] + s[2] > w0]
    pieces = _innermost(spans, w0, w1)
    starts = [p[0] for p in pieces]
    out: dict = {}
    for a, b in gaps:
        i = bisect.bisect_right(starts, a) - 1
        while i < len(pieces) and pieces[i][0] < b:
            lo, hi = max(a, pieces[i][0]), min(b, pieces[i][1])
            if hi > lo:
                name = pieces[i][2]
                out[name] = out.get(name, 0.0) + (hi - lo) * 1e-9
            i += 1
    return out


def unscoped_s(trace: dict, kind_of, span: str) -> float | None:
    """Device seconds per ``span`` call of the operations that start in
    its window and have no stage, averaged over the devices; None without
    ``span``."""
    w = _bounds(trace, span)
    if w is None or not trace["devices"]:
        return None
    calls = sum(1 for s in trace["spans"] if s[0] == span)
    per_dev = [sum(d for name, s, d in ops
                   if w[0] <= s < w[1] and kind_of(name) is None)
               for ops in trace["devices"].values()]
    return sum(per_dev) * 1e-9 / len(per_dev) / calls


def _program_window(ctx, name: str, span: str):
    """(raw trace, ``span`` calls) of the window ``ctx`` reads, where the
    run is of ``span`` and the program wrote ``name`` spans; else None."""
    found = window(ctx) if ctx["run"]["span"] == span else None
    if found is None or not count(found[0], name, span):
        return None
    trace = found[0]
    return trace, sum(1 for s in trace["spans"] if s[0] == span)


def spans_per_call(ctx, name: str, span: str) -> float | None:
    """The program's host spans ``name`` per ``span`` call; None in a cell
    of another span, or where the program wrote none."""
    found = _program_window(ctx, name, span)
    return None if found is None else count(found[0], name, span) / found[1]


def idle_ms_per_call(ctx, name: str, span: str) -> float | None:
    """Device 0's idle milliseconds per ``span`` call whose innermost host
    span is the program's ``name``; None in a cell of another span, where
    the program wrote no ``name`` span, or where no device was traced."""
    found = _program_window(ctx, name, span)
    idle = None if found is None else idle_by_span(found[0], span)
    return None if idle is None else idle.get(name, 0.0) * 1e3 / found[1]
