"""Device milliseconds per application of every operation that is neither
the Phase-3 kernel, an FFT nor a collective: pad/cast, reorders, unpad
and copies, averaged over the cell's devices."""

import tracing


def read(ctx):
    t = tracing.per_call_s(ctx["trace"], "other")
    return None if ctx["run"]["span"] != "apply" or t is None else t * 1e3
