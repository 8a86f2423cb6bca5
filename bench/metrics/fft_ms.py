"""Device milliseconds of the FFT operations (HLO ``fft``) per
application, averaged over the cell's devices."""

import tracing


def read(ctx):
    t = tracing.per_call_s(ctx["trace"], "fft")
    return None if ctx["run"]["span"] != "apply" or t is None else t * 1e3
