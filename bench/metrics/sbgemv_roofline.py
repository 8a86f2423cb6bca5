"""Phase 3's share of its roofline: the least time the chip needs to move
the SBGEMV's bytes (both F_hat planes and the Fourier-space vectors,
computed from shapes) over the device time of the Phase-3
kernels per application."""

import cost
import tracing


def read(ctx):
    t = tracing.per_call_s(ctx["trace"], "phase3")
    if ctx["run"]["span"] != "apply" or t is None:
        return None
    cfg = ctx["cell"]["config"]
    shape = (cfg["N_t"], cfg["N_d"], cfg["N_m"])
    least, _ = cost.roofline_s(cost.phase3_flops(*shape),
                               cost.phase3_bytes(*shape, cfg["precision"]),
                               ctx["peak"])
    return 100.0 * least / t
