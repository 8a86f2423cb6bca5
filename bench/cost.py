"""Operations and bytes the algorithm needs, computed from shapes alone.

These are the yardstick's numbers, kept with the benchmark so that no
change to the program can move them: a roofline share is the least time
these bytes and operations take at the chip's published peaks, over the
device time the trace measured.
"""

from __future__ import annotations

# bytes per element of each rung of the precision ladder on the chip
LEVEL_BYTES = {"h": 2, "s": 4, "d": 8}


def phase3_bytes(N_t: int, N_d: int, N_m: int, precision: str) -> int:
    """HBM bytes one Phase-3 SBGEMV must move on one chip.

    Both F_hat planes (K x N_d x N_m, K = N_t + 1) and the Fourier-space
    vectors in and out (two planes each), all at the gemv level (the third
    letter of the precision string).
    """
    K = N_t + 1
    return 2 * K * (N_d * N_m + N_m + N_d) * LEVEL_BYTES[precision[2]]


def phase3_flops(N_t: int, N_d: int, N_m: int) -> int:
    """Real operations of one Phase-3 product: a complex multiply-add is 8
    of them, one per plane entry."""
    return 8 * (N_t + 1) * N_d * N_m


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple:
    """(least seconds, bound) at the chip's peaks: the larger of the
    compute time and the memory time, and which of the two it is."""
    t_flops = flops / peak["peak_flops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "flops")
