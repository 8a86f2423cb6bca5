"""The plain float64 host reference, independent of the program under test.

The block lower-triangular Toeplitz operator F, given its first block
column ``F`` (N_t, N_d, N_m) on the host, applied through float64 FFTs of
the zero-padded column (``HostOperator``): every time step of every
product, for the products and for the solves, whose iterates need F and
F* everywhere.

``HostOperator(F, precision=...)`` is the same operator with the rounding
points that a precision string of the paper's ladder states (section
"stated precision" below): float64 arithmetic, every stored intermediate
rounded to the rung its phase names.  A program at that configuration
differs from it by its own float32 arithmetic alone; one that rounds
anywhere else, or to a lower rung, differs by a whole rounding.

Vectors are SOTI blocks: m (N_m, N_t[, S]), d (N_d, N_t[, S]).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np
import scipy.fft

WORKERS = os.cpu_count() or 1

# ---------------------------------------------------------------------------
# stated precision
# ---------------------------------------------------------------------------

# the ladder on the chip, low to high, and what each rung stores
LADDER = ("h", "s", "d")
STORED = {"h": ml_dtypes.bfloat16, "s": np.float32, "d": None}


def lower(a: str, b: str) -> str:
    """The lower of two rungs: a memory-only reorder between two phases
    stores at it (the paper's rule)."""
    return min(a, b, key=LADDER.index)


def round_to(a, rung: str):
    """``a`` (float64 or complex128) rounded to what ``rung`` stores,
    kept in float64: real and imaginary parts apart, to nearest even."""
    dt = STORED[rung]
    if dt is None:
        return a
    if np.iscomplexobj(a):
        return round_to(a.real, rung) + 1j * round_to(a.imag, rung)
    return a.astype(dt).astype(np.float64)


def rel_err(got, ref) -> float:
    got = np.asarray(got, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


class HostOperator:
    """F and F* through float64 FFTs: pad the time axis to 2 N_t, multiply
    each frequency's (N_d x N_m) block, transform back and truncate.  The
    (N_t + 1, N_d, N_m) complex128 blocks are built once (8 GB at the
    paper's shape); each product is one pass over them, split over
    threads by frequency.

    ``precision``: a string of the ladder, one rung per phase (pad, FFT,
    SBGEMV, IFFT, unpad), for ``matvec`` at the rounding points it states:
    the padded input at the pad rung; the transform's output at the FFT
    rung and, reordered for Phase 3, at the lower of the FFT's and
    Phase 3's; the blocks, their product's input and its output at
    Phase 3's rung; reordered back at the lower of Phase 3's and the
    IFFT's; the inverse transform's output at the IFFT rung; the
    truncated answer at the unpad rung.  Every operation in between is
    float64.  ``None``: the exact operator."""

    def __init__(self, F, precision: str | None = None):
        N_t, N_d, N_m = F.shape
        self.N_t, self.n = N_t, 2 * N_t
        self.rungs = dict(zip(("pad", "fft", "gemv", "ifft", "unpad"),
                              precision)) if precision else None
        self.F_hat = np.empty((N_t + 1, N_d, N_m), np.complex128)
        chunk = max(1, (1 << 26) // (N_t * N_d))
        for j in range(0, N_m, chunk):
            self.F_hat[:, :, j:j + chunk] = scipy.fft.rfft(
                F[:, :, j:j + chunk].astype(np.float64), n=self.n, axis=0,
                workers=WORKERS)
        self.bins = np.array_split(np.arange(N_t + 1), 4 * WORKERS)
        if self.rungs:
            def store(s):
                self.F_hat[s] = round_to(self.F_hat[s], self.rungs["gemv"])
            self._each_bin(store)

    def _each_bin(self, fn):
        """``fn(slice of bins)`` for every bin, over threads."""
        def part(ks):
            if len(ks):
                fn(slice(ks[0], ks[-1] + 1))

        with ThreadPoolExecutor(WORKERS) as pool:
            list(pool.map(part, self.bins))

    def _per_bin(self, fn, like, rows):
        """``fn(slice of bins)`` for every bin, into an array shaped as
        ``like`` with ``rows`` rows per bin."""
        out = np.empty((like.shape[0], rows, like.shape[2]), np.complex128)

        def put(s):
            out[s] = fn(s)
        self._each_bin(put)
        return out

    def _at(self, a, phase: str, after: str | None = None):
        """``a`` as the stated precision stores it after ``phase`` (and,
        with ``after``, after the reorder into the next phase)."""
        if not self.rungs:
            return a
        a = round_to(a, self.rungs[phase])
        return a if after is None else round_to(
            a, lower(self.rungs[phase], self.rungs[after]))

    def matvec(self, m):
        """d = F m for m (N_m, N_t, S)."""
        m = self._at(np.asarray(m, np.float64), "pad")
        x_hat = scipy.fft.rfft(m, n=self.n, axis=1, workers=WORKERS)
        x_hat = self._at(self._at(x_hat, "fft", "gemv"), "gemv")
        x_hat = np.ascontiguousarray(x_hat.transpose(1, 0, 2))   # (K, N_m, S)
        y_hat = self._per_bin(lambda s: self.F_hat[s] @ x_hat[s], x_hat,
                              self.F_hat.shape[1])
        y_hat = self._at(y_hat, "gemv", "ifft")
        y = self._at(scipy.fft.irfft(y_hat, n=self.n, axis=0,
                                     workers=WORKERS), "ifft")
        return self._at(np.ascontiguousarray(
            y[:self.N_t].transpose(1, 0, 2)), "unpad")

    def rmatvec(self, d):
        """m = F* d for d (N_d, N_t, S): the circular correlation of the
        zero-padded d with the column, truncated (exact operator only)."""
        assert self.rungs is None, "F* is kept exact"
        d_hat = scipy.fft.rfft(d, n=self.n, axis=1, workers=WORKERS)
        d_hat = np.ascontiguousarray(d_hat.transpose(1, 0, 2).conj())
        m_hat = self._per_bin(
            lambda s: self.F_hat[s].transpose(0, 2, 1) @ d_hat[s], d_hat,
            self.F_hat.shape[2])
        m = scipy.fft.irfft(m_hat.conj(), n=self.n, axis=0, workers=WORKERS)
        return np.ascontiguousarray(m[:self.N_t].transpose(1, 0, 2))


def cgnr(op: HostOperator, d, iters: int):
    """CG on the normal equations F*F m = F* d from m = 0, in float64, one
    independent chain per column of d (N_d, N_t, S).

    Returns (iterates, relres): ``iterates[k]`` is the (N_m, N_t, S)
    iterate after k iterations and ``relres[k]`` its relative residual
    ||F*d - F*F m_k|| / ||F*d|| per column, the quantity the solver
    stops on, for k = 0..iters."""
    def norms(v):
        return np.sqrt(np.einsum("itc,itc->c", v, v))

    b = op.rmatvec(d)
    b_norm = norms(b)
    x = np.zeros_like(b)
    r = b.copy()
    p = r.copy()
    rho = norms(r) ** 2
    iterates, relres = [x.copy()], [norms(r) / b_norm]
    for _ in range(iters):
        q = op.rmatvec(op.matvec(p))
        # a column solved to round-off stops moving (0/0 would follow)
        alpha = np.where(relres[-1] > 1e-14,
                         rho / np.einsum("itc,itc->c", p, q), 0.0)
        x += p * alpha
        r -= q * alpha
        rho_new = norms(r) ** 2
        iterates.append(x.copy())
        relres.append(np.sqrt(rho_new) / b_norm)
        p = r + p * np.where(relres[-1] > 1e-14, rho_new / rho, 0.0)
        rho = rho_new
    return iterates, np.asarray(relres)
