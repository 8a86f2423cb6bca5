"""Block-(lower)-triangular Toeplitz operators (paper §2.3-2.4).

The parameter-to-observable (p2o) map of a discretized linear autonomous
dynamical system is a block lower-triangular Toeplitz matrix

        [ F_1                 ]
    F = [ F_2  F_1            ]     F_k in R^{N_d x N_m}
        [ ...      ...        ]
        [ F_Nt ... F_2  F_1   ]

Only the first block column (N_t, N_d, N_m) is stored.  ``F`` embeds in a
block-circulant matrix of block dimension 2*N_t (zero padding of the first
block column), which the DFT block-diagonalizes: in Fourier space the p2o
matvec is a batched block-diagonal matvec (paper §2.4).

Layout convention (paper §C.1 "SOTI/TOSI"): time-domain block vectors are
carried *space-outer-time-inner* (SOTI) so the FFT runs over the minor
axis; Fourier-space data is *time(frequency)-outer-space-inner* (TOSI) so
the batched GEMV has the frequency batch major.  The SOTI<->TOSI reorders
are the paper's "purely memory" intermediate phases.

    m  : (N_m, N_t)   SOTI parameter vector
    d  : (N_d, N_t)   SOTI observable vector
    F_col: (N_t, N_d, N_m)  first block column (block index major)
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp


def dense_from_block_column(F_col: jax.Array) -> jax.Array:
    """Materialize the full (N_t*N_d, N_t*N_m) matrix.  Test-scale only."""
    N_t, N_d, N_m = F_col.shape
    zero = jnp.zeros_like(F_col[0])
    rows = []
    for i in range(N_t):
        blocks = [F_col[i - j] if i >= j else zero for j in range(N_t)]
        rows.append(jnp.concatenate(blocks, axis=1))
    return jnp.concatenate(rows, axis=0)


def dense_matvec(F_col: jax.Array, m_soti: jax.Array) -> jax.Array:
    """Reference O(N_t^2) matvec: d_i = sum_{j<=i} F_{i-j} m_j.  SOTI in/out."""
    N_t, N_d, N_m = F_col.shape
    m_blocks = m_soti.T  # (N_t, N_m), block index major
    out = []
    for i in range(N_t):
        acc = jnp.zeros((N_d,), dtype=jnp.result_type(F_col, m_soti))
        for j in range(i + 1):
            acc = acc + F_col[i - j] @ m_blocks[j]
        out.append(acc)
    return jnp.stack(out, axis=0).T  # (N_d, N_t) SOTI


def dense_rmatvec(F_col: jax.Array, d_soti: jax.Array) -> jax.Array:
    """Reference adjoint matvec m = F^T d (F_col is real).  SOTI in/out."""
    N_t, N_d, N_m = F_col.shape
    d_blocks = d_soti.T  # (N_t, N_d)
    out = []
    for j in range(N_t):
        acc = jnp.zeros((N_m,), dtype=jnp.result_type(F_col, d_soti))
        for i in range(j, N_t):
            acc = acc + F_col[i - j].T @ d_blocks[i]
        out.append(acc)
    return jnp.stack(out, axis=0).T  # (N_m, N_t)


# Elements of F_col per set-up chunk (128 MiB of f32): bounds the live
# padded chunk and its complex transform, whatever N_m is.
SETUP_CHUNK_ELEMS = 1 << 25


def fourier_block_column(F_col: jax.Array, dtype=None, *,
                         compute_dtype=None) -> tuple[jax.Array, jax.Array]:
    """Phase-0 setup: batched FFT of the zero-padded first block column.

    Computed at ``compute_dtype`` — by default the highest available
    precision: f64 with x64 on (the paper computes setup in FP64; on CPU
    that is reproduced exactly), f32 otherwise.  A backend without f64
    (a TPU) passes f32.

    The transform streams over chunks of N_m columns
    (:data:`SETUP_CHUNK_ELEMS` elements of F_col per chunk), each written
    straight into the stored planes: neither the whole
    padded column nor the whole complex F_hat is ever live, so the set-up
    peak is F_col plus the two output planes plus one chunk.  Columns are
    independent, so chunking does not change the result.

    Returns TOSI-layout split planes ``(F_hat_re, F_hat_im)`` each of shape
    (N_t + 1, N_d, N_m) in ``dtype`` (default: the compute dtype) — rfft
    of length 2*N_t keeps N_t+1 bins.
    """
    N_t, N_d, N_m = F_col.shape
    if compute_dtype is None:
        compute_dtype = jnp.float64 if jax.config.jax_enable_x64 \
            else jnp.float32
    compute = jax.dtypes.canonicalize_dtype(compute_dtype)
    out = jax.dtypes.canonicalize_dtype(
        compute if dtype is None else dtype)
    chunk = max(1, min(N_m, SETUP_CHUNK_ELEMS // (N_t * N_d)))
    return _fourier_planes(F_col, compute, out, chunk)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _fourier_planes(F_col, compute, out, chunk: int):
    N_t, N_d, N_m = F_col.shape

    def planes(cols):
        F_hat = jnp.fft.rfft(cols.astype(compute), n=2 * N_t, axis=0)
        return F_hat.real.astype(out), F_hat.imag.astype(out)

    def put(acc, start, cols):
        return tuple(jax.lax.dynamic_update_slice_in_dim(a, p, start, axis=2)
                     for a, p in zip(acc, planes(cols)))

    def body(i, acc):
        start = i * chunk
        return put(acc, start,
                   jax.lax.dynamic_slice_in_dim(F_col, start, chunk, axis=2))

    shape = (N_t + 1, N_d, N_m)
    acc = (jnp.zeros(shape, out), jnp.zeros(shape, out))
    n_full, tail = divmod(N_m, chunk)
    acc = jax.lax.fori_loop(0, n_full, body, acc)
    if tail:
        acc = put(acc, n_full * chunk, F_col[:, :, n_full * chunk:])
    return acc


# ---------------------------------------------------------------------------
# Operator construction helpers
# ---------------------------------------------------------------------------

def random_block_column(key, N_t: int, N_d: int, N_m: int, decay: float = 0.5,
                        dtype=jnp.float32) -> jax.Array:
    """Random p2o-like block column with geometrically decaying impulse
    response (physical p2o maps decay in time; keeps kappa(F_hat) moderate)."""
    blocks = jax.random.normal(key, (N_t, N_d, N_m), dtype=jnp.float32)
    scale = decay ** jnp.arange(N_t, dtype=jnp.float32)
    return (blocks * scale[:, None, None] / np.sqrt(N_m)).astype(dtype)


def random_unrepresentable(key, shape, scale: float = 1.0) -> jax.Array:
    """Random f64 values guaranteed to lose ~1/3 ulp(f32) when cast to f32.

    Reproduces the paper's §4.2.1 trick ("mantissa bits in positions
    greater than 23 set to one"): without it, a copy (pad/broadcast)
    executed in single precision would incur zero error and bias the
    Pareto analysis.  Note: literally setting ALL dropped bits to one puts
    the value 1 ulp(f64) below the next f32-representable number, so the
    cast is nearly lossless — we use an alternating 0101... pattern in the
    dropped 29 bits instead, which forces a genuine half-ulp(f32)-scale
    rounding error.  Requires x64.
    """
    if not jax.config.jax_enable_x64:
        raise RuntimeError("random_unrepresentable requires jax_enable_x64")
    x = jax.random.uniform(key, shape, dtype=jnp.float64, minval=0.5, maxval=1.0)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint64)
    # f64 has 52 mantissa bits; f32 keeps the top 23 -> bits 0..28 are lost.
    mask = jnp.uint64((1 << 29) - 1)
    pattern = jnp.uint64(0x0AAAAAAA)     # 0101... in the dropped bits
    bits = (bits & ~mask) | pattern
    out = jax.lax.bitcast_convert_type(bits, jnp.float64)
    return out * scale


def heat_equation_p2o(N_t: int, N_d: int, N_m: int, kappa: float = 0.05,
                      dt: float = 0.02, dtype=jnp.float64) -> jax.Array:
    """First block column of the p2o map of a 1-D periodic heat equation.

    du/dt = kappa u_xx + m(x, t), observed at N_d sensor locations — the
    paper's motivating LTI system class (§2.1).  Forward Euler on a periodic
    grid of N_m points; sensors sample the state.  The impulse response
    F_k = B A^{k-1} C dt gives the first block column.
    """
    if not jax.config.jax_enable_x64 and dtype == jnp.float64:
        dtype = jnp.float32
    n = N_m
    lam = kappa * dt * (n ** 2) / (2.0 * np.pi) ** 2
    # A = I + lam * (shift - 2I + shift^T) (periodic Laplacian), applied via roll
    def step(u):
        return u + lam * (jnp.roll(u, 1, axis=-1) - 2.0 * u + jnp.roll(u, -1, axis=-1))

    sensor_idx = np.linspace(0, n - 1, N_d).astype(np.int64)
    # impulse from every parameter point at once: u0 = I (n x n)
    u = jnp.eye(n, dtype=dtype) * dt
    cols = []
    for _ in range(N_t):
        cols.append(u[sensor_idx, :])  # (N_d, N_m): sensors x parameter-impulse
        u = step(u)
    return jnp.stack(cols, axis=0)  # (N_t, N_d, N_m)
