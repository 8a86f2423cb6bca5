"""Pallas TPU kernel: strided-batched GEMV for short-wide matrices (paper C2).

The paper's rocBLAS pathology: for batches of (m x n) matrices with
m << n (N_d sensors << N_m parameters), the stock conjugate-transpose
SBGEMV launches one gridblock per output element — n tiny blocks each
doing a length-m dot product — destroying memory bandwidth.  Their fix
tiles the *columns* of each matrix so a block computes a chunk of outputs,
with vectorized loads, read/compute/write pipelining and warp-shuffle
reductions.

TPU adaptation (DESIGN.md §2.3): the failure mode on TPU is lane/sublane
alignment rather than launch overhead, but the *insight* carries over —
tile the long n axis, keep a whole (m x block_n) tile of A resident in
VMEM, reduce inside fast memory, and pipeline HBM->VMEM loads against MXU
compute (Pallas double-buffers grid steps automatically; batch and column
grid axes are marked ``parallel``).  Complex data is carried as split
re/im planes (no complex dtype on the MXU): each A tile is loaded ONCE
and used for both the real and imaginary outputs — halving matrix traffic
vs. four independent real GEMVs, which is the kernel's bandwidth win.

All kernels accumulate in f32 (``preferred_element_type``) regardless of
the plane dtype (bf16/f32), and f32 planes contract at full f32
precision (see :func:`_dg`).  A planes are taken as they are stored, never
padded: the short m axis is one whole block (a block dim equal to the
array dim is always legal) and the long n axis runs ``pl.cdiv(n, bn)``
tiles, the last one partial (see :func:`_ragged`).  Padding F_hat to the
tile grid would copy the whole operator on every call.  Vectors are
carried as (B, 1, ·) so their blocks are whole (1, ·) minor dims.
Wrappers in ``ops.py`` pad only the RHS axis and cast the outputs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ACC = jnp.float32


def _dg(a, b, dims):
    """``dot_general`` accumulating in f32.  f32 operands contract at full
    f32 precision: the MXU's default for f32 is one bf16 pass, which would
    make the "s" rung a bf16 one.  bf16 operands are exact in one pass."""
    prec = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(a, b, dims, precision=prec,
                               preferred_element_type=_ACC)


def _dot(a, b):
    """(p, k) x (k, q) -> (p, q)."""
    return _dg(a, b, (((1,), (0,)), ((), ())))


def _dg_nt(a, b):
    """Contract minor axes: (p, k) x (q, k) -> (p, q)."""
    return _dg(a, b, (((1,), (1,)), ((), ())))


def _whole_tiles(x, bn: int):
    """An N-mode input vector (B, n) as (B, 1, cdiv(n, bn) * bn), zero-
    padded.  The vector is small; zeros past n (with :func:`_ragged`
    zeroing the operator's edge tile) keep the last tile's product exact.
    Masking the (1, bn) bf16 vector in the kernel instead is a sublane
    broadcast Mosaic does not implement."""
    pad = (-x.shape[1]) % bn
    return jnp.pad(x, ((0, 0), (0, pad)))[:, None]


def _ragged(j, n: int, tile, axis: int):
    """Zero the entries of an n-axis tile that lie past the array's ``n``.

    The grids run ``pl.cdiv(n, bn)`` column tiles, so when ``bn`` does not
    divide ``n`` the last tile reads unspecified values past the edge.
    Where those columns only produce outputs past the edge (the T/H
    modes), the partial write drops them; where the contraction runs
    over them (the N mode, and the Gram's row tiles over m) they are
    masked here.  ``j`` is the tile index and ``axis`` the tiled axis."""
    bn = tile.shape[axis]
    if n % bn == 0:
        return tile
    idx = j * bn + jax.lax.broadcasted_iota(jnp.int32, tile.shape, axis)
    return jnp.where(idx < n, tile, jnp.zeros_like(tile))


# ---------------------------------------------------------------------------
# Transpose / conjugate-transpose, complex: y = A^T x or A^H x
#   A planes: (B, m, n), x planes: (B, m)  ->  y planes: (B, n) in f32.
# Grid (B, n_tiles): every step writes a distinct output tile (parallel).
# ---------------------------------------------------------------------------

def _sbgemv_th_complex_kernel(conj: bool, Ar_ref, Ai_ref, xr_ref, xi_ref,
                              yr_ref, yi_ref):
    Ar = Ar_ref[0]                      # (m, bn)
    Ai = Ai_ref[0]
    xr = xr_ref[0]                      # (1, m)
    xi = xi_ref[0]
    rr = _dot(xr, Ar)                   # (1, bn) — MXU matmul
    ii = _dot(xi, Ai)
    ri = _dot(xr, Ai)
    ir = _dot(xi, Ar)
    if conj:   # y = conj(A)^T x
        yr_ref[0] = rr + ii
        yi_ref[0] = ir - ri
    else:      # y = A^T x
        yr_ref[0] = rr - ii
        yi_ref[0] = ir + ri


def sbgemv_th_complex(A_re, A_im, x_re, x_im, *, conj: bool,
                      block_n: int = 512, interpret: bool = False):
    """(Conjugate-)transpose batched complex GEMV.  Any m, n.  Returns (y_re, y_im) f32 of shape (B, n)."""
    B, m, n = A_re.shape
    assert x_re.shape == (B, m)
    grid = (B, pl.cdiv(n, block_n))
    spec_A = pl.BlockSpec((1, m, block_n), lambda b, j: (b, 0, j))
    spec_x = pl.BlockSpec((1, 1, m), lambda b, j: (b, 0, 0))
    spec_y = pl.BlockSpec((1, 1, block_n), lambda b, j: (b, 0, j))
    out = jax.ShapeDtypeStruct((B, 1, n), _ACC)
    y_re, y_im = pl.pallas_call(
        functools.partial(_sbgemv_th_complex_kernel, conj),
        grid=grid,
        in_specs=[spec_A, spec_A, spec_x, spec_x],
        out_specs=[spec_y, spec_y],
        out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="sbgemv_th_complex",
    )(A_re, A_im, x_re[:, None], x_im[:, None])
    return y_re[:, 0], y_im[:, 0]


# ---------------------------------------------------------------------------
# Non-transpose, complex: y = A x
#   A planes: (B, m, n), x planes: (B, n)  ->  y planes: (B, m) in f32.
# Grid (B, n_tiles): column tiles accumulate into the same output block, so
# the j axis is a reduction ("arbitrary") and is innermost.
# ---------------------------------------------------------------------------

def _sbgemv_n_complex_kernel(n: int, Ar_ref, Ai_ref, xr_ref, xi_ref, yr_ref,
                             yi_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        yr_ref[...] = jnp.zeros_like(yr_ref)
        yi_ref[...] = jnp.zeros_like(yi_ref)

    Ar, Ai = (_ragged(j, n, r[0], 1) for r in (Ar_ref, Ai_ref))  # (m, bn)
    xr = xr_ref[0]                      # (1, bn), zero-padded by the caller
    xi = xi_ref[0]
    # contract over the bn axis: (1, bn) x (m, bn) -> (1, m)
    rr = _dg_nt(xr, Ar)
    ii = _dg_nt(xi, Ai)
    ri = _dg_nt(xr, Ai)
    ir = _dg_nt(xi, Ar)
    yr_ref[0] += rr - ii
    yi_ref[0] += ir + ri


def sbgemv_n_complex(A_re, A_im, x_re, x_im, *, block_n: int = 512,
                     interpret: bool = False):
    """Non-transpose batched complex GEMV.  Any m, n.  Returns (y_re, y_im) f32 of shape (B, m)."""
    B, m, n = A_re.shape
    assert x_re.shape == (B, n)
    grid = (B, pl.cdiv(n, block_n))
    spec_A = pl.BlockSpec((1, m, block_n), lambda b, j: (b, 0, j))
    spec_x = pl.BlockSpec((1, 1, block_n), lambda b, j: (b, 0, j))
    spec_y = pl.BlockSpec((1, 1, m), lambda b, j: (b, 0, 0))
    out = jax.ShapeDtypeStruct((B, 1, m), _ACC)
    y_re, y_im = pl.pallas_call(
        functools.partial(_sbgemv_n_complex_kernel, n),
        grid=grid,
        in_specs=[spec_A, spec_A, spec_x, spec_x],
        out_specs=[spec_y, spec_y],
        out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="sbgemv_n_complex",
    )(A_re, A_im, *(_whole_tiles(x, block_n) for x in (x_re, x_im)))
    return y_re[:, 0], y_im[:, 0]


# ---------------------------------------------------------------------------
# Real variants (the paper ships real s/d kernels too — Fig. 1 benchmarks
# both real and complex datatypes).
# ---------------------------------------------------------------------------

def _sbgemv_th_real_kernel(A_ref, x_ref, y_ref):
    y_ref[0] = _dot(x_ref[0], A_ref[0])


def sbgemv_th_real(A, x, *, block_n: int = 512, interpret: bool = False):
    """y = A^T x, real.  A (B, m, n), x (B, m) -> y (B, n) f32."""
    B, m, n = A.shape
    assert x.shape == (B, m)
    grid = (B, pl.cdiv(n, block_n))
    return pl.pallas_call(
        _sbgemv_th_real_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, m, block_n), lambda b, j: (b, 0, j)),
                  pl.BlockSpec((1, 1, m), lambda b, j: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, block_n), lambda b, j: (b, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, 1, n), _ACC),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="sbgemv_th_real",
    )(A, x[:, None])[:, 0]


def _sbgemv_n_real_kernel(n: int, A_ref, x_ref, y_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    y_ref[0] += _dg_nt(x_ref[0], _ragged(j, n, A_ref[0], 1))


def sbgemv_n_real(A, x, *, block_n: int = 512, interpret: bool = False):
    """y = A x, real.  A (B, m, n), x (B, n) -> y (B, m) f32."""
    B, m, n = A.shape
    assert x.shape == (B, n)
    grid = (B, pl.cdiv(n, block_n))
    return pl.pallas_call(
        functools.partial(_sbgemv_n_real_kernel, n),
        grid=grid,
        in_specs=[pl.BlockSpec((1, m, block_n), lambda b, j: (b, 0, j)),
                  pl.BlockSpec((1, 1, block_n), lambda b, j: (b, 0, j))],
        out_specs=pl.BlockSpec((1, 1, m), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, m), _ACC),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="sbgemv_n_real",
    )(A, _whole_tiles(x, block_n))[:, 0]


# ===========================================================================
# Multi-RHS (block) variants: SBGEMM.
#
# Batching S right-hand sides turns the bandwidth-bound SBGEMV into an
# MXU-friendly SBGEMM: each (m x block_n) A tile is loaded from HBM once
# and contracted against an S-column panel, so matrix traffic amortizes
# over S outputs (arithmetic intensity grows ~linearly in S until the MXU
# saturates).  Both the long n axis AND the RHS axis are tiled; grids mark
# independent output tiles ``parallel`` and keep the contraction axis
# innermost (``arbitrary``).  Accumulation stays f32.
# ===========================================================================


def _dg_t(a, b):
    """Contract leading axes: (m, p) x (m, q) -> (p, q)."""
    return _dg(a, b, (((0,), (0,)), ((), ())))


# ---------------------------------------------------------------------------
# Transpose / conjugate-transpose, complex: Y = A^T X or A^H X
#   A planes: (B, m, n), X planes: (B, m, S)  ->  Y planes: (B, n, S) f32.
# Grid (B, n_tiles, s_tiles): every step writes a distinct output tile.
# ---------------------------------------------------------------------------

def _sbgemm_th_complex_kernel(conj: bool, Ar_ref, Ai_ref, Xr_ref, Xi_ref,
                              Yr_ref, Yi_ref):
    Ar = Ar_ref[0]                      # (m, bn)
    Ai = Ai_ref[0]
    Xr = Xr_ref[0]                      # (m, bs)
    Xi = Xi_ref[0]
    rr = _dg_t(Ar, Xr)                  # (bn, bs)
    ii = _dg_t(Ai, Xi)
    ri = _dg_t(Ai, Xr)
    ir = _dg_t(Ar, Xi)
    if conj:   # Y = conj(A)^T X
        Yr_ref[0] = rr + ii
        Yi_ref[0] = ir - ri
    else:      # Y = A^T X
        Yr_ref[0] = rr - ii
        Yi_ref[0] = ir + ri


def sbgemm_th_complex(A_re, A_im, X_re, X_im, *, conj: bool,
                      block_n: int = 512, block_s: int = 128,
                      interpret: bool = False):
    """(Conjugate-)transpose batched complex GEMM.  Any m, n;
    S % block_s == 0.  Returns (Y_re, Y_im)
    f32 of shape (B, n, S)."""
    B, m, n = A_re.shape
    S = X_re.shape[2]
    assert S % block_s == 0 and X_re.shape == (B, m, S)
    grid = (B, pl.cdiv(n, block_n), S // block_s)
    spec_A = pl.BlockSpec((1, m, block_n), lambda b, j, s: (b, 0, j))
    spec_X = pl.BlockSpec((1, m, block_s), lambda b, j, s: (b, 0, s))
    spec_Y = pl.BlockSpec((1, block_n, block_s), lambda b, j, s: (b, j, s))
    out = jax.ShapeDtypeStruct((B, n, S), _ACC)
    return pl.pallas_call(
        functools.partial(_sbgemm_th_complex_kernel, conj),
        grid=grid,
        in_specs=[spec_A, spec_A, spec_X, spec_X],
        out_specs=[spec_Y, spec_Y],
        out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="sbgemm_th_complex",
    )(A_re, A_im, X_re, X_im)


# ---------------------------------------------------------------------------
# Non-transpose, complex: Y = A X
#   A planes: (B, m, n), X planes: (B, n, S)  ->  Y planes: (B, m, S) f32.
# Grid (B, s_tiles, n_tiles): column tiles accumulate into the same output
# block, so the n axis is a reduction ("arbitrary") and is innermost.
# ---------------------------------------------------------------------------

def _sbgemm_n_complex_kernel(n: int, Ar_ref, Ai_ref, Xr_ref, Xi_ref, Yr_ref,
                             Yi_ref):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        Yr_ref[...] = jnp.zeros_like(Yr_ref)
        Yi_ref[...] = jnp.zeros_like(Yi_ref)

    Ar, Ai = (_ragged(j, n, r[0], 1) for r in (Ar_ref, Ai_ref))  # (m, bn)
    Xr, Xi = (_ragged(j, n, r[0], 0) for r in (Xr_ref, Xi_ref))  # (bn, bs)
    rr = _dot(Ar, Xr)                   # (m, bs)
    ii = _dot(Ai, Xi)
    ri = _dot(Ai, Xr)
    ir = _dot(Ar, Xi)
    Yr_ref[0] += rr - ii
    Yi_ref[0] += ir + ri


def sbgemm_n_complex(A_re, A_im, X_re, X_im, *, block_n: int = 512,
                     block_s: int = 128, interpret: bool = False):
    """Non-transpose batched complex GEMM.  Any m, n; S % block_s == 0.  Returns (Y_re, Y_im) f32 of shape (B, m, S)."""
    B, m, n = A_re.shape
    S = X_re.shape[2]
    assert S % block_s == 0 and X_re.shape == (B, n, S)
    grid = (B, S // block_s, pl.cdiv(n, block_n))
    spec_A = pl.BlockSpec((1, m, block_n), lambda b, s, j: (b, 0, j))
    spec_X = pl.BlockSpec((1, block_n, block_s), lambda b, s, j: (b, j, s))
    spec_Y = pl.BlockSpec((1, m, block_s), lambda b, s, j: (b, 0, s))
    out = jax.ShapeDtypeStruct((B, m, S), _ACC)
    return pl.pallas_call(
        functools.partial(_sbgemm_n_complex_kernel, n),
        grid=grid,
        in_specs=[spec_A, spec_A, spec_X, spec_X],
        out_specs=[spec_Y, spec_Y],
        out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="sbgemm_n_complex",
    )(A_re, A_im, X_re, X_im)


# ---------------------------------------------------------------------------
# Per-bin Gram blocks: G = A^H A  (the Fourier-domain Hessian setup).
#   A planes: (B, m, n)  ->  G planes: (B, n, n) in f32.
# Grid (B, i_tiles, j_tiles, m_tiles): every (b, i, j) owns a distinct
# (bi x bj) output tile built from TWO column tiles of A; the contraction
# over m is the innermost ("arbitrary") axis, one row tile of at most
# GRAM_BLOCK_M rows at a time, so the resident tiles fit VMEM whatever m is
# (the data-space Gram at the paper shape contracts over N_m = 5000).
# Hermitian-aware: each A tile pair is loaded once and serves both the real
# and imaginary output planes (the same single-read traffic trick as the
# GEMV kernels), and the strictly conjugate-symmetric structure
# (G == conj(G)^T) is enforced exactly by the ops-layer wrapper, which also
# derives the data-space twin A A^H from this kernel on the
# conjugate-transposed planes.
# ---------------------------------------------------------------------------

GRAM_BLOCK_M = 512


def _gram_block_m(m: int) -> int:
    """Rows per contraction step: all of m when it fits one block (a block
    dim equal to the array dim is always legal), else ``GRAM_BLOCK_M``."""
    return min(m, GRAM_BLOCK_M)


def _gram_rows(k, m: int, *tiles):
    """Zero the rows of the k-th row tiles that lie past the array's m:
    the last row tile is partial when its size does not divide m."""
    return [_ragged(k, m, t, 0) for t in tiles]


def _sbgemm_gram_kernel(m: int, Ari_ref, Arj_ref, Aii_ref, Aij_ref, Gr_ref,
                        Gi_ref):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        Gr_ref[...] = jnp.zeros_like(Gr_ref)
        Gi_ref[...] = jnp.zeros_like(Gi_ref)

    # (bm, bi) / (bm, bj) row tiles of the column tiles i and j
    Ari, Arj, Aii, Aij = _gram_rows(k, m, Ari_ref[0], Arj_ref[0],
                                    Aii_ref[0], Aij_ref[0])
    # G = (Ar - i Ai)^T (Ar + i Ai), contracted over the m axis
    Gr_ref[0] += _dg_t(Ari, Arj) + _dg_t(Aii, Aij)
    Gi_ref[0] += _dg_t(Ari, Aij) - _dg_t(Aii, Arj)


def sbgemm_gram_complex(A_re, A_im, *, block_n: int = 512,
                        interpret: bool = False):
    """Per-batch Gram blocks G = A^H A on split planes.  Any m, n (edge
    tiles past n only reach outputs past n, which are dropped; rows past m
    in the last row tile are zeroed).  Returns (G_re, G_im) f32 of shape
    (B, n, n)."""
    B, m, n = A_re.shape
    bm = _gram_block_m(m)
    grid = (B, pl.cdiv(n, block_n), pl.cdiv(n, block_n), pl.cdiv(m, bm))
    spec_i = pl.BlockSpec((1, bm, block_n), lambda b, i, j, k: (b, k, i))
    spec_j = pl.BlockSpec((1, bm, block_n), lambda b, i, j, k: (b, k, j))
    spec_G = pl.BlockSpec((1, block_n, block_n),
                          lambda b, i, j, k: (b, i, j))
    out = jax.ShapeDtypeStruct((B, n, n), _ACC)
    return pl.pallas_call(
        functools.partial(_sbgemm_gram_kernel, m),
        grid=grid,
        in_specs=[spec_i, spec_j, spec_i, spec_j],
        out_specs=[spec_G, spec_G],
        out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="sbgemm_gram_complex",
    )(A_re, A_re, A_im, A_im)


# ---------------------------------------------------------------------------
# Real variants
# ---------------------------------------------------------------------------

def _sbgemm_th_real_kernel(A_ref, X_ref, Y_ref):
    Y_ref[0] = _dg_t(A_ref[0], X_ref[0])


def sbgemm_th_real(A, X, *, block_n: int = 512, block_s: int = 128,
                   interpret: bool = False):
    """Y = A^T X, real.  A (B, m, n), X (B, m, S) -> Y (B, n, S) f32."""
    B, m, n = A.shape
    S = X.shape[2]
    assert S % block_s == 0 and X.shape == (B, m, S)
    grid = (B, pl.cdiv(n, block_n), S // block_s)
    return pl.pallas_call(
        _sbgemm_th_real_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, m, block_n), lambda b, j, s: (b, 0, j)),
                  pl.BlockSpec((1, m, block_s), lambda b, j, s: (b, 0, s))],
        out_specs=pl.BlockSpec((1, block_n, block_s),
                               lambda b, j, s: (b, j, s)),
        out_shape=jax.ShapeDtypeStruct((B, n, S), _ACC),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="sbgemm_th_real",
    )(A, X)


def _sbgemm_n_real_kernel(n: int, A_ref, X_ref, Y_ref):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        Y_ref[...] = jnp.zeros_like(Y_ref)

    Y_ref[0] += _dot(_ragged(j, n, A_ref[0], 1), _ragged(j, n, X_ref[0], 0))


def sbgemm_n_real(A, X, *, block_n: int = 512, block_s: int = 128,
                  interpret: bool = False):
    """Y = A X, real.  A (B, m, n), X (B, n, S) -> Y (B, m, S) f32."""
    B, m, n = A.shape
    S = X.shape[2]
    assert S % block_s == 0 and X.shape == (B, n, S)
    grid = (B, S // block_s, pl.cdiv(n, block_n))
    return pl.pallas_call(
        functools.partial(_sbgemm_n_real_kernel, n),
        grid=grid,
        in_specs=[pl.BlockSpec((1, m, block_n), lambda b, s, j: (b, 0, j)),
                  pl.BlockSpec((1, block_n, block_s), lambda b, s, j: (b, j, s))],
        out_specs=pl.BlockSpec((1, m, block_s), lambda b, s, j: (b, 0, s)),
        out_shape=jax.ShapeDtypeStruct((B, m, S), _ACC),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="sbgemm_n_real",
    )(A, X)


# ===========================================================================
# Tile-centric mixed precision (DESIGN.md §8).
#
# Tiled variants take an extra int32 ``lvl`` array of shape (B, n_tiles) —
# one ladder index (h=0, s=1, d=2) per (batch row, column kernel-tile),
# derived from per-block norms of F_hat (tune/tile_map.py).  Each kernel
# step reads its tile's scalar level from a (1, 1) block and round-trips
# the resident A tile through that storage dtype *in VMEM* before the MXU
# contraction; X and the accumulator stay in the carrier dtype, so the MXU
# datapath and output tiling are identical to the untiled kernels — only
# the operand mantissas shrink.  The quantization is a branch-free
# where-select over the (at most two) lossy round-trips, matching the
# kernels/ref.py element-wise oracle bit-exactly whenever the kernel tile
# grid aligns with the tile-map cells (the ops layer checks alignment and
# falls back to element-wise pre-quantization otherwise).
# ===========================================================================


def _lvl_tiles(lvl):
    """(B, n_tiles) level array -> (B, n_tiles, 1, 1): each kernel step
    reads its own level as a (1, 1) block, which Mosaic accepts only as
    the full minor dims of the array."""
    return lvl.reshape(lvl.shape + (1, 1))


def _lvl_spec(index_map):
    """Block spec of one tile's level in :func:`_lvl_tiles` layout; the
    kernel sees a (1, 1) int32 block that broadcasts against the tile."""
    return pl.BlockSpec((None, None, 1, 1),
                        lambda *g: index_map(*g) + (0, 0))


def _tile_quantize(lvl, *planes):
    """Round-trip carrier-dtype planes through the storage dtype selected
    by the scalar ladder index ``lvl`` (h=0, s=1, d=2).  Round-trips at or
    above the carrier are the identity (nested mantissas), so the d-branch
    passes through untouched."""
    outs = []
    for A in planes:
        q_h = A.astype(jnp.bfloat16).astype(A.dtype)
        q_s = A.astype(jnp.float32).astype(A.dtype)
        outs.append(jnp.where(lvl == 0, q_h, jnp.where(lvl == 1, q_s, A)))
    return outs


def _sbgemm_th_complex_tiled_kernel(conj: bool, lvl_ref, Ar_ref, Ai_ref,
                                    Xr_ref, Xi_ref, Yr_ref, Yi_ref):
    lvl = lvl_ref[...]
    Ar, Ai = _tile_quantize(lvl, Ar_ref[0], Ai_ref[0])
    Xr = Xr_ref[0]                      # (m, bs) — carrier, never quantized
    Xi = Xi_ref[0]
    rr = _dg_t(Ar, Xr)                  # (bn, bs)
    ii = _dg_t(Ai, Xi)
    ri = _dg_t(Ai, Xr)
    ir = _dg_t(Ar, Xi)
    if conj:
        Yr_ref[0] = rr + ii
        Yi_ref[0] = ir - ri
    else:
        Yr_ref[0] = rr - ii
        Yi_ref[0] = ir + ri


def sbgemm_th_complex_tiled(A_re, A_im, X_re, X_im, lvl, *, conj: bool,
                            block_n: int = 512, block_s: int = 128,
                            interpret: bool = False):
    """Tile-quantized (conjugate-)transpose batched complex GEMM.  ``lvl``
    int32 (B, cdiv(n, block_n)).  Shapes as :func:`sbgemm_th_complex`."""
    B, m, n = A_re.shape
    S = X_re.shape[2]
    assert S % block_s == 0 and X_re.shape == (B, m, S)
    assert lvl.shape == (B, pl.cdiv(n, block_n))
    grid = (B, pl.cdiv(n, block_n), S // block_s)
    spec_lvl = _lvl_spec(lambda b, j, s: (b, j))
    spec_A = pl.BlockSpec((1, m, block_n), lambda b, j, s: (b, 0, j))
    spec_X = pl.BlockSpec((1, m, block_s), lambda b, j, s: (b, 0, s))
    spec_Y = pl.BlockSpec((1, block_n, block_s), lambda b, j, s: (b, j, s))
    out = jax.ShapeDtypeStruct((B, n, S), _ACC)
    return pl.pallas_call(
        functools.partial(_sbgemm_th_complex_tiled_kernel, conj),
        grid=grid,
        in_specs=[spec_lvl, spec_A, spec_A, spec_X, spec_X],
        out_specs=[spec_Y, spec_Y],
        out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="sbgemm_th_complex_tiled",
    )(_lvl_tiles(lvl), A_re, A_im, X_re, X_im)


def _sbgemm_n_complex_tiled_kernel(n: int, lvl_ref, Ar_ref, Ai_ref, Xr_ref,
                                   Xi_ref, Yr_ref, Yi_ref):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        Yr_ref[...] = jnp.zeros_like(Yr_ref)
        Yi_ref[...] = jnp.zeros_like(Yi_ref)

    Ar, Ai = _tile_quantize(lvl_ref[...], *(_ragged(j, n, r[0], 1)
                                            for r in (Ar_ref, Ai_ref)))
    Xr, Xi = (_ragged(j, n, r[0], 0) for r in (Xr_ref, Xi_ref))  # (bn, bs)
    rr = _dot(Ar, Xr)                   # (m, bs)
    ii = _dot(Ai, Xi)
    ri = _dot(Ai, Xr)
    ir = _dot(Ar, Xi)
    Yr_ref[0] += rr - ii
    Yi_ref[0] += ir + ri


def sbgemm_n_complex_tiled(A_re, A_im, X_re, X_im, lvl, *,
                           block_n: int = 512, block_s: int = 128,
                           interpret: bool = False):
    """Tile-quantized non-transpose batched complex GEMM.  ``lvl`` int32
    (B, cdiv(n, block_n)).  Shapes as :func:`sbgemm_n_complex`."""
    B, m, n = A_re.shape
    S = X_re.shape[2]
    assert S % block_s == 0 and X_re.shape == (B, n, S)
    assert lvl.shape == (B, pl.cdiv(n, block_n))
    grid = (B, S // block_s, pl.cdiv(n, block_n))
    spec_lvl = _lvl_spec(lambda b, s, j: (b, j))
    spec_A = pl.BlockSpec((1, m, block_n), lambda b, s, j: (b, 0, j))
    spec_X = pl.BlockSpec((1, block_n, block_s), lambda b, s, j: (b, j, s))
    spec_Y = pl.BlockSpec((1, m, block_s), lambda b, s, j: (b, 0, s))
    out = jax.ShapeDtypeStruct((B, m, S), _ACC)
    return pl.pallas_call(
        functools.partial(_sbgemm_n_complex_tiled_kernel, n),
        grid=grid,
        in_specs=[spec_lvl, spec_A, spec_A, spec_X, spec_X],
        out_specs=[spec_Y, spec_Y],
        out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="sbgemm_n_complex_tiled",
    )(_lvl_tiles(lvl), A_re, A_im, X_re, X_im)


def _sbgemm_gram_tiled_kernel(m: int, lvli_ref, lvlj_ref, Ari_ref, Arj_ref,
                              Aii_ref, Aij_ref, Gr_ref, Gi_ref):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        Gr_ref[...] = jnp.zeros_like(Gr_ref)
        Gi_ref[...] = jnp.zeros_like(Gi_ref)

    # The i and j column tiles may sit in different map cells: quantize
    # each side at its own level, exactly as the oracle quantizes A once
    # and then forms A^H A.
    Ari, Arj, Aii, Aij = _gram_rows(k, m, Ari_ref[0], Arj_ref[0],
                                    Aii_ref[0], Aij_ref[0])
    Ari, Aii = _tile_quantize(lvli_ref[0, 0], Ari, Aii)
    Arj, Aij = _tile_quantize(lvlj_ref[0, 0], Arj, Aij)
    Gr_ref[0] += _dg_t(Ari, Arj) + _dg_t(Aii, Aij)
    Gi_ref[0] += _dg_t(Ari, Aij) - _dg_t(Aii, Arj)


def sbgemm_gram_tiled(A_re, A_im, lvl, *, block_n: int = 512,
                      interpret: bool = False):
    """Tile-quantized per-batch Gram blocks G = A^H A.  ``lvl`` int32
    (B, cdiv(n, block_n)); both passes read the same quantized operand."""
    B, m, n = A_re.shape
    assert lvl.shape == (B, pl.cdiv(n, block_n))
    bm = _gram_block_m(m)
    grid = (B, pl.cdiv(n, block_n), pl.cdiv(n, block_n), pl.cdiv(m, bm))
    spec_li = _lvl_spec(lambda b, i, j, k: (b, i))
    spec_lj = _lvl_spec(lambda b, i, j, k: (b, j))
    spec_i = pl.BlockSpec((1, bm, block_n), lambda b, i, j, k: (b, k, i))
    spec_j = pl.BlockSpec((1, bm, block_n), lambda b, i, j, k: (b, k, j))
    spec_G = pl.BlockSpec((1, block_n, block_n),
                          lambda b, i, j, k: (b, i, j))
    out = jax.ShapeDtypeStruct((B, n, n), _ACC)
    return pl.pallas_call(
        functools.partial(_sbgemm_gram_tiled_kernel, m),
        grid=grid,
        in_specs=[spec_li, spec_lj, spec_i, spec_j, spec_i, spec_j],
        out_specs=[spec_G, spec_G],
        out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="sbgemm_gram_tiled",
    )(_lvl_tiles(lvl), _lvl_tiles(lvl), A_re, A_re, A_im, A_im)


def _sbgemm_th_real_tiled_kernel(lvl_ref, A_ref, X_ref, Y_ref):
    (A,) = _tile_quantize(lvl_ref[...], A_ref[0])
    Y_ref[0] = _dg_t(A, X_ref[0])


def sbgemm_th_real_tiled(A, X, lvl, *, block_n: int = 512,
                         block_s: int = 128, interpret: bool = False):
    """Tile-quantized Y = A^T X, real.  ``lvl`` int32 (B, cdiv(n, block_n))."""
    B, m, n = A.shape
    S = X.shape[2]
    assert S % block_s == 0 and X.shape == (B, m, S)
    assert lvl.shape == (B, pl.cdiv(n, block_n))
    grid = (B, pl.cdiv(n, block_n), S // block_s)
    return pl.pallas_call(
        _sbgemm_th_real_tiled_kernel,
        grid=grid,
        in_specs=[_lvl_spec(lambda b, j, s: (b, j)),
                  pl.BlockSpec((1, m, block_n), lambda b, j, s: (b, 0, j)),
                  pl.BlockSpec((1, m, block_s), lambda b, j, s: (b, 0, s))],
        out_specs=pl.BlockSpec((1, block_n, block_s),
                               lambda b, j, s: (b, j, s)),
        out_shape=jax.ShapeDtypeStruct((B, n, S), _ACC),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="sbgemm_th_real_tiled",
    )(_lvl_tiles(lvl), A, X)


def _sbgemm_n_real_tiled_kernel(n: int, lvl_ref, A_ref, X_ref, Y_ref):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        Y_ref[...] = jnp.zeros_like(Y_ref)

    (A,) = _tile_quantize(lvl_ref[...], _ragged(j, n, A_ref[0], 1))
    Y_ref[0] += _dot(A, _ragged(j, n, X_ref[0], 0))


def sbgemm_n_real_tiled(A, X, lvl, *, block_n: int = 512,
                        block_s: int = 128, interpret: bool = False):
    """Tile-quantized Y = A X, real.  ``lvl`` int32 (B, cdiv(n, block_n))."""
    B, m, n = A.shape
    S = X.shape[2]
    assert S % block_s == 0 and X.shape == (B, n, S)
    assert lvl.shape == (B, pl.cdiv(n, block_n))
    grid = (B, S // block_s, pl.cdiv(n, block_n))
    return pl.pallas_call(
        functools.partial(_sbgemm_n_real_tiled_kernel, n),
        grid=grid,
        in_specs=[_lvl_spec(lambda b, s, j: (b, j)),
                  pl.BlockSpec((1, m, block_n), lambda b, s, j: (b, 0, j)),
                  pl.BlockSpec((1, block_n, block_s), lambda b, s, j: (b, j, s))],
        out_specs=pl.BlockSpec((1, m, block_s), lambda b, s, j: (b, 0, s)),
        out_shape=jax.ShapeDtypeStruct((B, m, S), _ACC),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="sbgemm_n_real_tiled",
    )(_lvl_tiles(lvl), A, X)
