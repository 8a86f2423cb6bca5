"""Pure-jnp oracles for the Pallas kernels.

These define the semantics the kernels are validated against (interpret
mode on CPU, sweeping shapes and dtypes in tests/test_kernels_*).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_TILE_LEVEL_INDEX = {"h": 0, "s": 1, "d": 2}


def _einsum(spec, *operands):
    """``jnp.einsum`` at the full precision of its operands: a TPU's
    default for f32 is one bf16 MXU pass."""
    return jnp.einsum(spec, *operands, precision=jax.lax.Precision.HIGHEST)


def sbgemv_real_ref(A, x, mode: str = "N"):
    """Strided-batched real GEMV.

    A: (B, m, n).  mode "N": x (B, n) -> y (B, m);  mode "T": x (B, m) ->
    y (B, n).  Accumulation in f32 (or f64 under x64 for f64 inputs).
    """
    acc = jnp.float64 if A.dtype == jnp.float64 else jnp.float32
    if mode == "N":
        y = _einsum("bmn,bn->bm", A.astype(acc), x.astype(acc))
    elif mode == "T":
        y = _einsum("bmn,bm->bn", A.astype(acc), x.astype(acc))
    else:
        raise ValueError(f"bad mode {mode!r}")
    return y.astype(A.dtype)


def sbgemv_complex_ref(A_re, A_im, x_re, x_im, mode: str = "N"):
    """Strided-batched complex GEMV on split re/im planes.

    modes: "N" (y = A x), "T" (y = A^T x), "H" (y = A^H x — the paper's
    conjugate-transpose case).  Returns (y_re, y_im) in the input dtype.
    """
    acc = jnp.float64 if A_re.dtype == jnp.float64 else jnp.float32
    Ar, Ai = A_re.astype(acc), A_im.astype(acc)
    xr, xi = x_re.astype(acc), x_im.astype(acc)
    if mode == "N":
        y_re = _einsum("bmn,bn->bm", Ar, xr) - _einsum("bmn,bn->bm", Ai, xi)
        y_im = _einsum("bmn,bn->bm", Ar, xi) + _einsum("bmn,bn->bm", Ai, xr)
    elif mode == "T":
        y_re = _einsum("bmn,bm->bn", Ar, xr) - _einsum("bmn,bm->bn", Ai, xi)
        y_im = _einsum("bmn,bm->bn", Ar, xi) + _einsum("bmn,bm->bn", Ai, xr)
    elif mode == "H":  # conj(A)^T x
        y_re = _einsum("bmn,bm->bn", Ar, xr) + _einsum("bmn,bm->bn", Ai, xi)
        y_im = _einsum("bmn,bm->bn", Ar, xi) - _einsum("bmn,bm->bn", Ai, xr)
    else:
        raise ValueError(f"bad mode {mode!r}")
    return y_re.astype(A_re.dtype), y_im.astype(A_re.dtype)


def pad_cast_ref(x, pad_to: int, out_dtype):
    """Zero-pad the minor (time) axis to ``pad_to`` and cast: (..., T) ->
    (..., pad_to).  Fused Phase-1 memory op."""
    T = x.shape[-1]
    pad = [(0, 0)] * (x.ndim - 1) + [(0, pad_to - T)]
    return jnp.pad(x.astype(out_dtype), pad)


def unpad_cast_ref(x, keep: int, out_dtype):
    """Slice the first ``keep`` entries of the minor axis and cast.  Fused
    Phase-5 memory op."""
    return x[..., :keep].astype(out_dtype)


def sbgemm_real_ref(A, X, mode: str = "N"):
    """Strided-batched real GEMM (multi-RHS GEMV).

    A: (B, m, n).  mode "N": X (B, n, S) -> Y (B, m, S);  mode "T":
    X (B, m, S) -> Y (B, n, S).  f32 accumulation (f64 under x64).
    """
    acc = jnp.float64 if A.dtype == jnp.float64 else jnp.float32
    if mode == "N":
        Y = _einsum("bmn,bns->bms", A.astype(acc), X.astype(acc))
    elif mode == "T":
        Y = _einsum("bmn,bms->bns", A.astype(acc), X.astype(acc))
    else:
        raise ValueError(f"bad mode {mode!r}")
    return Y.astype(A.dtype)


def sbgemm_gram_ref(A_re, A_im, space: str = "parameter"):
    """Per-batch Hermitian Gram blocks on split re/im planes.

    A planes (B, m, n).  ``space="parameter"``: G = A^H A, (B, n, n);
    ``space="data"``: G = A A^H, (B, m, m).  G is Hermitian per batch
    (G == conj(G)^T; the imaginary diagonal is exactly zero up to the
    accumulator's roundoff).  Accumulation in f32 (f64 under x64 for f64
    inputs).  Returns (G_re, G_im) in the input dtype.
    """
    acc = jnp.float64 if A_re.dtype == jnp.float64 else jnp.float32
    Ar, Ai = A_re.astype(acc), A_im.astype(acc)
    if space == "parameter":
        e = lambda X, Y: _einsum("bmn,bmk->bnk", X, Y)
        # (Ar - i Ai)^T (Ar + i Ai)
        G_re = e(Ar, Ar) + e(Ai, Ai)
        G_im = e(Ar, Ai) - e(Ai, Ar)
    elif space == "data":
        e = lambda X, Y: _einsum("bmn,bkn->bmk", X, Y)
        # (Ar + i Ai) (Ar^T - i Ai^T)
        G_re = e(Ar, Ar) + e(Ai, Ai)
        G_im = e(Ai, Ar) - e(Ar, Ai)
    else:
        raise ValueError(f"bad gram space {space!r}")
    return G_re.astype(A_re.dtype), G_im.astype(A_re.dtype)


def sbgemm_complex_ref(A_re, A_im, X_re, X_im, mode: str = "N"):
    """Strided-batched complex GEMM on split re/im planes.

    modes: "N" (Y = A X), "T" (Y = A^T X), "H" (Y = A^H X).  X carries the
    RHS axis last: (B, n, S) for "N", (B, m, S) otherwise.  Returns
    (Y_re, Y_im) in the input dtype.
    """
    acc = jnp.float64 if A_re.dtype == jnp.float64 else jnp.float32
    Ar, Ai = A_re.astype(acc), A_im.astype(acc)
    Xr, Xi = X_re.astype(acc), X_im.astype(acc)
    if mode == "N":
        e = lambda A, X: _einsum("bmn,bns->bms", A, X)
    elif mode in ("T", "H"):
        e = lambda A, X: _einsum("bmn,bms->bns", A, X)
    else:
        raise ValueError(f"bad mode {mode!r}")
    if mode == "H":  # conj(A)^T X
        Y_re = e(Ar, Xr) + e(Ai, Xi)
        Y_im = e(Ar, Xi) - e(Ai, Xr)
    else:
        Y_re = e(Ar, Xr) - e(Ai, Xi)
        Y_im = e(Ar, Xi) + e(Ai, Xr)
    return Y_re.astype(A_re.dtype), Y_im.astype(A_re.dtype)


# -- tile-centric mixed precision (DESIGN.md §8) ----------------------------
#
# Ground-truth semantics: a tile map's (R, C) grid partitions the operand's
# batch axis B and minor (column) axis n *element-wise* — element (b, :, c)
# belongs to tile (b*R // B, c*C // n).  Each element of A is round-tripped
# through its tile's storage dtype; X and the accumulator stay in the
# carrier dtype.  Kernel lowerings (Pallas in-kernel select, XLA
# pre-quantize) must match these oracles bit-exactly.

def expand_tile_levels(tile_map, B: int, n: int):
    """Expand a tile-level grid to per-element ladder indices.

    ``tile_map`` is a TileMap or a tuple-of-tuples of level chars (the
    *effective* levels, ``min(cell, gemv)``).  Returns a numpy int32
    (B, n) array of ladder indices (h=0, s=1, d=2) — element (b, c) gets
    tile ``(b*R // B, c*C // n)``.
    """
    levels = getattr(tile_map, "levels", tile_map)
    R, C = len(levels), len(levels[0])
    grid = np.array([[_TILE_LEVEL_INDEX[l] for l in row] for row in levels],
                    dtype=np.int32)
    rows = (np.arange(B) * R) // B
    cols = (np.arange(n) * C) // n
    return grid[rows[:, None], cols[None, :]]


def quantize_tile_planes(lvl_idx, *planes):
    """Round-trip each element of the (B, m, n) A planes through its
    tile's storage dtype, returning carrier-dtype planes.

    ``lvl_idx`` is the (B, n) per-element index array from
    :func:`expand_tile_levels`; it broadcasts over the row axis m.  A
    round-trip through a dtype at or above the carrier is the identity
    (the ladder's mantissas nest: bf16 ⊂ f32 ⊂ f64), so only genuinely
    lower tiles lose bits.
    """
    sel = jnp.asarray(lvl_idx)[:, None, :]
    outs = []
    for A in planes:
        q_h = A.astype(jnp.bfloat16).astype(A.dtype)
        q_s = A.astype(jnp.float32).astype(A.dtype)
        outs.append(jnp.where(sel == 0, q_h, jnp.where(sel == 1, q_s, A)))
    return outs[0] if len(outs) == 1 else tuple(outs)


def sbgemm_tiled_ref(A_re, A_im, X_re, X_im, tile_map, mode: str = "N"):
    """Tile-quantized complex GEMM oracle: quantize A per tile, contract
    exactly like :func:`sbgemm_complex_ref` (carrier accumulation)."""
    B, _, n = A_re.shape
    idx = expand_tile_levels(tile_map, B, n)
    Ar, Ai = quantize_tile_planes(idx, A_re, A_im)
    return sbgemm_complex_ref(Ar, Ai, X_re, X_im, mode)


def sbgemm_tiled_real_ref(A, X, tile_map, mode: str = "N"):
    """Tile-quantized real GEMM oracle (see :func:`sbgemm_tiled_ref`)."""
    B, _, n = A.shape
    idx = expand_tile_levels(tile_map, B, n)
    Aq = quantize_tile_planes(idx, A)
    return sbgemm_real_ref(Aq, X, mode)


def sbgemm_gram_tiled_ref(A_re, A_im, tile_map, space: str = "parameter"):
    """Tile-quantized Gram oracle: both chained passes read the same
    quantized A (quantization happens once, on the (B, n) operand grid,
    *before* any data-space transpose)."""
    B, _, n = A_re.shape
    idx = expand_tile_levels(tile_map, B, n)
    Ar, Ai = quantize_tile_planes(idx, A_re, A_im)
    return sbgemm_gram_ref(Ar, Ai, space)
