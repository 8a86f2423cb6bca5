"""Backend-dispatched wrappers for the Pallas kernels.

This layer is the repo's rocBLAS *host dispatcher* (paper §2.3): the
optimized short-wide kernel was inserted into the rocBLAS dispatch
function with benchmarking-derived transition points, so application
call sites never chose a kernel.  Here every op resolves a
:class:`repro.backend.BackendSpec` (what the hardware can do) and a
:class:`repro.backend.DispatchTable` (where the transition points sit)
and routes between three lowerings:

    "pallas"  the custom short-wide Pallas kernels (``sbgemv.py``),
    "xla"     the traffic-fused XLA formulation (each A plane read once
              for both output planes),
    "ref"     the pure-jnp oracles (``ref.py``) — the forced ``xla-ref``
              reference backend and the numerical ground truth.

Explicit-vs-auto contract (the old silent-downgrade bug is gone): a
*forced* Pallas path that the backend cannot run — f64 data on a
Pallas without an f64 datapath, or no Pallas at all — raises
:class:`repro.backend.UnsupportedOnBackend`; automatic dispatch falls
back to the XLA path instead.

The legacy ``use_pallas=/interpret=/xla_fused=`` kwargs (and their
one-release deprecation shim) are gone: kernel selection is expressed
only through ``backend=``/``dispatch=`` — the interpret-mode Pallas
spelling is ``backend="cpu-interpret"`` +
``dispatch=DispatchTable(force="pallas")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend import (DispatchTable, TPU_PALLAS, UnsupportedOnBackend,
                           default_table, resolve_backend)
from repro.backend.dispatch import DEFAULT_SHORT_WIDE_RATIO

from . import ref as _ref
from . import pad_cast as _pad_cast
from . import sbgemv as _sbgemv
from .padding import pad_planes, pad_to_multiple, round_up
from .rounding import store

# Back-compat alias: the transition point now lives in DispatchTable.
SHORT_WIDE_RATIO = DEFAULT_SHORT_WIDE_RATIO

# f32 contractions run at full f32: a TPU's default is one bf16 MXU pass
_HIGHEST = jax.lax.Precision.HIGHEST


def resolve_backend_dispatch(backend=None, dispatch=None):
    """Resolve ``(BackendSpec, DispatchTable)``.

    ``backend`` is a :class:`repro.backend.BackendSpec`, a registered
    name, or None (the probed process backend, ``REPRO_BACKEND``
    override applies); ``dispatch`` defaults to the backend's table.
    """
    spec = resolve_backend(backend)
    if dispatch is None:
        dispatch = default_table(spec)
    return spec, dispatch


def use_custom_kernel(m: int, n: int, mode: str,
                      table: DispatchTable | None = None) -> bool:
    """Shape heuristic (the 'host dispatcher'), on the default table."""
    table = table or DispatchTable()
    return table.gemv_path(m, n, mode, jnp.float32, TPU_PALLAS) == "pallas"


def _sbgemv_xla_fused(A_re, A_im, x_re, x_im, mode: str):
    """XLA path with the custom kernel's *traffic pattern*: stack the two
    input-vector planes so each A plane is contracted ONCE against both
    (one HBM read of A_re + A_im total, vs twice each for 4 independent
    GEMVs), accumulating in f32 without materializing upcast copies of A.
    Measured on the fftmatvec dry-run: memory term 12.45 -> ~5 ms/step."""
    # accumulate at >= f32; f64 inputs keep full f64 accumulation (the
    # paper-faithful ladder depends on it)
    acc = jnp.float64 if A_re.dtype == jnp.float64 else jnp.float32
    if mode == "N":
        X = jnp.stack([x_re, x_im], axis=1)               # (B, 2, n)
        R = jnp.einsum("bmn,bkn->bkm", A_re, X, preferred_element_type=acc,
                       precision=_HIGHEST)
        I = jnp.einsum("bmn,bkn->bkm", A_im, X, preferred_element_type=acc,
                       precision=_HIGHEST)
        return R[:, 0] - I[:, 1], R[:, 1] + I[:, 0]
    X = jnp.stack([x_re, x_im], axis=1)                   # (B, 2, m)
    R = jnp.einsum("bmn,bkm->bkn", A_re, X, preferred_element_type=acc,
                   precision=_HIGHEST)
    I = jnp.einsum("bmn,bkm->bkn", A_im, X, preferred_element_type=acc,
                   precision=_HIGHEST)
    if mode == "H":   # conj(A)^T x
        return R[:, 0] + I[:, 1], R[:, 1] - I[:, 0]
    return R[:, 0] - I[:, 1], R[:, 1] + I[:, 0]           # "T"


def sbgemv(A_re, A_im, x_re, x_im, mode: str = "N", *, out_dtype=None,
           backend=None, dispatch=None, block_n: int | None = None,
           tile_map=None):
    """Strided-batched complex GEMV on split planes, backend-dispatched.

    A planes (B, m, n); mode "N": x (B, n) -> y (B, m); "T"/"H": x (B, m)
    -> y (B, n).  Returns (y_re, y_im) in ``out_dtype`` (default: A dtype).
    ``backend``/``dispatch`` select the lowering (None = probed backend /
    its default table).  ``tile_map`` routes through the tiled SBGEMM
    path (a GEMV is the S=1 column panel — same kernels, same oracle).
    """
    if tile_map is not None:
        y_re, y_im = sbgemm(A_re, A_im, x_re[..., None], x_im[..., None],
                            mode, out_dtype=out_dtype, backend=backend,
                            dispatch=dispatch, block_n=block_n,
                            tile_map=tile_map)
        return y_re[..., 0], y_im[..., 0]
    B, m, n = A_re.shape
    out_dtype = out_dtype or A_re.dtype
    spec, table = resolve_backend_dispatch(backend, dispatch)
    path = table.gemv_path(m, n, mode, A_re.dtype, spec)
    if path != "pallas":
        fn = _ref.sbgemv_complex_ref if path == "ref" else _sbgemv_xla_fused
        y_re, y_im = fn(A_re, A_im, x_re, x_im, mode)
        return store(y_re, out_dtype), store(y_im, out_dtype)

    # A planes go in as stored (the kernels take any m, n): padding them
    # would copy the whole operator on every call
    bn = min(block_n or spec.default_block_n, max(spec.lane, n))
    itp = spec.pallas_interpret
    if mode == "N":
        y_re, y_im = _sbgemv.sbgemv_n_complex(A_re, A_im, x_re, x_im,
                                              block_n=bn, interpret=itp)
    else:
        y_re, y_im = _sbgemv.sbgemv_th_complex(A_re, A_im, x_re, x_im,
                                               conj=(mode == "H"),
                                               block_n=bn, interpret=itp)
    return store(y_re, out_dtype), store(y_im, out_dtype)


def sbgemv_real(A, x, mode: str = "N", *, out_dtype=None,
                backend=None, dispatch=None, block_n: int | None = None,
                tile_map=None):
    """Real strided-batched GEMV with the same dispatch logic."""
    if tile_map is not None:
        y = sbgemm_real(A, x[..., None], mode, out_dtype=out_dtype,
                        backend=backend, dispatch=dispatch,
                        block_n=block_n, tile_map=tile_map)
        return y[..., 0]
    B, m, n = A.shape
    out_dtype = out_dtype or A.dtype
    spec, table = resolve_backend_dispatch(backend, dispatch)
    path = table.gemv_path(m, n, mode, A.dtype, spec)
    if path != "pallas":
        return store(_ref.sbgemv_real_ref(A, x, mode), out_dtype)

    bn = min(block_n or spec.default_block_n, max(spec.lane, n))
    itp = spec.pallas_interpret
    fn = _sbgemv.sbgemv_n_real if mode == "N" else _sbgemv.sbgemv_th_real
    return store(fn(A, x, block_n=bn, interpret=itp), out_dtype)


def pad_cast(x, pad_to: int, out_dtype, *, backend=None, dispatch=None,
             fuse: bool | None = None):
    """(R, T) -> (R, pad_to) fused zero-pad + cast (Phase-1 memory op).

    ``fuse`` pins the fused-Pallas-kernel decision (None consults the
    dispatch table's cutover); a fuse preference the backend cannot
    honor (f64, no Pallas) silently takes the reference path — this is a
    memory op, the numerics are identical either way.
    """
    spec, table = resolve_backend_dispatch(backend, dispatch)
    if not table.fuse_pad_cast(x.shape[-1], x.dtype, out_dtype, spec,
                               prefer=fuse):
        return _ref.pad_cast_ref(x, pad_to, out_dtype)
    x2, R0 = pad_to_multiple(x, 0, spec.sublane)
    return _pad_cast.pad_cast(x2, pad_to, out_dtype,
                              block_rows=spec.sublane,
                              interpret=spec.pallas_interpret)[:R0]


def unpad_cast(x, keep: int, out_dtype, *, backend=None, dispatch=None,
               fuse: bool | None = None):
    """(R, P) -> (R, keep) fused unpad + cast (Phase-5 memory op)."""
    spec, table = resolve_backend_dispatch(backend, dispatch)
    if not table.fuse_pad_cast(x.shape[-1], x.dtype, out_dtype, spec,
                               prefer=fuse):
        return _ref.unpad_cast_ref(x, keep, out_dtype)
    x2, R0 = pad_to_multiple(x, 0, spec.sublane)
    return _pad_cast.unpad_cast(x2, keep, out_dtype,
                                block_rows=spec.sublane,
                                interpret=spec.pallas_interpret)[:R0]


# ---------------------------------------------------------------------------
# Tile-centric mixed precision plumbing (DESIGN.md §8).
#
# ``tile_map`` on the SBGEMM family is a TileMap (or raw tuple-of-tuples of
# ladder levels) whose (R, C) grid partitions the operand's batch axis B
# and minor axis n element-wise (kernels/ref.py defines the ground truth).
# Two lowerings, numerically identical:
#   aligned     each kernel column tile sits inside one map cell -> pass a
#               per-(b, tile) int32 level array to the tiled Pallas kernels,
#               which quantize the resident A tile in VMEM;
#   misaligned  (or non-Pallas path) -> round-trip A element-wise up front
#               and run the plain kernels on the quantized planes.
# ---------------------------------------------------------------------------


def _check_tile_support(spec, tile_map):
    if tile_map is not None and not spec.tile_precision:
        raise UnsupportedOnBackend(
            f"backend {spec.name!r} does not support tile-centric "
            f"precision (tile_map=); see BackendSpec.tile_precision")


def _quantize_planes_elementwise(tile_map, *planes):
    """Element-wise pre-quantization fallback — the oracle semantics."""
    B, _, n = planes[0].shape
    idx = _ref.expand_tile_levels(tile_map, B, n)
    out = _ref.quantize_tile_planes(idx, *planes)
    return out if isinstance(out, tuple) else (out,)


def _tile_lvl_per_block(tile_map, B: int, n: int, bn: int):
    """Per-(batch, kernel-tile) int32 level array for the tiled kernels,
    or None when the ``bn``-column kernel grid does not align with the
    map's cells.  Columns past n in the last, partial kernel tile
    (n -> round_up(n, bn)) inherit the last logical column's level: the
    kernels mask or drop them, so any level is exact."""
    idx = _ref.expand_tile_levels(tile_map, B, n)          # (B, n) int32
    n_pad = round_up(n, bn)
    if n_pad > n:
        idx = np.concatenate(
            [idx, np.repeat(idx[:, -1:], n_pad - n, axis=1)], axis=1)
    blocks = idx.reshape(B, n_pad // bn, bn)
    if not (blocks == blocks[:, :, :1]).all():
        return None
    return jnp.asarray(blocks[:, :, 0], dtype=jnp.int32)


# ---------------------------------------------------------------------------
# Multi-RHS (block) dispatch: SBGEMM.  Same transition-point heuristic as
# the GEMV path — the RHS axis only raises arithmetic intensity, so the
# shapes that favored the custom kernel still do.
# ---------------------------------------------------------------------------

def _sbgemm_xla_fused(A_re, A_im, X_re, X_im, mode: str):
    """XLA path with the kernel's traffic pattern: both RHS planes stacked
    so each A plane is read once per contraction (see _sbgemv_xla_fused)."""
    acc = jnp.float64 if A_re.dtype == jnp.float64 else jnp.float32
    X = jnp.stack([X_re, X_im], axis=1)               # (B, 2, n|m, S)
    if mode == "N":
        R = jnp.einsum("bmn,bkns->bkms", A_re, X, preferred_element_type=acc,
                       precision=_HIGHEST)
        I = jnp.einsum("bmn,bkns->bkms", A_im, X, preferred_element_type=acc,
                       precision=_HIGHEST)
        return R[:, 0] - I[:, 1], R[:, 1] + I[:, 0]
    R = jnp.einsum("bmn,bkms->bkns", A_re, X, preferred_element_type=acc,
                   precision=_HIGHEST)
    I = jnp.einsum("bmn,bkms->bkns", A_im, X, preferred_element_type=acc,
                   precision=_HIGHEST)
    if mode == "H":   # conj(A)^T X
        return R[:, 0] + I[:, 1], R[:, 1] - I[:, 0]
    return R[:, 0] - I[:, 1], R[:, 1] + I[:, 0]       # "T"


def sbgemm(A_re, A_im, X_re, X_im, mode: str = "N", *, out_dtype=None,
           backend=None, dispatch=None, block_n: int | None = None,
           block_s: int | None = None, tile_map=None):
    """Strided-batched complex GEMM (multi-RHS GEMV) on split planes.

    A planes (B, m, n); mode "N": X (B, n, S) -> Y (B, m, S); "T"/"H":
    X (B, m, S) -> Y (B, n, S).  The RHS axis S is tiled by ``block_s``
    (padded to a sublane multiple when smaller).  Returns (Y_re, Y_im) in
    ``out_dtype`` (default: A dtype).

    ``tile_map`` quantizes A per tile before the contraction (X and the
    accumulator stay in the carrier dtype) — the tile-centric mixed
    precision path, gated by ``BackendSpec.tile_precision``.
    """
    B, m, n = A_re.shape
    S = X_re.shape[2]
    out_dtype = out_dtype or A_re.dtype
    spec, table = resolve_backend_dispatch(backend, dispatch)
    _check_tile_support(spec, tile_map)
    path = table.gemv_path(m, n, mode, A_re.dtype, spec)
    if path != "pallas":
        if tile_map is not None:
            if path == "ref":
                Y_re, Y_im = _ref.sbgemm_tiled_ref(A_re, A_im, X_re, X_im,
                                                   tile_map, mode)
            else:
                Ar, Ai = _quantize_planes_elementwise(tile_map, A_re, A_im)
                Y_re, Y_im = _sbgemm_xla_fused(Ar, Ai, X_re, X_im, mode)
        else:
            fn = (_ref.sbgemm_complex_ref if path == "ref"
                  else _sbgemm_xla_fused)
            Y_re, Y_im = fn(A_re, A_im, X_re, X_im, mode)
        return store(Y_re, out_dtype), store(Y_im, out_dtype)

    bn = min(block_n or spec.default_block_n, max(spec.lane, n))
    bs = min(block_s or spec.default_block_s, round_up(S, spec.sublane))
    itp = spec.pallas_interpret
    lvl = None
    if tile_map is not None:
        lvl = _tile_lvl_per_block(tile_map, B, n, bn)
        if lvl is None:     # cells cut through kernel tiles: pre-quantize
            A_re, A_im = _quantize_planes_elementwise(tile_map, A_re, A_im)
    # A planes as stored; only the (small) RHS axis pads to whole tiles
    (Xr, Xi), _ = pad_planes((X_re, X_im), 2, bs)
    if mode == "N":
        if lvl is not None:
            Y_re, Y_im = _sbgemv.sbgemm_n_complex_tiled(
                A_re, A_im, Xr, Xi, lvl, block_n=bn, block_s=bs,
                interpret=itp)
        else:
            Y_re, Y_im = _sbgemv.sbgemm_n_complex(
                A_re, A_im, Xr, Xi, block_n=bn, block_s=bs, interpret=itp)
    else:
        if lvl is not None:
            Y_re, Y_im = _sbgemv.sbgemm_th_complex_tiled(
                A_re, A_im, Xr, Xi, lvl, conj=(mode == "H"),
                block_n=bn, block_s=bs, interpret=itp)
        else:
            Y_re, Y_im = _sbgemv.sbgemm_th_complex(
                A_re, A_im, Xr, Xi, conj=(mode == "H"), block_n=bn,
                block_s=bs, interpret=itp)
    return store(Y_re[..., :S], out_dtype), store(Y_im[..., :S], out_dtype)


def sbgemm_gram(A_re, A_im, *, space: str = "parameter", out_dtype=None,
                backend=None, dispatch=None, block_n: int | None = None,
                tile_map=None):
    """Per-bin Hermitian Gram blocks: G[k] = A[k]^H A[k] ("parameter") or
    A[k] A[k]^H ("data") on split planes, with the same dispatch logic as
    the GEMV/GEMM paths.

    A planes (B, m, n) -> G planes (B, n, n) or (B, m, m).  The returned
    planes are exactly Hermitian (G_re symmetric, G_im antisymmetric with a
    zero diagonal): roundoff asymmetry from the accumulation order is
    symmetrized away, so downstream Gram pipelines can rely on G == G^H.
    Setup-phase code (paper Phase 0) — run once per operator, not per apply.

    ``tile_map`` quantizes A once on its (B, n) operand grid — *before*
    any data-space transpose — so both chained passes read the same
    quantized operand (the oracle's rule).
    """
    B, m, n = A_re.shape
    out_dtype = out_dtype or A_re.dtype
    spec, table = resolve_backend_dispatch(backend, dispatch)
    _check_tile_support(spec, tile_map)
    if space not in ("parameter", "data"):
        raise ValueError(f"bad gram space {space!r}")
    if space == "data":
        # the kernel path runs A A^H as (A^H)^H (A^H) on conjugate-
        # transposed planes; its (n x n) blocks are the (m x m) ones here.
        # Tile quantization happens first, on the original operand grid
        # (it commutes with negation).
        if tile_map is not None:
            A_re, A_im = _quantize_planes_elementwise(tile_map, A_re, A_im)
            tile_map = None
        m, n = n, m
    path = table.gemv_path(m, n, "H", A_re.dtype, spec)
    if path != "pallas":
        # the oracle contracts in the requested space directly, so the
        # reference lowering is the oracle bit for bit in both spaces
        if tile_map is not None:
            A_re, A_im = _quantize_planes_elementwise(tile_map, A_re, A_im)
        G_re, G_im = _ref.sbgemm_gram_ref(A_re, A_im, space)
    else:
        if space == "data":
            A_re = A_re.transpose(0, 2, 1)
            A_im = -A_im.transpose(0, 2, 1)
        bn = min(block_n or spec.default_block_n, max(spec.lane, n))
        lvl = None
        if tile_map is not None:
            lvl = _tile_lvl_per_block(tile_map, B, n, bn)
            if lvl is None:
                A_re, A_im = _quantize_planes_elementwise(tile_map,
                                                          A_re, A_im)
        if lvl is not None:
            G_re, G_im = _sbgemv.sbgemm_gram_tiled(
                A_re, A_im, lvl, block_n=bn, interpret=spec.pallas_interpret)
        else:
            G_re, G_im = _sbgemv.sbgemm_gram_complex(
                A_re, A_im, block_n=bn, interpret=spec.pallas_interpret)
    # enforce exact Hermitian symmetry (kills accumulation-order roundoff)
    G_re = 0.5 * (G_re + G_re.transpose(0, 2, 1))
    G_im = 0.5 * (G_im - G_im.transpose(0, 2, 1))
    return store(G_re, out_dtype), store(G_im, out_dtype)


def sbgemm_real(A, X, mode: str = "N", *, out_dtype=None,
                backend=None, dispatch=None, block_n: int | None = None,
                block_s: int | None = None, tile_map=None):
    """Real strided-batched GEMM with the same dispatch logic."""
    B, m, n = A.shape
    S = X.shape[2]
    out_dtype = out_dtype or A.dtype
    spec, table = resolve_backend_dispatch(backend, dispatch)
    _check_tile_support(spec, tile_map)
    path = table.gemv_path(m, n, mode, A.dtype, spec)
    if path != "pallas":
        if tile_map is not None:
            return store(_ref.sbgemm_tiled_real_ref(A, X, tile_map, mode),
                         out_dtype)
        return store(_ref.sbgemm_real_ref(A, X, mode), out_dtype)

    bn = min(block_n or spec.default_block_n, max(spec.lane, n))
    bs = min(block_s or spec.default_block_s, round_up(S, spec.sublane))
    itp = spec.pallas_interpret
    lvl = None
    if tile_map is not None:
        lvl = _tile_lvl_per_block(tile_map, B, n, bn)
        if lvl is None:
            (A,) = _quantize_planes_elementwise(tile_map, A)
    X2, _ = pad_to_multiple(X, 2, bs)
    if mode == "N":
        if lvl is not None:
            Y = _sbgemv.sbgemm_n_real_tiled(A, X2, lvl, block_n=bn,
                                            block_s=bs, interpret=itp)
        else:
            Y = _sbgemv.sbgemm_n_real(A, X2, block_n=bn, block_s=bs,
                                      interpret=itp)
    else:
        if lvl is not None:
            Y = _sbgemv.sbgemm_th_real_tiled(A, X2, lvl, block_n=bn,
                                             block_s=bs, interpret=itp)
        else:
            Y = _sbgemv.sbgemm_th_real(A, X2, block_n=bn, block_s=bs,
                                       interpret=itp)
    return store(Y[..., :S], out_dtype)
