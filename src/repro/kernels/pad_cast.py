"""Pallas TPU kernels: fused zero-pad + precision cast (paper §3.2).

"At all possible points, the casting kernels are fused with any nearby
memory operations (zero-padding, unpadding, etc.) to reduce kernel launch
latencies" — these kernels fuse the Phase-1 pad / Phase-5 unpad memory op
with the precision cast at the phase boundary, so the vector is read and
written exactly once at the *lower* of the two adjacent precisions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pad_cast_kernel(T: int, x_ref, o_ref):
    blk = x_ref[...].astype(o_ref.dtype)        # (br, T) cast on the fly
    o_ref[:, :T] = blk
    o_ref[:, T:] = jnp.zeros_like(o_ref[:, T:])


def pad_cast(x, pad_to: int, out_dtype, *, block_rows: int = 8,
             interpret: bool = False):
    """(R, T) -> (R, pad_to) zero-padded on the minor axis, cast to
    ``out_dtype``.  R % block_rows == 0 (wrappers pad)."""
    R, T = x.shape
    assert R % block_rows == 0 and pad_to >= T
    grid = (R // block_rows,)
    return pl.pallas_call(
        functools.partial(_pad_cast_kernel, T),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, T), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, pad_to), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, pad_to), out_dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)


def _unpad_cast_kernel(keep: int, x_ref, o_ref):
    o_ref[...] = x_ref[:, :keep].astype(o_ref.dtype)


def unpad_cast(x, keep: int, out_dtype, *, block_rows: int = 8,
               interpret: bool = False):
    """(R, P) -> (R, keep): slice the leading minor-axis entries + cast.

    The input block spans ``keep`` rounded up to whole 128-lane tiles (or
    all of P): a block must be lane-aligned or the full minor dim, and
    ``keep = N_t`` rarely is a multiple of 128."""
    R, P = x.shape
    assert R % block_rows == 0 and keep <= P
    grid = (R // block_rows,)
    read = min(-(-keep // 128) * 128, P)
    return pl.pallas_call(
        functools.partial(_unpad_cast_kernel, keep),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, read), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, keep), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, keep), out_dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)
