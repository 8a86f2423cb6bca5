"""Alignment padding for the Pallas kernel wrappers — one implementation.

Every kernel entry point used to carry its own copy of the
round-up/zero-pad logic with the sublane multiple hardcoded to 8; the
helpers here are the single backend-aware version: the alignment comes
from the :class:`repro.backend.BackendSpec` the op was dispatched on, so
a backend with different tiling (bf16's 16-row sublanes, a future GPU
lowering) changes the padding in exactly one place.

Zero padding is semantically free where it is used: the stacked rows of
the pad-cast kernels and the RHS axis of the SBGEMM kernels, sliced away
by the wrapper before returning.  The operator planes are never padded
(``kernels/sbgemv.py`` takes them as stored).
"""

from __future__ import annotations

import jax.numpy as jnp


def round_up(size: int, multiple: int) -> int:
    """Smallest multiple of ``multiple`` >= ``size``."""
    return ((size + multiple - 1) // multiple) * multiple


def pad_to_multiple(x, axis: int, multiple: int):
    """Zero-pad ``axis`` up to a multiple; returns ``(padded, orig_size)``."""
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x, size
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad), size


def pad_planes(planes, axis: int, multiple: int):
    """Pad each array of a plane tuple identically; returns
    ``(padded_planes, orig_size)`` — the split re/im (or A/X) pairs the
    complex kernels carry always pad in lockstep."""
    out = []
    size = planes[0].shape[axis]
    for p in planes:
        q, _ = pad_to_multiple(p, axis, multiple)
        out.append(q)
    return tuple(out), size
