"""A stated rounding point: a value stored at a narrower float dtype, in a
form the compiler keeps (DESIGN.md §13)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def store(x, dtype):
    """``x`` stored at ``dtype``.

    Where ``dtype`` is the narrower float, the value is first rounded to
    nearest even with ``lax.reduce_precision`` to its exponent and mantissa
    bits, then cast.  A cast alone is not enough: allowed excess precision,
    the TPU compiler may delete a cast down that meets a cast back up in
    one fusion and carry the wider value on.  It keeps a
    ``reduce_precision``.  Where the dtype already matches this is the
    array itself, so a program with no narrowing gains no op.
    """
    dt = jnp.dtype(dtype)
    if x.dtype == dt:
        return x
    if (jnp.issubdtype(x.dtype, jnp.floating)
            and jnp.issubdtype(dt, jnp.floating)
            and dt.itemsize < x.dtype.itemsize):
        fi = jnp.finfo(dt)
        x = jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                     mantissa_bits=fi.nmant)
    return x.astype(dt)
