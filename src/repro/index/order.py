"""Order statistics over float32 arrays through a single-operand int32 sort.

`sort` and `percentile` return exactly what `jnp.sort` and
`jnp.percentile` (linear interpolation) return, bit for bit, on arrays
without a negative zero (`jnp.sort` keeps -0.0 and +0.0 in input order;
here -0.0 sorts first).  The env's sorted quantities — normalized keys,
search distances — are never -0.0.  They differ only in how the sort is
expressed: a float32 array is mapped onto int32
keys that order like the floats' total order (negative values have their
magnitude bits flipped), sorted unstably — equal keys are equal bit
patterns, so stability cannot change the result — and mapped back.  The
TPU compiler builds a stable float32 sort of 2^20 elements in about 45 s
and an unstable int32 sort of the same size in about 5 s (v5e, ahead-of-
time compile), and every env program at deployment key counts holds such
sorts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_FLIP = 0x7FFFFFFF      # magnitude bits of a float32's int32 pattern


def _to_keys(x):
    i = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return jnp.where(i < 0, i ^ _FLIP, i)


def _from_keys(k):
    return lax.bitcast_convert_type(jnp.where(k < 0, k ^ _FLIP, k),
                                    jnp.float32)


def sort(x):
    """`jnp.sort(x)` for a 1-D float32 array, bitwise (see above)."""
    return _from_keys(lax.sort(_to_keys(x), is_stable=False))


@jax.jit
def _percentile(x, q):
    # op for op the body of `jnp.percentile` -> `jnp.quantile` (linear),
    # including `q` arriving as a traced operand: XLA may rewrite the
    # division by 100 as a reciprocal multiply, and must see the same
    # program to round the same way
    a = sort(x)
    qf = jnp.true_divide(q, 100.0)
    n = jnp.float32(x.shape[0])
    qf = lax.mul(qf, n - 1)
    low, high = lax.floor(qf), lax.ceil(qf)
    high_w = lax.sub(qf, low)
    low_w = lax.sub(jnp.float32(1), high_w)
    low = lax.clamp(jnp.float32(0), low, n - 1).astype(jnp.int32)
    high = lax.clamp(jnp.float32(0), high, n - 1).astype(jnp.int32)
    out = lax.add(lax.mul(a[low], low_w), lax.mul(a[high], high_w))
    return jnp.where(jnp.any(jnp.isnan(x)), jnp.nan, out)


def percentile(x, q):
    """`jnp.percentile(x, q)` (method="linear") for a 1-D float32 array,
    bitwise: the same interpolation arithmetic over the same sorted
    values, NaN when any element is NaN."""
    return _percentile(x, q)
