"""Per-segment linear models (key -> position) with error bounds.

ALEX/CARMI leaves predict positions with linear models; the probe cost is
O(log |error|) via exponential+binary search inside the error bound.  Both
exact least-squares fits and the cheap 2-point "approximate" fits that ALEX's
`approx_model_computation` flag selects are provided, fully vectorized over
segments (static shapes, masked).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fit_segments_exact(keys: jax.Array, seg_id: jax.Array, n_segs: int):
    """Least-squares fit per segment of (key -> local rank).

    keys [n] sorted; seg_id [n] in [0, n_segs); returns (slope, intercept,
    count) each [n_segs].  Positions are local ranks within the segment.
    """
    n = keys.shape[0]
    ones = jnp.ones_like(keys)
    cnt = jnp.zeros(n_segs).at[seg_id].add(ones)
    # local rank = global rank - segment start rank
    starts = jnp.cumsum(cnt) - cnt                      # [n_segs]
    pos = jnp.arange(n, dtype=keys.dtype) - starts[seg_id]

    sx = jnp.zeros(n_segs).at[seg_id].add(keys)
    sy = jnp.zeros(n_segs).at[seg_id].add(pos)
    sxx = jnp.zeros(n_segs).at[seg_id].add(keys * keys)
    sxy = jnp.zeros(n_segs).at[seg_id].add(keys * pos)
    c = jnp.maximum(cnt, 1.0)
    var = sxx - sx * sx / c
    cov = sxy - sx * sy / c
    slope = jnp.where(var > 1e-18, cov / jnp.maximum(var, 1e-18), 0.0)
    intercept = (sy - slope * sx) / c
    return slope, intercept, cnt


def fit_root(keys: jax.Array):
    """One least-squares model over all keys (the root model's fit).

    The all-zero segment ids pass through an optimization barrier: with
    constant ids XLA constant-folds the count and rank-sum scatters at
    compile time, which takes the TPU compiler about 3.5 s each at 2^20
    keys (v5e, ahead-of-time compile) — in every program that builds an
    index.  The barrier leaves the arithmetic as it is."""
    seg = jax.lax.optimization_barrier(jnp.zeros(keys.shape, jnp.int32))
    return fit_segments_exact(keys, seg, 1)


def fit_segments_approx(keys: jax.Array, seg_id: jax.Array, n_segs: int):
    """2-point (min/max) fit per segment — ALEX's approximate model path."""
    big = jnp.inf
    kmin = jnp.full((n_segs,), big).at[seg_id].min(keys)
    kmax = jnp.full((n_segs,), -big).at[seg_id].max(keys)
    cnt = jnp.zeros(n_segs).at[seg_id].add(jnp.ones_like(keys))
    rng = jnp.maximum(kmax - kmin, 1e-18)
    slope = jnp.where(cnt > 1, (cnt - 1) / rng, 0.0)
    intercept = -slope * jnp.where(jnp.isfinite(kmin), kmin, 0.0)
    return slope, intercept, cnt


def predict(slope, intercept, seg_of_q, q):
    """Predicted local rank for queries q given their segment."""
    return slope[seg_of_q] * q + intercept[seg_of_q]


def segment_errors(keys, seg_id, n_segs, slope, intercept):
    """Max |prediction - actual local rank| per segment (the probe bound)."""
    n = keys.shape[0]
    cnt = jnp.zeros(n_segs).at[seg_id].add(jnp.ones_like(keys))
    starts = jnp.cumsum(cnt) - cnt
    pos = jnp.arange(n, dtype=keys.dtype) - starts[seg_id]
    pred = slope[seg_id] * keys + intercept[seg_id]
    err = jnp.abs(pred - pos)
    return jnp.zeros(n_segs).at[seg_id].max(err)
