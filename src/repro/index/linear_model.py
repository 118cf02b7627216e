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


def _segment_sums(seg_id: jax.Array, n_segs: int, *terms):
    """Per-segment sums of each of `terms` [n]: scatter-added within
    blocks of about sqrt(n) consecutive keys, then summed across the
    blocks.  A scatter-add adds its terms one after another, each
    rounded against the running total: over the 2^20 keys of one root
    fit the float32 totals drift by up to 8e-4, and the fit's slope by
    1.5% (clustered `osm` keys, against float64; 1e-6 in blocks)."""
    n = seg_id.shape[0]
    block = 1 << ((max(n, 2) - 1).bit_length() + 1) // 2
    n_blocks = -(-n // block)
    cell = jnp.arange(n, dtype=jnp.int32) // block * n_segs + seg_id
    return [jnp.zeros(n_blocks * n_segs, t.dtype).at[cell].add(t)
            .reshape(n_blocks, n_segs).sum(axis=0) for t in terms]


def fit_segments_exact(keys: jax.Array, seg_id: jax.Array, n_segs: int):
    """Least-squares fit per segment of (key -> local rank).

    keys [n] sorted; seg_id [n] non-decreasing in [0, n_segs); returns
    (slope, intercept, count) each [n_segs], the line in global form
    (local rank ~ slope * key + intercept).

    The sums are taken about each segment's own origin: keys shifted by
    the segment's first key, ranks centred on the segment's mean rank
    (c - 1) / 2.  One-pass float32 sums about zero (`var = sxx -
    sx*sx/c`) cancel on a narrow segment far from zero, which clustered
    keys give; about the segment's origin they do not, and the centred
    ranks sum to zero exactly, so their sum is never taken.  The sums
    are accumulated in blocks (`_segment_sums`).  A segment whose keys
    are all equal gets slope 0.
    """
    n = keys.shape[0]
    with jax.named_scope("leaf_fit"):
        # counts are whole numbers below 2^24: exact in one scatter-add
        cnt = jnp.zeros(n_segs).at[seg_id].add(jnp.ones_like(keys))
        starts = jnp.cumsum(cnt) - cnt                  # [n_segs]
        anchor = keys[jnp.minimum(starts.astype(jnp.int32), n - 1)]
        half = 0.5 * jnp.maximum(cnt - 1.0, 0.0)        # mean local rank
        # key and local rank about the segment's origin
        xs = keys - anchor[seg_id]
        ys = jnp.arange(n, dtype=keys.dtype) - (starts + half)[seg_id]

        sx, sxx, sxy = _segment_sums(seg_id, n_segs, xs, xs * xs, xs * ys)
        c = jnp.maximum(cnt, 1.0)
        var = sxx - sx * sx / c
        slope = jnp.where(var > 1e-18, sxy / jnp.maximum(var, 1e-18), 0.0)
        # the line through the centroid (sx / c, 0) about the origin,
        # moved back to global keys and local ranks.  Kept in this order:
        # written `half - slope * (sx / c)`, XLA:CPU fuses the product
        # and the difference into one fma inside a serving pool's
        # `lax.map` and not in a serial episode, whose answers the pool's
        # must equal bit for bit
        intercept = half - slope * sx / c - slope * anchor
        return slope, intercept, cnt


def fit_root(keys: jax.Array):
    """One least-squares model over all keys (the root model's fit).

    The all-zero segment ids pass through an optimization barrier: with
    constant ids XLA constant-folds the count scatter at compile time,
    which takes the TPU compiler about 3.5 s at 2^20 keys (v5e,
    ahead-of-time compile) — in every program that builds an index.
    The barrier leaves the arithmetic as it is."""
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
