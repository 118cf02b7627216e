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


def _bf16_pieces(t: jax.Array):
    """Three bfloat16 pieces of float32 `t` whose sum is `t` exactly:
    the top 8 significant bits, the next 8 and the last 8, each cut by
    masking the low 16 bits.  Not by a round trip through bfloat16: XLA
    may drop such a pair of conversions where it allows excess
    precision, its default."""
    def cut(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)
    hi = cut(t)
    mid = cut(t - hi)
    lo = t - hi - mid
    return [p.astype(jnp.bfloat16) for p in (hi, mid, lo)]


def _segment_sums(seg_id: jax.Array, n_segs: int, *terms):
    """Per-segment sums of each of the float32 `terms` [n], taken in
    blocks of about sqrt(n) consecutive keys and then summed across the
    blocks: one running total over the 2^20 keys of a root fit drifts
    by up to 8e-4, and the fit's slope by 1.5% (clustered `osm` keys,
    against float64; 1e-6 in blocks).

    Within a block the sums are a contraction on the MXU, onehot(seg_id)^T
    @ terms, not scatter-adds, which the TPU runs one key after another.
    The MXU multiplies bfloat16 operands, and a term rounded to bfloat16
    keeps 8 of its 24 significant bits; so each term goes in as three
    bfloat16 pieces that hold all 24 (`_bf16_pieces`).  The one-hot is
    exact in bfloat16, so every product is exact, the products
    accumulate in float32, and the pieces' sums are added back after the
    contraction.  Padding keys get segment id -1, which matches no
    column.  With one segment the one-hot is all ones: the sums are
    plain blocked sums."""
    n = seg_id.shape[0]
    block = 1 << ((max(n, 2) - 1).bit_length() + 1) // 2
    n_blocks = -(-n // block)
    pad = n_blocks * block - n
    if n_segs == 1:
        return [jnp.pad(t, (0, pad)).reshape(n_blocks, block).sum(axis=1)
                .sum(axis=0, keepdims=True) for t in terms]
    seg = jnp.pad(seg_id, (0, pad), constant_values=-1)
    onehot = (seg.reshape(n_blocks, block, 1) == jnp.arange(n_segs)
              ).astype(jnp.bfloat16)                  # [blocks, block, segs]
    cols = jnp.stack([p for t in terms for p in _bf16_pieces(t)], axis=1)
    cols = jnp.pad(cols, ((0, pad), (0, 0))).reshape(n_blocks, block, -1)
    sums = jnp.einsum("bks,bkt->bst", onehot, cols,
                      preferred_element_type=jnp.float32).sum(axis=0)
    sums = sums.reshape(n_segs, len(terms), 3).sum(axis=2)
    return [sums[:, i] for i in range(len(terms))]


def _segment_counts(seg_id: jax.Array, n_segs: int, dtype):
    """Keys per segment [n_segs] of non-decreasing `seg_id`: the
    differences of where each segment starts, found by binary search
    (n_segs + 1 searches of log2(n) steps each).  A contraction with a
    column of ones gives the same whole numbers, but the compiler turns
    it into a reduction over the whole one-hot: 3.5 ms against the
    search's 0.9 at 2^20 keys and 1,024 segments (TPU v5e)."""
    edges = jnp.searchsorted(seg_id, jnp.arange(n_segs + 1,
                                                dtype=seg_id.dtype))
    return jnp.diff(edges).astype(dtype)


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
    are contractions with a one-hot of the segment ids over blocks of
    keys (`_segment_sums`), and the counts come from a binary search of
    the segment edges (`_segment_counts`): no scatter.  A segment whose
    keys are all equal gets slope 0.
    """
    n = keys.shape[0]
    with jax.named_scope("leaf_fit"):
        cnt = _segment_counts(seg_id, n_segs, keys.dtype)
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
    """One least-squares model over all keys (the root model's fit): one
    segment, so its sums are plain blocked sums and its count is n."""
    return fit_segments_exact(keys, jnp.zeros(keys.shape, jnp.int32), 1)


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
    """Max |prediction - actual local rank| per segment (the probe bound).

    The counts are computed as `fit_segments_exact` computes them, so
    that the compiler merges the two: with counts of their own, XLA:CPU
    fuses the prediction into an fma inside a serving pool's `lax.map`
    and not in a serial episode, whose answers the pool's must equal bit
    for bit."""
    n = keys.shape[0]
    cnt = _segment_counts(seg_id, n_segs, keys.dtype)
    starts = jnp.cumsum(cnt) - cnt
    pos = jnp.arange(n, dtype=keys.dtype) - starts[seg_id]
    pred = slope[seg_id] * keys + intercept[seg_id]
    err = jnp.abs(pred - pos)
    return jnp.zeros(n_segs).at[seg_id].max(err)
