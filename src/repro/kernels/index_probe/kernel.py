"""Pallas TPU kernel: batched learned-index probe (the paper's hot loop).

Hardware adaptation: instead of ALEX's pointer-chasing exponential
search (a scalar-CPU pattern), keys live in sorted VMEM tiles and each
grid step answers a *block* of per-tile query groups with a
compare-and-count: a query's predecessor count inside its tile is the
number of tile keys <= it, accumulated one key column at a time on the
VPU (the key broadcast along lanes, the queries lane-dense).  The
model-routing stage (query -> tile) happens outside as a capacity-grouped
dispatch, mirroring the MoE token dispatch.

Grid: (n_tiles / rows,).  Each step holds `rows` key tiles `[rows, tile]`
and their query groups `[rows, qcap]` in VMEM.  `rows` is 8 (one f32
sublane group), or all of `n_tiles` when there are fewer; the last block
dims are always the full `tile` / `qcap` extents, which is what Mosaic
requires of a block that is not (8, 128)-aligned.  Ragged tile counts are
padded with +inf key rows and invalid query rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 8        # key tiles per grid step: one f32 sublane group
_CHUNK = 128    # key columns per loop iteration: one lane group


def _count_le(keys, q):
    """[rows, c] keys, [rows, qcap] queries -> #(keys <= q) per query."""
    cnt = jnp.zeros(q.shape, jnp.int32)
    for j in range(keys.shape[1]):              # static: one lane column
        cnt += (keys[:, j:j + 1] <= q).astype(jnp.int32)
    return cnt


def _probe_kernel(keys_ref, q_ref, valid_ref, out_ref, *, tile: int):
    q = q_ref[...]                              # [rows, qcap] f32
    if tile <= _CHUNK:
        cnt = _count_le(keys_ref[...], q)
    else:
        def body(c, acc):
            start = pl.multiple_of(c * _CHUNK, _CHUNK)
            return acc + _count_le(keys_ref[:, pl.ds(start, _CHUNK)], q)
        cnt = jax.lax.fori_loop(0, tile // _CHUNK, body,
                                jnp.zeros(q.shape, jnp.int32))
    out_ref[...] = jnp.where(valid_ref[...] > 0, cnt, -1)


def probe_pallas(key_tiles: jax.Array, queries: jax.Array,
                 valid: jax.Array, interpret: bool = True) -> jax.Array:
    """key_tiles [n_tiles, tile] sorted per tile; queries/valid
    [n_tiles, qcap] -> in-tile predecessor counts [n_tiles, qcap] int32
    (-1 where not valid)."""
    n_tiles, tile = key_tiles.shape
    qcap = queries.shape[1]
    assert tile & (tile - 1) == 0, "tile must be a power of two"
    rows = min(n_tiles, ROWS)
    pad = -n_tiles % rows
    valid = valid.astype(jnp.int32)
    if pad:
        key_tiles = jnp.pad(key_tiles, ((0, pad), (0, 0)),
                            constant_values=jnp.inf)
        queries = jnp.pad(queries, ((0, pad), (0, 0)))
        valid = jnp.pad(valid, ((0, pad), (0, 0)))
    kern = functools.partial(_probe_kernel, tile=tile)
    out = pl.pallas_call(
        kern,
        grid=((n_tiles + pad) // rows,),
        in_specs=[
            pl.BlockSpec((rows, tile), lambda i: (i, 0)),
            pl.BlockSpec((rows, qcap), lambda i: (i, 0)),
            pl.BlockSpec((rows, qcap), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, qcap), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles + pad, qcap), jnp.int32),
        interpret=interpret,
    )(key_tiles, queries, valid)
    return out[:n_tiles]
