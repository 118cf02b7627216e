"""Jitted public API for the learned-index probe kernel, including the
model-routing dispatch (query -> tile grouping) that precedes the kernel.

`batched_lookup` is the capacity-bounded op: (sorted keys, queries) ->
global predecessor ranks, using a boundary-key root route + a
capacity-grouped tile dispatch + the Pallas in-VMEM compare-and-count
kernel.  Queries beyond a tile's capacity come back flagged `dropped`.
Execution mode (compiled / interpret / jnp ref) routes through
`kernels/dispatch.py` — ``mode=None`` defers to the process-wide
resolution, so CPU callers get the bitwise jnp reference and accelerator
callers the compiled kernel without any per-callsite flags.

`probe_ranks` is the exact op built on it: the grouped buffers stay
O(n + m) (`qcap` is about twice a tile's mean query load), and the
queries that overflow a tile are answered by `searchsorted` in bounded
chunks (`auto_ocap`, an eighth of the queries), addressed through the
per-tile overflow counts.  The overflow is counted, so a caller can
report the share the kernel answered.

`predecessor_positions` is the env-facing wrapper the index simulators'
``run_reads`` hot paths call: predecessor *positions* (clipped rank-1),
numerically equal to ``clip(searchsorted(side="right") - 1, 0, n-1)`` on
every input (tests/test_kernels.py asserts the parity property-based).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.index_probe.kernel import probe_pallas
from repro.kernels.index_probe.ref import probe_ref


def _group_order(tile_of, n_tiles: int):
    """The stable argsort of `tile_of`, as one unstable sort of packed
    uint32 `(tile, index)` keys where they fit: the keys are unique, so
    the order is the stable one, and the TPU compiler builds a
    single-operand integer sort in a fraction of the time it takes for
    an argsort (about 5 s against 24 s for 2^20 elements on v5e)."""
    m = tile_of.shape[0]
    b = max(m - 1, 1).bit_length()
    if (n_tiles - 1).bit_length() + b > 32:
        return jnp.argsort(tile_of)
    packed = (tile_of.astype(jnp.uint32) << b) | jnp.arange(m,
                                                            dtype=jnp.uint32)
    low = jnp.uint32((1 << b) - 1)
    return (jax.lax.sort(packed, is_stable=False) & low).astype(jnp.int32)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def auto_qcap(m: int, n_tiles: int) -> int:
    """Per-tile query capacity: twice the mean tile load, lane-aligned —
    the grouped buffers are then `n_tiles * qcap ~ 2m + 128 n_tiles`."""
    return _round_up(max(2 * -(-m // n_tiles), 8), 128)


def _grouped_ranks(keys, queries, tile: int, qcap: int, mode: str):
    """The capacity-grouped probe, in tile-sorted query order.

    Returns (order [m], ranks [m] with -1 where dropped, keep [m],
    sorted queries [m], per-tile counts and starts [n_tiles], qcap):
    position j of every [m] array is query `order[j]`."""
    mode = dispatch.resolve(mode)
    n = keys.shape[0]
    m = queries.shape[0]
    n_tiles = -(-n // tile)
    qcap = qcap or auto_qcap(m, n_tiles)
    keys = keys.astype(jnp.float32)
    if n_tiles * tile != n:
        keys = jnp.pad(keys, (0, n_tiles * tile - n),
                       constant_values=jnp.inf)
    key_tiles = keys.reshape(n_tiles, tile)
    # searchsorted orders NaN after +inf: a NaN query's rank is n
    queries = queries.astype(jnp.float32)
    queries = jnp.where(jnp.isnan(queries), jnp.inf, queries)

    # root routing: tile of the predecessor via boundary keys
    boundaries = key_tiles[:, 0]
    tile_of = jnp.clip(
        jnp.searchsorted(boundaries, queries, side="right") - 1, 0,
        n_tiles - 1).astype(jnp.int32)

    # capacity-grouped dispatch: queries sorted by tile, each tile's
    # first qcap gathered into its row (slots past its count are invalid)
    order = _group_order(tile_of, n_tiles)
    t_sorted = tile_of[order]
    q_sorted = queries[order]
    starts = jnp.searchsorted(t_sorted, jnp.arange(n_tiles))
    counts = jnp.diff(starts, append=m)
    pos_in_tile = jnp.arange(m) - starts[t_sorted]
    keep = pos_in_tile < qcap
    slot = jnp.arange(qcap)
    v_grouped = slot[None, :] < counts[:, None]             # [T, qcap]
    src = jnp.minimum(starts[:, None] + slot[None, :], m - 1)
    q_grouped = jnp.where(v_grouped, q_sorted[src], -jnp.inf)

    if mode == "ref":
        pos = probe_ref(key_tiles, q_grouped, v_grouped)
    else:
        pos = probe_pallas(key_tiles, q_grouped, v_grouped,
                           interpret=dispatch.interpret_flag(mode))

    # global rank = tile_start + local rank (dropped entries read a
    # clamped slot and are masked to -1; the min drops +inf pad keys an
    # +inf query counted)
    local = pos[t_sorted, jnp.minimum(pos_in_tile, qcap - 1)]
    ranks = jnp.where(keep, jnp.minimum(t_sorted * tile + local, n), -1)
    return order, ranks, keep, q_sorted, counts, starts, qcap


@partial(jax.jit, static_argnames=("tile", "qcap", "mode"))
def batched_lookup(keys: jax.Array, queries: jax.Array, tile: int = 512,
                   qcap: int = 0, mode: str | None = None):
    """keys [n] sorted; queries [m].

    Returns (ranks [m] int32, dropped [m] bool).  `dropped` marks queries
    beyond a tile's query capacity (rank -1; `probe_ranks` resolves
    them — same contract as MoE capacity dispatch).  A ragged `n` is
    padded to whole tiles with +inf keys.  `mode` is a `kernels.dispatch`
    mode (None/"auto" -> the process default; resolution is
    process-cached, so the static jit key stays stable).
    """
    order, ranks, keep, *_ = _grouped_ranks(keys, queries, tile, qcap, mode)
    m = queries.shape[0]
    return (jnp.zeros((m,), jnp.int32).at[order].set(ranks),
            jnp.zeros((m,), bool).at[order].set(~keep))


def auto_ocap(m: int) -> int:
    """Overflow chunk: an eighth of the queries, lane-aligned.  Queries
    that hit keys spread over tiles like the keys do and stay within
    `qcap`; those that pile into one tile are misses landing in a gap
    between keys, and the SOSD-style workloads send a tenth of their
    reads as misses (`index/workloads.py`), so one chunk holds them."""
    return min(m, _round_up(max(m // 8, 128), 128))


@partial(jax.jit, static_argnames=("tile", "qcap", "mode"))
def probe_ranks(keys: jax.Array, queries: jax.Array, tile: int = 512,
                qcap: int = 0, mode: str | None = None):
    """Exact ranks #(keys <= q) through the grouped probe.  Queries that
    overflowed their tile's capacity are answered by `searchsorted` in
    chunks of `auto_ocap(m)`: chunk slot s maps to its sorted position
    through the per-tile overflow counts, so no [m]-wide compaction or
    search runs, and no chunk runs when none overflowed.
    Returns (ranks [m] int32, n_overflow int32)."""
    order, ranks, _, q_sorted, counts, starts, qcap = _grouped_ranks(
        keys, queries, tile, qcap, mode)
    m = queries.shape[0]
    ocap = auto_ocap(m)
    keys = keys.astype(jnp.float32)
    over = jnp.maximum(counts - qcap, 0)                    # [T]
    ends = jnp.cumsum(over)
    n_over = ends[-1].astype(jnp.int32)

    def chunk(c, r):
        s = c * ocap + jnp.arange(ocap)
        t = jnp.minimum(jnp.searchsorted(ends, s, side="right"),
                        counts.shape[0] - 1)
        j = jnp.where(s < n_over, starts[t] + qcap + s - (ends[t] - over[t]),
                      m)                                    # m: dropped
        q = q_sorted.at[j].get(mode="fill", fill_value=0)
        return r.at[j].set(jnp.searchsorted(keys, q, side="right"),
                           mode="drop")

    ranks = jax.lax.fori_loop(0, (n_over + ocap - 1) // ocap, chunk, ranks)
    return jnp.zeros((m,), jnp.int32).at[order].set(ranks), n_over


def auto_tile(n: int, cap: int = 512) -> int:
    """The key-tile size the kernel grids over: the smallest power of two
    covering n, capped at `cap` (and at least 8); `batched_lookup` pads
    the last tile with +inf keys."""
    return min(cap, max(8, 1 << (n - 1).bit_length()))


def predecessor_positions(keys: jax.Array, queries: jax.Array,
                          kernel=None) -> jax.Array:
    """Predecessor positions clip(#(keys <= q) - 1, 0, n-1) — the probe
    at the bottom of every `run_reads` hot path.

    `kernel` is a `dispatch.KernelConfig` (None -> defaults).  Pallas
    modes route through `probe_ranks` (exact: capacity overflow is
    resolved, not dropped) and are numerically equal to the searchsorted
    reference; "ref" mode *is* the searchsorted reference, and the only
    path that runs it for every query.
    """
    n = keys.shape[0]
    kcfg = kernel if kernel is not None else dispatch.KernelConfig()
    mode = kcfg.resolved() if kcfg.probe_reads else "ref"
    if mode == "ref":
        rank = jnp.searchsorted(keys, queries, side="right")
    else:
        rank, _ = probe_ranks(keys, queries,
                              tile=kcfg.probe_tile or auto_tile(n),
                              mode=mode)
    return jnp.clip(rank - 1, 0, n - 1)
