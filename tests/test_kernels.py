"""Per-kernel correctness sweeps: Pallas (interpret=True, kernel body
executed on CPU) vs the pure-jnp ref oracle, across shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.index_probe.kernel import probe_pallas
from repro.kernels.index_probe.ops import (auto_ocap, auto_qcap,
                                           auto_tile, batched_lookup,
                                           predecessor_positions,
                                           probe_ranks)
from repro.kernels.dispatch import KernelConfig
from repro.kernels.index_probe.ref import probe_ref
from repro.kernels.mamba_scan.kernel import selective_scan
from repro.kernels.mamba_scan.ref import selective_scan_ref

# compiled Pallas rows only run where a compiled backend exists; on CPU
# CI they skip-mark (the interpret rows execute the same kernel body)
requires_accel = pytest.mark.skipif(
    jax.default_backend() not in ("gpu", "tpu"),
    reason="compiled Pallas path needs an accelerator backend")

MODES = ["ref", "interpret",
         pytest.param("compiled", marks=requires_accel)]


# ------------------------------------------------------------ index probe
@pytest.mark.parametrize("n_tiles,tile,qcap", [
    (4, 128, 32), (8, 256, 16), (2, 512, 64), (16, 64, 8), (12, 256, 128)])
def test_probe_matches_ref(n_tiles, tile, qcap, rng_key):
    keys = jnp.sort(jax.random.uniform(rng_key, (n_tiles * tile,))
                    ).reshape(n_tiles, tile)
    k2 = jax.random.fold_in(rng_key, 1)
    queries = jax.random.uniform(k2, (n_tiles, qcap))
    valid = jax.random.uniform(jax.random.fold_in(k2, 3),
                               (n_tiles, qcap)) < 0.8
    got = probe_pallas(keys, queries, valid.astype(jnp.int32))
    want = probe_ref(keys, queries, valid)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_probe_boundary_queries(rng_key):
    keys = jnp.linspace(0.0, 1.0, 256).reshape(1, 256)
    queries = jnp.array([[-1.0, 0.0, 0.5, 1.0, 2.0, keys[0, 7], 0.25, 0.75]])
    valid = jnp.ones((1, 8), bool)
    got = probe_pallas(keys, queries, valid.astype(jnp.int32))
    want = probe_ref(keys, queries, valid)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([128, 256]))
def test_batched_lookup_end_to_end(seed, tile):
    """End-to-end op: global ranks equal searchsorted on the full array."""
    key = jax.random.PRNGKey(seed)
    n = 8 * tile
    keys = jnp.sort(jax.random.uniform(key, (n,)))
    queries = jax.random.uniform(jax.random.fold_in(key, 1), (64,))
    ranks, dropped = batched_lookup(keys, queries, tile=tile, qcap=64,
                                    mode="interpret")
    want = jnp.searchsorted(keys, queries, side="right").astype(jnp.int32)
    kept = ~dropped
    np.testing.assert_array_equal(np.asarray(ranks)[np.asarray(kept)],
                                  np.asarray(want)[np.asarray(kept)])
    assert float(jnp.mean(kept)) > 0.9  # capacity ample here


# ------------------------------------------------------------ flash attn
@pytest.mark.parametrize("b,h,s,d,dtype", [
    (2, 2, 256, 64, jnp.float32),
    (1, 4, 128, 128, jnp.float32),
    (2, 1, 512, 32, jnp.bfloat16),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_matches_ref(b, h, s, d, dtype, causal, window, rng_key):
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (b, h, s, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, h, s, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, h, s, d)).astype(dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=128, block_k=128, interpret=True)
    want = attention_ref(q, k, v, causal=causal, window=window)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_flash_matches_model_attention(rng_key):
    """The kernel agrees with the model stack's streaming-softmax jnp path."""
    from repro.models.attention import flash_attention_jnp
    b, s, h, d = 2, 256, 4, 64
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)
    want = flash_attention_jnp(q, k, v, causal=True)
    got = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal=True,
                          interpret=True).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ------------------------------------------------------------ mamba scan
@pytest.mark.parametrize("b,s,di,n,chunk", [
    (2, 128, 64, 16, 32), (1, 256, 256, 16, 256), (2, 64, 512, 8, 64)])
def test_mamba_scan_matches_ref(b, s, di, n, chunk, rng_key):
    ks = jax.random.split(rng_key, 4)
    u = jax.random.normal(ks[0], (b, s, di), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, di)) - 1.0)
    b_mat = jax.random.normal(ks[2], (b, s, n), jnp.float32)
    c_mat = jax.random.normal(ks[3], (b, s, n), jnp.float32)
    a = -jnp.exp(jax.random.normal(rng_key, (di, n)) * 0.5)
    got = selective_scan(u, dt, b_mat, c_mat, a, chunk=chunk, di_block=128,
                         interpret=True)
    want = selective_scan_ref(u, dt, b_mat, c_mat, a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_mamba_scan_matches_model_block(rng_key):
    """The kernel recurrence equals the model's chunked _scan_chunk path."""
    from repro.models.mamba import _scan_chunk
    b, s, di, n = 2, 64, 32, 8
    ks = jax.random.split(rng_key, 4)
    u = jax.random.normal(ks[0], (b, s, di), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, di)))
    b_mat = jax.random.normal(ks[2], (b, s, n), jnp.float32)
    c_mat = jax.random.normal(ks[3], (b, s, n), jnp.float32)
    a = -jnp.exp(jax.random.normal(rng_key, (di, n)) * 0.3)
    h0 = jnp.zeros((b, di, n), jnp.float32)
    _, want = _scan_chunk(h0, u, dt, b_mat, c_mat, a)
    got = selective_scan(u, dt, b_mat, c_mat, a, chunk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


# ------------------------------------------------- capacity path (dispatch)
def test_batched_lookup_capacity_overflow_flags_dropped(rng_key):
    """Queries beyond a tile's `qcap` come back flagged `dropped` with
    rank -1 — never a silently-wrong rank."""
    tile = 128
    keys = jnp.sort(jax.random.uniform(rng_key, (8 * tile,)))
    # cram 32 queries into tile 0's key range with qcap=4 -> overflow
    queries = jnp.linspace(float(keys[1]), float(keys[tile - 2]), 32)
    ranks, dropped = batched_lookup(keys, queries, tile=tile, qcap=4,
                                    mode="interpret")
    ranks, dropped = np.asarray(ranks), np.asarray(dropped)
    assert dropped.sum() == 32 - 4          # exactly qcap survive
    assert np.all(ranks[dropped] == -1)
    want = np.asarray(jnp.searchsorted(keys, queries, side="right"))
    np.testing.assert_array_equal(ranks[~dropped], want[~dropped])


def test_batched_lookup_capacity_retry_recovers(rng_key):
    """The caller contract: retrying with a larger `qcap` clears the drops
    and recovers the reference ranks (probe_ref path and searchsorted)."""
    tile = 128
    keys = jnp.sort(jax.random.uniform(rng_key, (8 * tile,)))
    queries = jnp.linspace(float(keys[1]), float(keys[tile - 2]), 32)
    _, dropped = batched_lookup(keys, queries, tile=tile, qcap=4,
                                mode="interpret")
    assert bool(np.asarray(dropped).any())
    # retry the same batch with ample capacity
    ranks2, dropped2 = batched_lookup(keys, queries, tile=tile, qcap=32,
                                      mode="interpret")
    assert not bool(np.asarray(dropped2).any())
    ref_ranks, ref_dropped = batched_lookup(keys, queries, tile=tile,
                                            qcap=32, mode="ref")
    assert not bool(np.asarray(ref_dropped).any())
    np.testing.assert_array_equal(np.asarray(ranks2), np.asarray(ref_ranks))
    want = np.asarray(jnp.searchsorted(keys, queries, side="right"))
    np.testing.assert_array_equal(np.asarray(ranks2), want)


# ------------------------------------- tri-mode dispatch parity (ISSUE 10)
@pytest.mark.parametrize("mode", MODES)
@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([64, 128, 256]),
       st.sampled_from(["float32", "float64"]))
def test_batched_lookup_mode_parity(mode, seed, tile, dtype):
    """Every execution mode agrees with searchsorted on duplicate-heavy
    keys and out-of-range queries, across tiles, dtypes and ragged key
    counts."""
    key = jax.random.PRNGKey(seed)
    # whole tiles and a ragged tail (padded with +inf keys)
    n = 8 * tile - seed % 3 * 5
    # integer-valued keys -> runs of duplicates, some spanning tiles
    keys = jnp.sort(jax.random.randint(key, (n,), 0, n // 4)
                    ).astype(dtype)
    k2 = jax.random.fold_in(key, 1)
    # queries stretched past both ends of the key range
    queries = (jax.random.uniform(k2, (96,), jnp.float32)
               * (n // 4) * 1.5 - (n // 8)).astype(dtype)
    ranks, dropped = batched_lookup(keys, queries, tile=tile,
                                    qcap=queries.shape[0], mode=mode)
    assert not bool(np.asarray(dropped).any())   # qcap=m is drop-free
    want = jnp.searchsorted(keys, queries, side="right").astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(ranks), np.asarray(want))


@pytest.mark.parametrize("mode", MODES)
@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([512, 768, 2048, 1001]))
def test_predecessor_positions_mode_parity(mode, seed, n):
    """The env-facing probe equals clip(searchsorted-1) in every mode —
    including n=768 and a ragged n=1001, which pad their last 512-key
    tile with +inf keys."""
    key = jax.random.PRNGKey(seed)
    keys = jnp.sort(jax.random.randint(key, (n,), 0, n // 2)
                    ).astype(jnp.float32)
    q = (jax.random.uniform(jax.random.fold_in(key, 1), (64,))
         * (n // 2) * 1.5 - (n // 8))
    got = predecessor_positions(keys, q, kernel=KernelConfig(mode=mode))
    want = jnp.clip(jnp.searchsorted(keys, q, side="right") - 1, 0, n - 1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_predecessor_positions_ragged_falls_back(rng_key):
    """Array lengths with no usable pow2 divisor no longer fall back to
    searchsorted under a Pallas mode: the last tile is padded with +inf
    keys and the kernel answers (still exact)."""
    keys = jnp.sort(jax.random.uniform(rng_key, (1001,)))   # odd length
    q = jax.random.uniform(jax.random.fold_in(rng_key, 1), (32,))
    assert auto_tile(1001) == 512                # 2 tiles, 23 pad keys
    # one rule for every n: 512-key tiles (a power of two covering a
    # smaller n), never a small divisor of n
    assert auto_tile(8 * 125_001) == auto_tile(768) == 512
    assert (auto_tile(5), auto_tile(100), auto_tile(256)) == (8, 128, 256)
    got = predecessor_positions(keys, q, kernel=KernelConfig(mode="interpret"))
    want = jnp.clip(jnp.searchsorted(keys, q, side="right") - 1, 0, 1000)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    ranks, n_over = probe_ranks(keys, q, tile=512, mode="interpret")
    assert int(n_over) == 0                      # every query in-kernel
    np.testing.assert_array_equal(
        np.asarray(ranks), np.asarray(jnp.searchsorted(keys, q, side="right")))
    # probe_reads=False forces the reference regardless of mode
    got_off = predecessor_positions(
        jnp.sort(jax.random.uniform(rng_key, (1024,))), q,
        kernel=KernelConfig(mode="interpret", probe_reads=False))
    keys2 = jnp.sort(jax.random.uniform(rng_key, (1024,)))
    want2 = jnp.clip(jnp.searchsorted(keys2, q, side="right") - 1, 0, 1023)
    np.testing.assert_array_equal(np.asarray(got_off), np.asarray(want2))


@pytest.mark.parametrize("n_crammed", [200, 400])
@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_probe_ranks_resolves_overflow_exactly(mode, n_crammed, rng_key):
    """Queries beyond a tile's bounded capacity are answered by
    searchsorted in overflow chunks and counted; every rank stays exact —
    in one chunk (200 crammed) and in several (400 crammed)."""
    tile = 128
    keys = jnp.sort(jax.random.uniform(rng_key, (8 * tile,)))
    # queries crammed into tile 0's range, 40 spread: qcap=128 drops
    crammed = jnp.linspace(float(keys[1]), float(keys[tile - 2]),
                           n_crammed)
    spread = jax.random.uniform(jax.random.fold_in(rng_key, 2), (40,))
    q = jnp.concatenate([crammed, spread])
    _, dropped = batched_lookup(keys, q, tile=tile, qcap=128, mode=mode)
    ranks, n_over = probe_ranks(keys, q, tile=tile, qcap=128, mode=mode)
    assert int(n_over) == int(np.asarray(dropped).sum()) >= n_crammed - 128
    # one chunk holds the overflow, or it takes several
    assert (int(n_over) <= auto_ocap(q.shape[0])) == (n_crammed == 200)
    np.testing.assert_array_equal(
        np.asarray(ranks), np.asarray(jnp.searchsorted(keys, q, side="right")))


@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_probe_ranks_nonfinite_queries(mode):
    """NaN queries rank after every key (searchsorted's order), +/-inf
    queries at the ends, with and without +inf pad keys."""
    keys = jnp.sort(jnp.concatenate([jnp.linspace(-1.0, 1.0, 250),
                                     jnp.array([jnp.inf] * 3)]))
    q = jnp.array([jnp.nan, jnp.inf, -jnp.inf, 0.0, -0.0, 1.0, 5.0])
    ranks, _ = probe_ranks(keys, q, tile=128, mode=mode)
    np.testing.assert_array_equal(
        np.asarray(ranks), np.asarray(jnp.searchsorted(keys, q, side="right")))


def test_auto_qcap_keeps_grouped_buffers_linear():
    """At 2^20 keys the grouped buffers are O(n + m), not O(n * m): the
    buffers that used to take 6 GiB each at the read-heavy mix."""
    n_tiles = (1 << 20) // auto_tile(1 << 20)
    for m in (786_432, 524_288, 262_144):
        qcap = auto_qcap(m, n_tiles)
        assert qcap % 128 == 0
        assert n_tiles * qcap <= 2 * m + 128 * n_tiles
