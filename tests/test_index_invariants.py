"""Property-based tests (hypothesis) on the learned-index substrate."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.spaces import alex_space, carmi_space
from repro.index import alex, carmi
from repro.index import linear_model as lm
from repro.index.workloads import sample_keys, wr_workload

SPACE = alex_space()


def _params(overrides=None):
    p = {k: jnp.float32(v) for k, v in alex.DEFAULTS.items()}
    p.update({k: jnp.float32(v) for k, v in (overrides or {}).items()})
    return p


# ------------------------------------------------------------------ fits
@settings(max_examples=20, deadline=None)
@given(st.integers(10, 400), st.integers(1, 8), st.integers(0, 10_000))
def test_linear_fit_perfect_on_linear_data(n, n_segs, seed):
    """On exactly-linear data the exact fit has ~zero error bound."""
    key = jax.random.PRNGKey(seed)
    keys = jnp.sort(jax.random.uniform(key, (n,)))
    keys = jnp.linspace(0.1, 0.9, n)  # perfectly linear CDF
    seg = jnp.minimum((jnp.arange(n) * n_segs) // n, n_segs - 1).astype(
        jnp.int32)
    slope, icpt, cnt = lm.fit_segments_exact(keys, seg, n_segs)
    err = lm.segment_errors(keys, seg, n_segs, slope, icpt)
    assert float(jnp.max(err)) < 1e-2


@settings(max_examples=15, deadline=None)
@given(st.integers(50, 500), st.integers(2, 16), st.integers(0, 10_000))
def test_fit_error_bound_nonnegative_and_bounded(n, n_segs, seed):
    keys = jnp.sort(jax.random.uniform(jax.random.PRNGKey(seed), (n,)))
    seg = jnp.minimum((jnp.arange(n) * n_segs) // n, n_segs - 1).astype(
        jnp.int32)
    slope, icpt, cnt = lm.fit_segments_exact(keys, seg, n_segs)
    err = lm.segment_errors(keys, seg, n_segs, slope, icpt)
    assert float(jnp.min(err)) >= 0.0
    assert float(jnp.max(err)) <= n  # can't be worse than the segment size


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_approx_fit_never_better_than_exact_on_average(seed):
    key = jax.random.PRNGKey(seed)
    keys = sample_keys(key, 1024, "fb")
    seg = jnp.minimum(jnp.arange(1024) * 8 // 1024, 7).astype(jnp.int32)
    s_e, i_e, _ = lm.fit_segments_exact(keys, seg, 8)
    s_a, i_a, _ = lm.fit_segments_approx(keys, seg, 8)
    err_e = lm.segment_errors(keys, seg, 8, s_e, i_e)
    err_a = lm.segment_errors(keys, seg, 8, s_a, i_a)
    assert float(jnp.mean(err_a)) >= float(jnp.mean(err_e)) - 1.0


def _segment_ids(keys, n_segs, kind):
    """Rank-based (equal counts), equi-width (equal key ranges: empty
    segments where the keys thin out) or every other id (half empty)."""
    n = keys.shape[0]
    if kind == "width":
        w = (keys - keys[0]) / (keys[-1] - keys[0]) * n_segs
        return np.minimum(w, n_segs - 1).astype(np.int32)
    seg = np.minimum(np.arange(n) * n_segs // n, n_segs - 1)
    if kind == "sparse":
        seg = np.minimum(2 * (seg // 2), n_segs - 1)
    return seg.astype(np.int32)


@pytest.mark.parametrize("n,n_segs,kind", [
    (1000, 1, "rank"), (70001, 1, "rank"), (3001, 8, "rank"),
    (3001, 8, "width"), (70001, 512, "rank"), (70001, 512, "width"),
    (5000, 1024, "rank"), (20000, 1024, "width"), (2048, 1024, "sparse")])
def test_segment_sums_and_counts_match_float64(n, n_segs, kind):
    """The fits' per-segment sums (one-hot contractions over blocks of
    keys, three bfloat16 pieces per float32 term) within 1e-6 of float64
    sums, relative to each segment's sum of magnitudes; counts exact.
    `n` is never a whole number of blocks here."""
    rng = np.random.default_rng(n * n_segs)
    keys = np.sort(rng.lognormal(0.0, 2.0, n)).astype(np.float32)
    keys = (keys - keys[0]) / (keys[-1] - keys[0])
    seg = _segment_ids(keys, n_segs, kind)
    terms = [keys, keys * keys, (keys - 0.01) * rng.normal(size=n)]
    terms = [t.astype(np.float32) for t in terms]
    got = jax.jit(lambda s, *t: lm._segment_sums(s, n_segs, *t))(seg, *terms)
    for g, t in zip(got, terms):
        want = np.zeros(n_segs)
        np.add.at(want, seg, t.astype(np.float64))
        scale = np.zeros(n_segs)
        np.add.at(scale, seg, np.abs(t.astype(np.float64)))
        assert np.all(np.abs(np.asarray(g, np.float64) - want)
                      <= 1e-6 * scale)
    want_cnt = np.bincount(seg, minlength=n_segs)
    np.testing.assert_array_equal(
        np.asarray(lm._segment_counts(jnp.asarray(seg), n_segs,
                                      jnp.float32)), want_cnt)
    _, _, cnt = lm.fit_segments_exact(jnp.asarray(keys), jnp.asarray(seg),
                                      n_segs)
    np.testing.assert_array_equal(np.asarray(cnt), want_cnt)
    if kind != "rank":
        assert np.any(want_cnt == 0)


@pytest.mark.parametrize("fit", ["leaves", "root"])
def test_fits_lower_without_scatter(fit):
    """Neither the leaf fit (2^14 keys, 512 segments) nor the root fit
    takes a scatter: the TPU runs a scatter-add one key after another."""
    n = 1 << 14
    keys = jax.ShapeDtypeStruct((n,), jnp.float32)
    if fit == "root":
        lowered = jax.jit(lm.fit_root).lower(keys)
    else:
        seg = jax.ShapeDtypeStruct((n,), jnp.int32)
        lowered = jax.jit(lambda k, s: lm.fit_segments_exact(k, s, 512)
                          ).lower(keys, seg)
    assert "scatter" not in lowered.as_text()


# ------------------------------------------------------------------ alex
def test_alex_search_exact_on_uniform(rng_key):
    """Near-linear data + exact fits => tiny search distances."""
    keys = jnp.linspace(0.0, 1.0, 2048)
    idx = alex.build(keys, _params())
    _, m = alex.run_reads(idx, keys[100:200])
    assert float(m["avg_search_dist"]) < 2.0


def test_alex_skewed_data_larger_distance(rng_key):
    uni = jnp.linspace(0.0, 1.0, 2048)
    skew = sample_keys(rng_key, 2048, "fb")
    p = _params({"fanout_selection_method": 1})  # equi-width fanout
    d_uni = float(alex.run_reads(alex.build(uni, p), uni[:256])[1]
                  ["avg_search_dist"])
    d_skew = float(alex.run_reads(alex.build(skew, p), skew[:256])[1]
                   ["avg_search_dist"])
    assert d_skew > d_uni


def test_alex_insert_monotonic_counters(small_index_instance):
    data, workload = small_index_instance
    idx = alex.build(data, _params())
    idx2, ns, m = alex.run_inserts(idx, workload["inserts"], _params())
    assert float(ns) > 0
    assert float(jnp.sum(idx2["cnt"])) >= float(jnp.sum(idx["cnt"]))
    assert float(idx2["counters"]["n_retrains"]) >= 0


def test_alex_dangerous_zone_memory():
    """Fig 11: aggressive ood thresholds with equi-width+upward splitting
    blow the memory budget."""
    from repro.index import cost as C
    keys = jnp.linspace(0.0, 1.0, 2048)
    danger = _params({"fanout_selection_method": 1,
                      "splitting_policy_method": 1,
                      "allow_splitting_upwards": 1,
                      "kmax_ood_keys_log2": 14,
                      "ood_tolerance_factor": 48})
    idx = alex.build(keys, danger)
    assert float(alex.memory_bytes(idx, danger)) > C.MEM_BUDGET_BYTES
    safe = _params()
    assert float(alex.memory_bytes(alex.build(keys, safe), safe)) \
        < C.MEM_BUDGET_BYTES


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_alex_runtime_positive_any_params(seed):
    rng = np.random.default_rng(seed)
    raw = SPACE.random_raw(rng)
    p = {k: jnp.float32(v) for k, v in raw.items()}
    keys = jnp.linspace(0.0, 1.0, 512)
    idx = alex.build(keys, p)
    ns, m = alex.run_reads(idx, keys[:64])
    assert np.isfinite(float(ns)) and float(ns) > 0


# ------------------------------------------------------------------ carmi
def test_carmi_prefetch_helps_predictable_data():
    keys = jnp.linspace(0.0, 1.0, 2048)
    p0 = {k: jnp.float32(v) for k, v in carmi.DEFAULTS.items()}
    p1 = dict(p0, prefetch_aggr=jnp.float32(1.0))
    ns0, _ = carmi.run_reads(carmi.build(keys, p0), keys[:256], p0)
    ns1, _ = carmi.run_reads(carmi.build(keys, p1), keys[:256], p1)
    assert float(ns1) < float(ns0)


def test_carmi_lambda_spacetime_tradeoff():
    keys = jnp.linspace(0.0, 1.0, 2048)
    p_time = {**{k: jnp.float32(v) for k, v in carmi.DEFAULTS.items()},
              "lambda_spacetime": jnp.float32(0.0)}   # snaps to time-only
    p_space = {**{k: jnp.float32(v) for k, v in carmi.DEFAULTS.items()},
               "lambda_spacetime": jnp.float32(1.0)}
    m_time = float(carmi.memory_bytes(carmi.build(keys, p_time)))
    m_space = float(carmi.memory_bytes(carmi.build(keys, p_space)))
    assert m_time > m_space  # time-mode spends memory (lower density)


# ------------------------------------------------------------------ spaces
@settings(max_examples=30, deadline=None)
@given(st.integers(0, 100_000))
def test_space_encode_decode_roundtrip(seed):
    rng = np.random.default_rng(seed)
    for space in (alex_space(), carmi_space()):
        raw = space.random_raw(rng)
        a = space.encode(raw)
        back = {k: float(v) for k, v in space.decode(jnp.asarray(a)).items()}
        for i, name in enumerate(space.names):
            if space.kinds[i] in ("int", "choice", "bool"):
                assert abs(back[name] - raw[name]) <= 0.5 + 1e-4, name
            else:
                rangei = float(space.highs[i] - space.lows[i])
                assert abs(back[name] - raw[name]) <= 0.02 * rangei + 1e-5


def test_table2_dimensions():
    """Table 2: ALEX 14 dims (5 cont/3 bool/4 int/2 choice); CARMI 13."""
    sa = alex_space()
    assert sa.dim == 14
    from collections import Counter
    ca = Counter(sa.kinds)
    assert ca["cont"] == 5 and ca["bool"] == 3 and ca["int"] == 4 \
        and ca["choice"] == 2
    sc = carmi_space()
    assert sc.dim == 13
    cc = Counter(sc.kinds)
    assert cc["cont"] == 10 and cc["int"] == 2 and cc["hybrid"] == 1
