"""`index/order.py`: the int32-keyed sort and percentile are bitwise
`jnp.sort` / `jnp.percentile` on the arrays the env sorts (no -0.0),
eagerly and inside a jitted program."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.index import order


def _bits(x):
    return np.asarray(x, np.float32).view(np.int32)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 3000),
       st.sampled_from([1e-3, 1.0, 1e6]), st.booleans(), st.booleans())
def test_sort_and_percentile_bitwise(seed, n, scale, nonneg, specials):
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal(n) * scale).astype(np.float32)
    x[rng.integers(0, n, n // 4)] = 0.0              # ties
    if specials:
        x[rng.integers(0, n, 2)] = np.inf
        x[rng.integers(0, n, 1)] = -np.inf
    if nonneg:
        x = np.abs(x)
    xs = jnp.asarray(x)
    np.testing.assert_array_equal(_bits(order.sort(xs)),
                                  _bits(jnp.sort(xs)))
    for q in (99, 50, 0, 100, 37.5):
        got, want = order.percentile(xs, q), jnp.percentile(xs, q)
        if np.isnan(want):
            assert np.isnan(got)
        else:
            assert _bits(got) == _bits(want), q


@pytest.mark.parametrize("n", [1, 7, 4096])
def test_percentile_bitwise_under_jit(n):
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(n), (n,)))
    got = jax.jit(lambda v: order.percentile(v * 2.0, 99))(x)
    want = jax.jit(lambda v: jnp.percentile(v * 2.0, 99))(x)
    assert _bits(got) == _bits(want)


def test_percentile_nan_propagates():
    x = jnp.array([1.0, jnp.nan, 3.0])
    assert np.isnan(order.percentile(x, 99))
    assert np.isnan(jnp.percentile(x, 99))
