"""The environment's answer against a float64 reference, at 2^17 keys.

`benchmarks/chip/reference.py` computes a tuning step's answer (ns per
operation of an index built over a request's keys, under given
parameters) in plain NumPy float64, importing nothing of the program.
Here the program's float32 `env.evaluate_params` is held to it on
requests from the benchmark's own generator (`benchmarks/chip/
traffic.py`): CARMI over clustered `osm` keys and ALEX over the `mix`,
at W/R 1/3, under the default parameters and two seeded random actions.

Tolerance 1e-4 relative: with each leaf fitted about its own first key
and mean rank, its sums accumulated in blocks, the program reads at
most 9.9e-6 on these requests (CARMI; ALEX 1.7e-7), while one-pass
float32 sums about zero cancel over CARMI's narrow `osm` leaves and
read 0.12-0.52 (ALEX up to 1.4e-3).
"""
import functools
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.index import alex, carmi
from repro.index import env as E
from repro.index import linear_model as lm

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
N_KEYS = 1 << 17
N_READS = 3 * N_KEYS // 4          # W/R 1/3
RTOL = 1e-4
CASES = [("carmi", "osm"), ("alex", "mix")]
MODULES = {"alex": alex, "carmi": carmi}


@functools.lru_cache(maxsize=None)
def _bench(name):
    """The benchmark's module `name`, under a name of its own."""
    spec = importlib.util.spec_from_file_location(f"_bench_{name}",
                                                  BENCH / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod         # its dataclasses look it up
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _request(dist, seed):
    (req,) = _bench("traffic").make_ring(
        seed, 1, n_keys=N_KEYS, n_reads=N_READS,
        n_inserts=N_KEYS - N_READS, dist=dist)
    return req


@functools.lru_cache(maxsize=None)
def _evaluate(index_type):
    cfg = E.EnvConfig(index_type=index_type)

    @jax.jit
    def run(params, keys, reads, inserts):
        runtime, _, _ = E.evaluate_params(
            cfg, params, keys, {"reads": reads, "inserts": inserts}, 1 / 3)
        return runtime
    return run


def _params(index_type, seed, which):
    """(program's raw parameters, reference's) for the defaults
    (`which` 0) or the seed's `which`-th random action."""
    R = _bench("reference")
    if which == 0:
        prog = {k: jnp.float32(v)
                for k, v in MODULES[index_type].DEFAULTS.items()}
        return prog, R.DEFAULTS[index_type]
    space = E.EnvConfig(index_type=index_type).space
    rng = np.random.default_rng([seed, which])
    action = rng.uniform(-1.0, 1.0, space.dim).astype(np.float32)
    return space.decode(jnp.asarray(action)), R.decode(index_type, action)


@pytest.mark.parametrize("which", [0, 1, 2])
@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("index_type,dist", CASES)
def test_runtime_matches_float64_reference(index_type, dist, seed, which):
    req = _request(dist, seed)
    prog, ref = _params(index_type, seed, which)
    got = float(_evaluate(index_type)(prog, req.keys, req.reads,
                                      req.inserts))
    want = _bench("reference").runtime_ns(index_type, ref, req.keys,
                                          req.reads, req.inserts)
    assert np.isfinite(got)
    assert abs(got - want) <= RTOL * want, (got, want)


def test_leaf_of_equal_keys_fits_flat():
    """A leaf whose keys are all equal gets slope 0, its mean rank as
    intercept and finite bounds, beside leaves that are not flat."""
    keys = jnp.concatenate([jnp.full((6,), 0.7), jnp.linspace(0.71, 0.8, 10),
                            jnp.full((5,), 0.9)]).astype(jnp.float32)
    seg = jnp.array([0] * 6 + [1] * 10 + [2] * 5, jnp.int32)
    slope, icpt, cnt = lm.fit_segments_exact(keys, seg, 4)
    err = lm.segment_errors(keys, seg, 4, slope, icpt)
    for x in (slope, icpt, cnt, err):
        assert bool(jnp.all(jnp.isfinite(x)))
    assert float(slope[0]) == 0.0 and float(slope[2]) == 0.0
    np.testing.assert_allclose(np.asarray(icpt)[[0, 2]], [2.5, 2.0])
    np.testing.assert_allclose(np.asarray(cnt), [6, 10, 5, 0])
    assert float(slope[1]) > 0.0 and float(err[1]) < 1e-2
    assert float(slope[3]) == 0.0 and float(icpt[3]) == 0.0


def test_root_fit_over_2e20_clustered_keys():
    """The root model's one fit over all 2^20 keys of an `osm` request:
    its slope within 1e-5 of a two-pass float64 fit (9.5e-7 with the
    sums accumulated in blocks; one running float32 total per sum drifts
    the slope by 1.5e-2)."""
    n = 1 << 20
    (req,) = _bench("traffic").make_ring(15000000101, 1, n_keys=n,
                                         n_reads=8, n_inserts=8,
                                         dist="osm")
    slope, intercept, _ = lm.fit_root(jnp.asarray(req.keys))
    x = req.keys.astype(np.float64)
    y = np.arange(n, dtype=np.float64)
    want = np.sum((x - x.mean()) * (y - y.mean())) / np.sum(
        (x - x.mean()) ** 2)
    assert abs(float(slope[0]) - want) <= 1e-5 * want
    assert abs(float(intercept[0]) - (y.mean() - want * x.mean())) \
        <= 1e-5 * n
