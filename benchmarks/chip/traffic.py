"""Request generator: SOSD-style key sets and read/insert workloads.

A copy of the repository's generator (`src/repro/index/workloads.py`:
`sample_keys`, `make_workload`, `wr_workload`, `stream_windows`, as they
stand at this benchmark's first commit), kept here so that a change to
the program never changes the traffic.  Two departures: the sort of the
keys is expressed as a single-operand int32 sort of the float32 bit
patterns (the same order, and a fraction of the TPU compile time of a
float sort at 2^20 elements), and a whole ring of requests is made in
one jitted call on the device.

A request is one tenant's index: `n_keys` sorted keys in [0, 1) from a
named distribution, plus `reads` lookups (90% hit existing keys) and
`inserts` new keys (90% inside the key range, the rest beyond it).  The
batch mix draws every request from the same distribution; the stream
mix drifts the distribution by `drift` per window, the paper's
tumbling-window data shift (LITune, arXiv:2502.05001, section 5.2.4(b)).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

READ_HIT_FRAC = 0.9
INSERT_IN_DOMAIN_FRAC = 0.9


@dataclasses.dataclass
class Request:
    """One tuning request as the client holds it (host arrays)."""
    index: int
    keys: np.ndarray
    reads: np.ndarray
    inserts: np.ndarray

    @property
    def wr_ratio(self) -> float:
        return self.inserts.shape[0] / self.reads.shape[0]


def _sort(x):
    i = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    i = jnp.where(i < 0, i ^ 0x7FFFFFFF, i)
    i = lax.sort(i, is_stable=False)
    return lax.bitcast_convert_type(jnp.where(i < 0, i ^ 0x7FFFFFFF, i),
                                    jnp.float32)


def _normalize(x):
    lo, hi = jnp.min(x), jnp.max(x)
    return (x - lo) / jnp.maximum(hi - lo, 1e-12)


def sample_keys(key, n: int, dist: str, shift=0.0):
    """n sorted keys in [0, 1); a `shift` above 0 drifts the
    distribution (wider lognormal, wider clusters, a lighter tail)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if dist == "uniform":
        x = jax.random.uniform(k1, (n,))
    elif dist == "books":       # lognormal popularity
        x = jnp.exp(jax.random.normal(k1, (n,)) * (1.0 + shift))
    elif dist == "osm":         # clusters, as geographic cell ids
        n_clusters = 8
        centers = jax.random.uniform(k1, (n_clusters,))
        widths = jax.random.uniform(k2, (n_clusters,), minval=0.001,
                                    maxval=0.05 + 0.1 * shift)
        assign = jax.random.randint(k3, (n,), 0, n_clusters)
        x = centers[assign] + jax.random.normal(k4, (n,)) * widths[assign]
    elif dist == "fb":          # heavy-tailed ids
        u = jax.random.uniform(k1, (n,), minval=1e-6)
        x = u ** (-1.0 / (1.5 + shift))
    elif dist == "mix":
        parts = [sample_keys(kk, n, d, shift)
                 for kk, d in zip(jax.random.split(k1, 4),
                                  ("uniform", "books", "osm", "fb"))]
        assign = jax.random.randint(k2, (n,), 0, 4)
        x = jnp.stack(parts, 0)[assign, jnp.arange(n)]
    else:
        raise ValueError(f"unknown key distribution {dist!r}")
    x = _normalize(x) + jnp.arange(n) * 1e-12
    return _sort(_normalize(x))


def make_workload(key, keys, n_reads: int, n_inserts: int, dist: str,
                  shift=0.0):
    """Reads (mostly existing keys, some misses) and inserts (mostly in
    the key range, from the possibly drifted distribution)."""
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    n = keys.shape[0]
    hits = keys[jax.random.randint(k1, (n_reads,), 0, n)]
    misses = jax.random.uniform(k2, (n_reads,))
    is_hit = jax.random.uniform(k3, (n_reads,)) < READ_HIT_FRAC
    reads = jnp.where(is_hit, hits, misses)
    fresh = sample_keys(k4, n_inserts, dist, shift)
    kmax = jnp.max(keys)
    beyond = kmax + jax.random.uniform(k5, (n_inserts,)) * 0.2 + 1e-6
    in_dom = jax.random.uniform(k5, (n_inserts,)) < INSERT_IN_DOMAIN_FRAC
    inserts = jnp.where(in_dom, fresh * kmax, beyond)
    return reads, inserts


def _one(key, shift, *, n_keys, n_reads, n_inserts, dist):
    k1, k2 = jax.random.split(key)
    keys = sample_keys(k1, n_keys, dist, shift)
    reads, inserts = make_workload(k2, keys, n_reads, n_inserts, dist,
                                   shift)
    return keys, reads, inserts


def make_ring(seed: int, n: int, *, n_keys: int, n_reads: int,
              n_inserts: int, dist: str, drift: float = 0.0,
              offset: float = 0.0):
    """`n` requests from `seed`, made on the device in one jitted call
    and fetched to host arrays.  Request i has its own key
    (`fold_in(seed, i)`).  Request 0 is drawn unshifted; request i > 0
    has the shift `offset + drift * (i - 1)`.  With both 0 every request
    is an independent draw from one distribution; otherwise request 0 is
    the reference the stream drifted away from, and requests 1.. are the
    stream's windows in order."""
    base = jax.random.key(seed & 0xFFFFFFFF)
    base = jax.random.fold_in(base, seed >> 32)
    keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(jnp.arange(n))
    i = jnp.arange(n, dtype=jnp.float32)
    shifts = jnp.where(i == 0, 0.0, offset + drift * (i - 1))
    fn = jax.jit(lambda ks, ss: lax.map(
        lambda a: _one(a[0], a[1], n_keys=n_keys, n_reads=n_reads,
                       n_inserts=n_inserts, dist=dist), (ks, ss)))
    k, r, i = jax.device_get(fn(keys, shifts))
    return [Request(j, k[j], r[j], i[j]) for j in range(n)]
