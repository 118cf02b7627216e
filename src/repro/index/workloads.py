"""SOSD-style datasets and query workloads.

The paper evaluates on SOSD (books / OSM / Facebook / MIX) and trains on
synthetic distributions (uniform, beta/normal, ...) with W/R ratios between
1:10 and 10:1.  This module generates statistically matching synthetic key
sets (we have no network access), plus the tumbling-window data-shift streams
of §5.2.4(b).

All keys are float64 in [0, 1): learned-index mechanics only depend on the
empirical CDF, so any monotone rescaling of the published datasets is
equivalent for tuning dynamics.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.index import order

DATASETS = ("uniform", "books", "osm", "fb", "mix")


def _normalize(keys: jax.Array) -> jax.Array:
    lo, hi = jnp.min(keys), jnp.max(keys)
    return (keys - lo) / jnp.maximum(hi - lo, 1e-12)


def sample_keys(key: jax.Array, n: int, dist: str = "mix",
                shift: float = 0.0) -> jax.Array:
    """n sorted unique-ish keys in [0,1). `shift` in [0,1] drifts the
    distribution (for data-shifting streams)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if dist == "uniform":
        x = jax.random.uniform(k1, (n,))
    elif dist == "books":  # lognormal-ish popularity
        x = jnp.exp(jax.random.normal(k1, (n,)) * (1.0 + shift))
    elif dist == "osm":    # multi-modal clusters (geographic)
        n_clusters = 8
        centers = jax.random.uniform(k1, (n_clusters,))
        widths = jax.random.uniform(k2, (n_clusters,), minval=0.001,
                                    maxval=0.05 + 0.1 * shift)
        assign = jax.random.randint(k3, (n,), 0, n_clusters)
        x = centers[assign] + jax.random.normal(k4, (n,)) * widths[assign]
    elif dist == "fb":     # heavy-tailed ids
        u = jax.random.uniform(k1, (n,), minval=1e-6)
        x = u ** (-1.0 / (1.5 + shift))  # pareto tail
    elif dist == "mix":
        parts = [sample_keys(kk, n, d, shift)
                 for kk, d in zip(jax.random.split(k1, 4),
                                  ("uniform", "books", "osm", "fb"))]
        assign = jax.random.randint(k2, (n,), 0, 4)
        x = jnp.stack(parts, 0)[assign, jnp.arange(n)]
    else:
        raise ValueError(f"unknown dataset {dist}")
    # dedupe-ish: add tiny deterministic jitter, normalize, sort
    x = _normalize(x) + jnp.arange(n) * 1e-12
    return order.sort(_normalize(x))


@dataclasses.dataclass(frozen=True)
class WorkloadConfig:
    n_reads: int = 2048
    n_inserts: int = 2048
    read_hit_frac: float = 0.9      # fraction of reads that hit existing keys
    insert_in_domain_frac: float = 0.9  # rest are out-of-domain (beyond max)
    insert_drift: float = 0.0       # distribution drift of inserted keys

    @property
    def wr_ratio(self) -> float:
        return self.n_inserts / max(self.n_reads, 1)


def make_workload(key: jax.Array, data_keys: jax.Array, cfg: WorkloadConfig,
                  dist: str = "mix"):
    """Returns dict of query arrays: reads [n_reads], inserts [n_inserts]."""
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    n = data_keys.shape[0]
    # reads: mostly existing keys, some misses
    idx = jax.random.randint(k1, (cfg.n_reads,), 0, n)
    hits = data_keys[idx]
    misses = jax.random.uniform(k2, (cfg.n_reads,))
    is_hit = jax.random.uniform(k3, (cfg.n_reads,)) < cfg.read_hit_frac
    reads = jnp.where(is_hit, hits, misses)
    # inserts: in-domain from (possibly drifted) distribution; rest beyond max
    fresh = sample_keys(k4, cfg.n_inserts, dist, shift=cfg.insert_drift)
    dmax = jnp.max(data_keys)
    out_of_domain = dmax + jax.random.uniform(
        k5, (cfg.n_inserts,)) * 0.2 + 1e-6
    in_dom = jax.random.uniform(k5, (cfg.n_inserts,)) \
        < cfg.insert_in_domain_frac
    inserts = jnp.where(in_dom, fresh * dmax, out_of_domain)
    return {"reads": reads, "inserts": inserts}


def wr_workload(key, data_keys, wr_ratio: float, total: int = 4096,
                dist: str = "mix", drift: float = 0.0):
    """Workload from a write/read ratio (paper: Balanced=1, RH=1/3, WH=3)."""
    n_ins = int(total * wr_ratio / (1.0 + wr_ratio))
    cfg = WorkloadConfig(n_reads=total - n_ins, n_inserts=n_ins,
                         insert_drift=drift)
    return make_workload(key, data_keys, cfg, dist), cfg


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Tumbling-window data-shift stream (paper §5.2.4(b))."""
    n_windows: int = 30
    base_per_window: int = 4096
    updates_per_window: int = 8192
    dist: str = "mix"
    drift_per_window: float = 0.03
    wr_start: float = 1.0
    wr_end: float = 3.0


def stream_windows(key: jax.Array, cfg: StreamConfig):
    """Yields (window_idx, data_keys, workload, wr_ratio) lazily."""
    for w in range(cfg.n_windows):
        kw = jax.random.fold_in(key, w)
        k1, k2 = jax.random.split(kw)
        shift = cfg.drift_per_window * w
        data = sample_keys(k1, cfg.base_per_window, cfg.dist, shift=shift)
        frac = w / max(cfg.n_windows - 1, 1)
        wr = cfg.wr_start + (cfg.wr_end - cfg.wr_start) * frac
        workload, _ = wr_workload(k2, data, wr, total=cfg.updates_per_window,
                                  dist=cfg.dist, drift=shift)
        yield w, data, workload, wr
