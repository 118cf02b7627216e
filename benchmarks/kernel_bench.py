"""Kernel fast-path microbenchmark: probe, env step, fused tick.

    PYTHONPATH=src python -m benchmarks.kernel_bench
    PYTHONPATH=src python -m benchmarks.kernel_bench --json BENCH_kernel.json

Three layers of the compiled-Pallas seam (kernels/dispatch.py), reported
as CSV rows and an optional JSON artifact for the CI perf gate
(benchmarks/check_bench.py, metric ``kernel``):

  probe    — us/call of the predecessor probe at the bottom of every
             `run_reads`: the `searchsorted` reference vs the Pallas
             `index_probe.batched_lookup` in interpret mode (kernel
             *logic* timing — the interpreter is not a serving path) vs
             compiled (skip-marked unless an accelerator backend is up);
  env_step — ns/op of the full `alex.run_reads` read path under the
             same three kernel postures (`KernelConfig(mode=...)`);
  tick     — the headline: one K-rung serving tick, fused (scan +
             capture append in one resident program,
             `_step_program(capture=True)`) vs unfused (the historical
             scan program + standalone `_capture_write` dispatch),
             best-of-``--repeats`` ms per tick.  The gate's hard
             invariant is fused <= unfused: the fused program does
             strictly less dispatch work for the same math.

The JSON ratio the gate trends is ``unfused_ms / fused_ms``
(dimensionless, so the committed baseline survives runner drift).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ddpg import DDPGConfig
from repro.core.etmdp import transition_view
from repro.core.litune import LITune, LITuneConfig
from repro.index import alex
from repro.index.workloads import sample_keys, wr_workload
from repro.kernels import dispatch
from repro.kernels.dispatch import KernelConfig
from repro.kernels.index_probe.ops import auto_tile, probe_ranks
from repro.launch.serving import O2ServiceConfig, ServeConfig, TuningService
from repro.launch.serving.programs import (_capture_write, _pow2_ladder,
                                           _step_program)
from repro.runtime import compile_cache

POSTURES = ("ref", "interpret", "compiled")


def _on_accel() -> bool:
    return jax.default_backend() in ("gpu", "tpu")


def _skip_compiled(mode: str) -> str | None:
    """Reason string when `mode` cannot run on this backend, else None."""
    if mode == "compiled" and not _on_accel():
        return f"no accelerator backend (jax: {jax.default_backend()})"
    return None


def _time_us(fn, n_timing: int) -> float:
    fn()                                    # warm (bind outside timing)
    t0 = time.perf_counter()
    for _ in range(n_timing):
        fn()
    return (time.perf_counter() - t0) / n_timing * 1e6


# ------------------------------------------------------------------ probe
def bench_probe(n_keys: int, n_queries: int, n_timing: int) -> list[dict]:
    key = jax.random.PRNGKey(0)
    keys = jnp.sort(jax.random.uniform(key, (n_keys,)))
    queries = jax.random.uniform(jax.random.fold_in(key, 1), (n_queries,))
    tile = auto_tile(n_keys)
    rows = []

    ss = jax.jit(lambda k, q: jnp.clip(
        jnp.searchsorted(k, q, side="right") - 1, 0, k.shape[0] - 1))
    us = _time_us(lambda: ss(keys, queries).block_until_ready(), n_timing)
    rows.append({"impl": "searchsorted_ref", "us_per_call": round(us, 1)})

    for mode in ("interpret", "compiled"):
        skip = _skip_compiled(mode)
        if skip:
            rows.append({"impl": f"pallas_{mode}", "skipped": skip})
            continue
        us = _time_us(
            lambda: probe_ranks(keys, queries, tile=tile,
                                mode=mode)[0].block_until_ready(),
            n_timing)
        rows.append({"impl": f"pallas_{mode}", "us_per_call": round(us, 1)})
    return rows


# --------------------------------------------------------------- env step
def bench_env_step(n_keys: int, n_reads: int, n_timing: int) -> list[dict]:
    key = jax.random.PRNGKey(1)
    keys = jnp.sort(jax.random.uniform(key, (n_keys,)))
    reads = jax.random.uniform(jax.random.fold_in(key, 1), (n_reads,))
    params = {k: jnp.float32(v) for k, v in alex.DEFAULTS.items()}
    idx = alex.build(keys, params)
    rows = []
    for mode in POSTURES:
        skip = _skip_compiled(mode)
        if skip:
            rows.append({"kernel": mode, "skipped": skip})
            continue
        kcfg = None if mode == "ref" else KernelConfig(mode=mode)
        fn = jax.jit(lambda r, _k=kcfg: alex.run_reads(idx, r, kernel=_k)[0])
        us = _time_us(lambda: fn(reads).block_until_ready(), n_timing)
        rows.append({"kernel": mode,
                     "ns_per_op": round(us * 1e3 / n_reads, 1)})
    return rows


# ------------------------------------------------------------- fused tick
def _make_requests(n: int, n_keys: int, seed: int = 1):
    dists = ["uniform", "books", "osm", "fb"]
    key = jax.random.PRNGKey(seed)
    out = []
    for i in range(n):
        k = jax.random.fold_in(key, i)
        data = sample_keys(k, n_keys, dists[i % len(dists)])
        wl, _ = wr_workload(jax.random.fold_in(k, 1), data, 1.0,
                            total=n_keys, dist="mix")
        out.append((data, wl, 1.0))
    return out


def bench_tick(slots: int, budget: int, n_keys: int, ticks: int,
               repeats: int) -> dict:
    """Fused vs unfused K-rung tick on real, program-cache-resident
    executables: serve a short O2 stream to bind the ladder and leave a
    live pool, then drive both step variants directly from its state.
    Each timed tick rebinds carry/capture exactly like the serving loop
    (donation-safe on accelerators) and blocks on the same narrow field
    the service fetches."""
    cfg = LITuneConfig(index_type="alex", episode_len=budget,
                       lstm_hidden=32, mlp_hidden=64,
                       ddpg=DDPGConfig(batch_size=16, seq_len=4, burn_in=1))
    svc = TuningService(LITune(cfg, seed=0), config=ServeConfig(
        slots=slots, horizon_cap=budget,
        o2=O2ServiceConfig(enabled=True)))
    for data, wl, wr in _make_requests(slots, n_keys):
        svc.submit(data, wl, wr, budget_steps=budget, noise_scale=0.02)
    svc.run()
    svc.flush_o2()
    pool = next(iter(svc.pools.values()))
    k = max(_pow2_ladder(budget))
    prog_u = _step_program(pool.slice, pool.net_cfg, pool.env_cfg,
                           pool.et_cfg, k)
    prog_f = _step_program(pool.slice, pool.net_cfg, pool.env_cfg,
                           pool.et_cfg, k, capture=True)
    noise = pool.noise_dev()
    off = jnp.zeros((slots,), jnp.int32)

    def fresh():
        # private buffers per timed run: the programs donate carry/cap
        # on accelerator backends, so state must rebind like the tick
        carry = jax.tree.map(jnp.array, pool.carry)
        cap = jax.tree.map(jnp.array, pool.ensure_cap())
        return carry, cap

    def run_fused():
        carry, cap = fresh()
        t0 = time.perf_counter()
        for _ in range(ticks):
            carry, out, cap = prog_f(pool.params, carry, noise, cap, off)
            np.asarray(out["reward"][-1])   # the serving loop's fetch
        return (time.perf_counter() - t0) / ticks * 1e3

    def run_unfused():
        carry, cap = fresh()
        t0 = time.perf_counter()
        for _ in range(ticks):
            carry, out = prog_u(pool.params, carry, noise)
            np.asarray(out["reward"][-1])
            cap = _capture_write(cap, transition_view(out), off)
            cap.block_until_ready()
        return (time.perf_counter() - t0) / ticks * 1e3

    run_fused(), run_unfused()              # warm both variants
    # interleave the variants so both mins sample the same machine
    # conditions (back-to-back blocks would let CPU-frequency / noisy-
    # neighbor drift decide the comparison)
    f_times, u_times = [], []
    for _ in range(repeats):
        f_times.append(run_fused())
        u_times.append(run_unfused())
    fused_ms, unfused_ms = min(f_times), min(u_times)
    return {"k": k, "slots": slots, "ticks": ticks,
            "fused_ms": round(fused_ms, 3),
            "unfused_ms": round(unfused_ms, 3),
            "speedup": round(unfused_ms / fused_ms, 3)}


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n-keys", type=int, default=4096)
    ap.add_argument("--n-queries", type=int, default=512)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--budget", type=int, default=16)
    ap.add_argument("--ticks", type=int, default=20,
                    help="ticks per timed tick-bench run")
    ap.add_argument("--timing", type=int, default=5,
                    help="calls per probe/env-step timing")
    ap.add_argument("--repeats", type=int, default=5,
                    help="timed runs per tick variant; min is reported")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as a JSON artifact (CI gate)")
    args = ap.parse_args()

    if dispatch.resolve(None) == "compiled" and not _on_accel():
        # REPRO_KERNEL_MODE=compiled forced without an accelerator: every
        # serving-path posture below would die inside pallas lowering.
        # Mirror the tests' skip-marker instead of crashing mid-bench.
        print("bench,layer,impl,value")
        print(f"kernel,all,compiled,SKIP(no accelerator backend, "
              f"jax: {jax.default_backend()})")
        return

    probe = bench_probe(args.n_keys, args.n_queries, args.timing)
    env_step = bench_env_step(args.n_keys, args.n_queries, args.timing)
    tick = bench_tick(args.slots, args.budget, min(args.n_keys, 1024),
                      args.ticks, args.repeats)

    print("bench,layer,impl,value")
    for r in probe:
        v = r.get("us_per_call", f"SKIP({r.get('skipped')})")
        print(f"kernel,probe,{r['impl']},{v}")
    for r in env_step:
        v = r.get("ns_per_op", f"SKIP({r.get('skipped')})")
        print(f"kernel,env_step,{r['kernel']},{v}")
    print(f"kernel,tick,fused_ms,{tick['fused_ms']}")
    print(f"kernel,tick,unfused_ms,{tick['unfused_ms']}")
    print(f"kernel,tick,speedup,{tick['speedup']}")

    if args.json:
        doc = {
            "benchmark": "kernel",
            "backend": jax.default_backend(),
            "mode_default": dispatch.resolve(None),
            "probe": probe,
            "env_step": env_step,
            "tick": tick,
            "config": {"n_keys": args.n_keys, "n_queries": args.n_queries,
                       "slots": args.slots, "budget": args.budget,
                       "ticks": args.ticks, "repeats": args.repeats},
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
