"""Requests/sec of the batched tuning service vs serial `LITune.tune`.

    PYTHONPATH=src python -m benchmarks.tune_serve
    PYTHONPATH=src python -m benchmarks.tune_serve --requests 16 \
        --budget 8 --n-keys 2048 --slots 1,4,16

Serves the same wave of R tuning requests two ways and reports req/s:

  serial   — `LITune.tune` answers one request at a time (the paper's
             single-tenant shape: one jitted episode-step dispatch per
             step per request, host sync after every step);
  batched  — `launch.serving.TuningService` with B slots: one jitted
             B-slot step per service tick, one host transfer per tick.

Both paths run the identical traced per-episode program (the parity the
test suite asserts bitwise), so the ratio is pure serving-architecture
win: one K-step program per tick instead of per-step dispatch+sync, and
slots sharded across host devices (cores) — parallelism a single-tenant
tuner cannot use.  Prints CSV ``tune_serve,<mode>,<slots>,<req/s>,<speedup>``.
"""
from __future__ import annotations

import argparse
import json
import os
import time

# expose every core as an XLA host device so the service can shard slots;
# must happen before jax initializes (no-op if the operator already set it)
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={os.cpu_count()}")

import jax

from repro.core.litune import LITune, LITuneConfig
from repro.index.workloads import sample_keys, wr_workload
from repro.launch.serving import ServeConfig, TuningService
from repro.runtime import compile_cache


def make_requests(n: int, n_keys: int, seed: int = 1, mixed_wr: bool = False):
    """`mixed_wr` cycles write/read ratios -> 3 workload shapes -> the
    service fragments into 3 pools (the heterogeneous-stream demo); the
    default single ratio keeps one pool fully utilized (the throughput
    measurement)."""
    key = jax.random.PRNGKey(seed)
    out = []
    for i in range(n):
        k = jax.random.fold_in(key, i)
        wr = [0.33, 1.0, 3.0][i % 3] if mixed_wr else 1.0
        data = sample_keys(k, n_keys, "mix")
        wl, _ = wr_workload(jax.random.fold_in(k, 1), data, wr,
                            total=n_keys, dist="mix")
        out.append((data, wl, wr))
    return out


def bench_serial(tuner: LITune, requests, budget: int) -> float:
    t0 = time.perf_counter()
    for data, wl, wr in requests:
        tuner.tune(data, wl, wr, budget_steps=budget)
    return len(requests) / (time.perf_counter() - t0)


def bench_batched(tuner: LITune, requests, budget: int, slots: int) -> float:
    service = TuningService(tuner, config=ServeConfig(slots=slots))
    t0 = time.perf_counter()
    for data, wl, wr in requests:
        service.submit(data, wl, wr, budget_steps=budget)
    results = service.run()
    dt = time.perf_counter() - t0
    assert len(results) == len(requests)
    return len(requests) / dt


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--budget", type=int, default=8)
    ap.add_argument("--n-keys", type=int, default=512)
    ap.add_argument("--index", default="alex", choices=["alex", "carmi"])
    ap.add_argument("--slots", default="1,4,16")
    ap.add_argument("--mixed-wr", action="store_true",
                    help="cycle write/read ratios (heterogeneous pools)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as a JSON artifact (CI trend)")
    args = ap.parse_args()
    slot_counts = [int(s) for s in args.slots.split(",")]

    cfg = LITuneConfig(index_type=args.index, episode_len=args.budget,
                       lstm_hidden=32, mlp_hidden=64)
    tuner = LITune(cfg, seed=args.seed)
    requests = make_requests(args.requests, args.n_keys, seed=args.seed + 1,
                             mixed_wr=args.mixed_wr)

    # warm both paths with the full wave so compile time is excluded (a
    # real service compiles its programs once at startup; the program
    # cache in launch/serving/programs.py is process-wide)
    bench_serial(tuner, requests, args.budget)
    for b in slot_counts:
        bench_batched(tuner, requests, args.budget, b)

    print(f"# tune_serve  requests={args.requests} budget={args.budget} "
          f"n_keys={args.n_keys} index={args.index} "
          f"mixed_wr={args.mixed_wr} devices={len(jax.devices())}")
    print("benchmark,mode,slots,req_per_s,speedup_vs_serial")
    serial_rps = bench_serial(tuner, requests, args.budget)
    rows = [{"mode": "serial", "slots": 1, "req_per_s": serial_rps,
             "speedup_vs_serial": 1.0}]
    print(f"tune_serve,serial,1,{serial_rps:.3f},1.00")
    for b in slot_counts:
        rps = bench_batched(tuner, requests, args.budget, b)
        rows.append({"mode": "batched", "slots": b, "req_per_s": rps,
                     "speedup_vs_serial": rps / serial_rps})
        print(f"tune_serve,batched,{b},{rps:.3f},{rps / serial_rps:.2f}")

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"benchmark": "tune_serve",
                       "config": {"requests": args.requests,
                                  "budget": args.budget,
                                  "n_keys": args.n_keys,
                                  "index": args.index,
                                  "mixed_wr": args.mixed_wr,
                                  "devices": len(jax.devices())},
                       "rows": rows}, f, indent=2)
        print(f"# wrote {args.json}")


if __name__ == "__main__":
    main()
