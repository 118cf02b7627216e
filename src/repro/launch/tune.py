"""LITune end-to-end tuning driver (the paper's own end-to-end scenario).

    PYTHONPATH=src python -m repro.launch.tune --index alex --dataset osm \
        --wr 1.0 --pretrain-iters 10 --budget 25

Pretrains the Meta-RL agent (or loads a saved one), answers a tuning
request on the chosen (dataset, workload), and reports runtime vs default
plus the recommended parameters.  `--stream` runs the data-shift scenario
through the O2 system instead.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax

from repro.core.litune import LITune, LITuneConfig
from repro.index.workloads import StreamConfig, sample_keys, stream_windows, wr_workload
from repro.runtime import compile_cache


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--index", default="alex", choices=["alex", "carmi"])
    ap.add_argument("--dataset", default="mix",
                    choices=["uniform", "books", "osm", "fb", "mix"])
    ap.add_argument("--wr", type=float, default=1.0,
                    help="write/read ratio (B=1, RH=1/3, WH=3)")
    ap.add_argument("--n-keys", type=int, default=8192)
    ap.add_argument("--budget", type=int, default=25, help="tuning steps")
    ap.add_argument("--pretrain-iters", type=int, default=10)
    ap.add_argument("--model", default="",
                    help="load/save pretrained agent at this path")
    ap.add_argument("--stream", action="store_true",
                    help="data-shift stream through the O2 system")
    ap.add_argument("--windows", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = LITuneConfig(index_type=args.index, episode_len=args.budget)
    if args.model and os.path.exists(args.model):
        tuner = LITune.load(args.model)
        print(f"loaded pretrained agent from {args.model}")
    else:
        tuner = LITune(cfg, seed=args.seed)
        if args.pretrain_iters:
            print(f"meta-pretraining {args.pretrain_iters} outer iters ...")
            t0 = time.time()
            tuner.pretrain(n_outer=args.pretrain_iters, seed=args.seed,
                           callback=lambda r: print(
                               f"  iter {r['iter']:3d} return "
                               f"{r['mean_return']:8.3f} violations "
                               f"{r['violations']:.0f}"))
            print(f"pretraining took {time.time() - t0:.0f}s")
        if args.model:
            tuner.save(args.model)
            print(f"saved agent to {args.model}")

    key = jax.random.PRNGKey(args.seed + 1)
    if args.stream:
        scfg = StreamConfig(n_windows=args.windows,
                            base_per_window=args.n_keys,
                            updates_per_window=args.n_keys,
                            dist=args.dataset, wr_start=args.wr,
                            wr_end=args.wr * 3)
        results = tuner.stream(stream_windows(key, scfg),
                               max_steps_per_window=5)
        for r in results:
            print(f"window {r['window']:2d}: best "
                  f"{r['best_runtime_ns']:9.1f} ns/op  default "
                  f"{r['r0_ns']:9.1f}  swap={r.get('swapped', False)}")
        return

    data = sample_keys(key, args.n_keys, args.dataset)
    workload, _ = wr_workload(jax.random.fold_in(key, 1), data, args.wr,
                              total=args.n_keys, dist=args.dataset)
    t0 = time.time()
    res = tuner.tune(data, workload, args.wr, budget_steps=args.budget)
    print(f"\ntuning request: index={args.index} data={args.dataset} "
          f"wr={args.wr} budget={args.budget} steps")
    print(f"default runtime : {res['r0_ns']:10.1f} ns/op")
    print(f"best runtime    : {res['best_runtime_ns']:10.1f} ns/op "
          f"({res['r0_ns'] / res['best_runtime_ns']:.2f}x speedup)")
    print(f"violations      : {res['violations']:.0f}   "
          f"tuning wall time: {time.time() - t0:.1f}s")
    print("recommended parameters:")
    print(json.dumps({k: round(v, 4) for k, v in
                      res["best_params"].items()}, indent=2))


if __name__ == "__main__":
    main()
