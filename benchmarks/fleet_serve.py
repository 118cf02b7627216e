"""Fleet mode at scale: hold req/s while the tenant count sweeps.

    PYTHONPATH=src python -m benchmarks.fleet_serve
    PYTHONPATH=src python -m benchmarks.fleet_serve \
        --tenants 64,256,1024,4096 --json BENCH_fleet.json

Builds one `TuningService` per sweep point with N fleet-mode tenants
(`FleetConfig(enabled=True)`) sharing one pretrained agent, drives the
same drifting request wave over a small *hot working set* of them, and
reports req/s per tenant count.  The point of fleet mode is that N is
almost free: tenants outside the working set stay **cold** (zero device
bytes — host-spilled replay pages, no learner copies), the working set
rides **stacked** fine-tune rounds (one jitted dispatch for all K hot
tenants), and the process-wide program caches never grow with N.

Reported per sweep point: req/s, hot/warm/cold tier counts, stacked
round occupancy, device bytes per tenant, and two hard invariants the
CI gate (benchmarks/check_bench.py, metric ``fleet``) enforces outright:

  * zero new `_step_program` binds across the whole tenant sweep (the
    serving cache must stay flat as N sweeps), and zero new stacked
    fine-tune programs after the first point's pow2 ladder warms;
  * every cold tenant at exactly zero device bytes.

A stacked-vs-serial microbench rides along: one K-wide stacked round
vs K width-1 rounds through the same machinery (same replay sampling,
same batch hops), timing the per-round fine-tune wall time's
sublinearity in the hot-tenant count.  The gated trend metric is the
req/s ratio of the largest tenant count over the smallest — the
"holding req/s while tenants sweep" claim as one dimensionless number.

CI smoke sweeps 64→512; the full sweep (64→4096) is the same command
with ``--tenants 64,256,1024,4096``.
"""
from __future__ import annotations

import argparse
import json
import os
import time

# expose every core plus one annex spare before jax initializes (no-op
# if the operator already set the flag) — same discipline as o2_serve
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count="
        + str(os.cpu_count() + 1))

import jax
import numpy as np

from repro.core.ddpg import DDPGConfig
from repro.core.litune import LITune, LITuneConfig
from repro.core.o2 import O2Config, _fleet_finetune_program, make_replay
from repro.index.workloads import sample_keys, wr_workload
from repro.launch.serving import (FleetConfig, FleetLearner,
                                  O2ServiceConfig, ServeConfig,
                                  TuningService)
from repro.launch.serving.programs import _step_program
from repro.runtime import compile_cache


def make_requests(n: int, n_keys: int, seed: int = 1):
    """The o2_serve drifting wave: the key distribution cycles so
    divergence fires and the O2/fleet path actually does its work."""
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


def build_service(cfg: LITuneConfig, tuner: LITune, n_tenants: int,
                  slots: int, fleet: FleetConfig,
                  replay_capacity: int) -> TuningService:
    """N fleet tenants sharing one pretrained agent (the homogeneous
    fleet: one config, one stacked program group)."""
    agents = {f"t{i}": tuner for i in range(n_tenants)}
    return TuningService(agents, config=ServeConfig(
        slots=slots,
        o2=O2ServiceConfig(enabled=True, o2=cfg.o2,
                           offline_updates_per_tick=2,
                           replay_capacity=replay_capacity,
                           fleet=fleet)))


def drive(service: TuningService, requests, budget: int, hot: int):
    """Submit the wave round-robin over the hot working set and serve
    it; timing covers submit+run only (the serving contract), flush
    settles the trailing learner outside the window."""
    t0 = time.perf_counter()
    for i, (data, wl, wr) in enumerate(requests):
        service.submit(data, wl, wr, budget_steps=budget,
                       index_type=f"t{i % hot}", noise_scale=0.02)
    results = service.run()
    dt = time.perf_counter() - t0
    service.flush_o2()
    assert len(results) == len(requests)
    return len(requests) / dt


def sweep_point(cfg, tuner, n_tenants, requests, budget, slots, hot,
                fleet, replay_capacity, repeats) -> dict:
    best = 0.0
    for _ in range(repeats):
        service = build_service(cfg, tuner, n_tenants, slots, fleet,
                                replay_capacity)
        rps = drive(service, requests, budget, hot)
        best = max(best, rps)
    st = service.stats()
    o2 = st["o2"]
    tenants = service.tenants
    cold_max = max((t.device_bytes() for t in tenants.values()
                    if t.tier == "cold"), default=0)
    return {
        "tenants": n_tenants,
        "req_per_s": best,
        "tenants_hot": o2["tenants_hot"],
        "tenants_warm": o2["tenants_warm"],
        "tenants_cold": o2["tenants_cold"],
        "occupancy": o2["fleet"]["occupancy"],
        "fleet_rounds": o2["fleet"]["rounds"],
        "fleet_lanes": o2["fleet"]["lanes"],
        "warm_starts": o2["warm_starts"],
        "device_bytes_per_tenant": o2["device_bytes"] // n_tenants,
        "cold_device_bytes_max": int(cold_max),
    }


def stack_microbench(cfg: LITuneConfig, fleet: FleetConfig, k: int,
                     n_updates: int, reps: int) -> dict:
    """One K-wide stacked round vs K width-1 rounds through the same
    `FleetLearner.round` machinery — per-round fine-tune wall time's
    sublinearity in the hot-tenant count, on this host."""
    import types

    from repro.core import ddpg as _ddpg

    net_cfg, ddpg_cfg, env_cfg = cfg.net_cfg(), cfg.ddpg, cfg.env_cfg()

    def tenant(i):
        replay = make_replay(net_cfg, ddpg_cfg, env_cfg, capacity=256,
                             seed=i, device=True)
        rng = np.random.default_rng(100 + i)
        T, hid = 24, net_cfg.lstm_hidden
        f32 = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
        for _ in range(4):
            replay.add_episode(
                obs=f32(T, replay.obs_dim), action=f32(T, replay.action_dim),
                reward=f32(T), next_obs=f32(T, replay.obs_dim),
                done=np.concatenate([np.zeros(T - 1, np.float32),
                                     [1.0]]).astype(np.float32),
                cost=(rng.random(T) < 0.3).astype(np.float32),
                actor_hidden=(f32(T, hid), f32(T, hid)),
                critic_hidden=(f32(T, hid), f32(T, hid)))
        return types.SimpleNamespace(
            net_cfg=net_cfg, ddpg_cfg=ddpg_cfg, replay=replay,
            offline=_ddpg.init_state(jax.random.PRNGKey(i), net_cfg,
                                     ddpg_cfg))

    learner = FleetLearner(FleetConfig(enabled=True, max_hot=k,
                                       stack_impl=fleet.stack_impl))

    def timed(tenants, width):
        # re-seed each rep's learner states so every round does the same
        # numeric work; block on the outputs (round returns async trees)
        for i, t in enumerate(tenants):
            t.offline = _ddpg.init_state(jax.random.PRNGKey(i), net_cfg,
                                         ddpg_cfg)
        t0 = time.perf_counter()
        if width == 1:
            for t in tenants:
                learner.round([(t, n_updates)])
        else:
            learner.round([(t, n_updates) for t in tenants])
        for t in tenants:
            jax.block_until_ready(t.offline["params"])
        return 1e3 * (time.perf_counter() - t0)

    ts = [tenant(i) for i in range(k)]
    timed(ts, 1)       # warm both program shapes outside the timing
    timed(ts, k)
    serial_ms = min(timed(ts, 1) for _ in range(reps))
    stacked_ms = min(timed(ts, k) for _ in range(reps))
    return {"k": k, "serial_ms": round(serial_ms, 3),
            "stacked_ms": round(stacked_ms, 3),
            "speedup": round(serial_ms / max(stacked_ms, 1e-9), 3)}


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tenants", default="64,128,256,512",
                    metavar="N1,N2,...",
                    help="tenant counts to sweep (full: 64,256,1024,4096)")
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--budget", type=int, default=8)
    ap.add_argument("--n-keys", type=int, default=256)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--hot", type=int, default=8,
                    help="hot working set: distinct tenants receiving "
                         "traffic (constant across the sweep)")
    ap.add_argument("--replay-capacity", type=int, default=128,
                    help="per-tenant ring rows (a fleet bounds its "
                         "per-tenant footprint here)")
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--stack-k", type=int, default=8,
                    help="hot-tenant count for the stacked-vs-serial "
                         "fine-tune microbench")
    ap.add_argument("--updates", type=int, default=4,
                    help="fine-tune updates per round in the microbench")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None, metavar="PATH")
    args = ap.parse_args()

    counts = sorted(int(n) for n in args.tenants.split(",") if n)
    assert counts and args.hot <= min(counts)
    cfg = LITuneConfig(
        index_type="t0", episode_len=args.budget,
        lstm_hidden=32, mlp_hidden=64,
        ddpg=DDPGConfig(batch_size=16, seq_len=4, burn_in=1),
        o2=O2Config(divergence_threshold=0.10, assess_every=4,
                    offline_updates_per_window=2))
    fleet = FleetConfig(enabled=True, max_hot=max(args.hot, args.stack_k))
    tuner = LITune(cfg, seed=args.seed)
    requests = make_requests(args.requests, args.n_keys,
                             seed=args.seed + 1)

    # warm every program the sweep will touch (caches are process-wide),
    # then snapshot the cache sizes: the sweep must bind nothing new
    drive(build_service(cfg, tuner, counts[0], args.slots, fleet,
                        args.replay_capacity),
          requests, args.budget, args.hot)
    step_binds0 = _step_program.cache_info().currsize
    fleet_binds0 = _fleet_finetune_program.cache_info().currsize

    rows = []
    for n in counts:
        row = sweep_point(cfg, tuner, n, requests, args.budget,
                          args.slots, args.hot, fleet,
                          args.replay_capacity, args.repeats)
        row["new_step_binds"] = (_step_program.cache_info().currsize
                                 - step_binds0)
        row["new_fleet_binds"] = (
            _fleet_finetune_program.cache_info().currsize - fleet_binds0)
        rows.append(row)

    stack = stack_microbench(cfg, fleet, args.stack_k, args.updates,
                             args.repeats)
    rps_ratio = rows[-1]["req_per_s"] / rows[0]["req_per_s"]

    print(f"# fleet_serve  requests={args.requests} budget={args.budget} "
          f"n_keys={args.n_keys} slots={args.slots} hot={args.hot} "
          f"replay_capacity={args.replay_capacity} "
          f"repeats={args.repeats} devices={len(jax.devices())} "
          f"impl={FleetLearner(fleet).impl}")
    print("benchmark,tenants,req_per_s,hot,warm,cold,occupancy,"
          "dev_bytes_per_tenant,cold_dev_max,new_step_binds")
    for r in rows:
        print(f"fleet_serve,{r['tenants']},{r['req_per_s']:.3f},"
              f"{r['tenants_hot']},{r['tenants_warm']},"
              f"{r['tenants_cold']},{r['occupancy']:.2f},"
              f"{r['device_bytes_per_tenant']},"
              f"{r['cold_device_bytes_max']},{r['new_step_binds']}")
    print(f"fleet_serve,stack_k{stack['k']},serial={stack['serial_ms']}ms,"
          f"stacked={stack['stacked_ms']}ms,"
          f"speedup={stack['speedup']},,,,,")
    print(f"# rps_ratio (N={counts[-1]} over N={counts[0]}) = "
          f"{rps_ratio:.3f}")

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"benchmark": "fleet",
                       "config": {"tenants": counts,
                                  "requests": args.requests,
                                  "budget": args.budget,
                                  "n_keys": args.n_keys,
                                  "slots": args.slots,
                                  "hot": args.hot,
                                  "replay_capacity": args.replay_capacity,
                                  "repeats": args.repeats,
                                  "stack_k": args.stack_k,
                                  "devices": len(jax.devices())},
                       "rows": rows,
                       "stack": stack,
                       "rps_ratio": rps_ratio}, f, indent=2)
        print(f"# wrote {args.json}")


if __name__ == "__main__":
    main()
