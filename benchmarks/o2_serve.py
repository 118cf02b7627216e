"""Cost of continuous tuning (O2) inside the batched tuning service.

    PYTHONPATH=src python -m benchmarks.o2_serve
    PYTHONPATH=src python -m benchmarks.o2_serve --requests 12 --budget 16 \
        --n-keys 1024 --slots 4 --assess-every 2 --json BENCH_o2_serve.json

Serves the same drifting request wave through two service configurations
and reports req/s (best of ``--repeats`` runs per mode — the CPU hosts CI
runs on are noisy):

  frozen — `TuningService` as PR 1 shipped it: a frozen pretrained agent,
           no transition capture, no offline learner;
  o2     — `O2ServiceConfig(enabled=True)`: per-request divergence
           observation, device-resident transition capture into the
           annex replay ring, backpressured offline fine-tune rounds,
           and divergence-triggered pooled assessments / hot-swaps.

The gap between the two is the end-to-end price of continuous tuning.
Timing covers `run()` only — the serving contract; the trailing learner
and any still-executing assessment verdicts settle in `flush_o2()`
*outside* the timed window, exactly as a serving deployment experiences
them.  `--assess-every 1` is the worst case (every diverged window
assesses, costing up to one offline episode per served episode);
production rate-limits via the same knob.

Prints CSV ``o2_serve,<mode>,<slots>,<req/s>,<vs_frozen>`` plus swap
latency and per-phase host-time rows; ``--json`` writes the same numbers
as a JSON artifact for the CI perf gate (benchmarks/check_bench.py).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _argv_value(flag: str, default: str) -> str:
    """Peek one CLI value before argparse (and before jax initializes —
    the sweep's annex widths size the forced device count).  Accepts
    ``--flag value`` and ``--flag=value``; like argparse, the last
    occurrence wins.  main() cross-checks the peek against argparse and
    refuses forms the peek cannot see (abbreviated flags)."""
    value = default
    for i, arg in enumerate(sys.argv):
        if arg == flag and i + 1 < len(sys.argv):
            value = sys.argv[i + 1]
        elif arg.startswith(flag + "="):
            value = arg.split("=", 1)[1]
    return value


_ANNEX_WIDTHS = sorted(int(w) for w in
                       _argv_value("--annex-width", "").split(",") if w)

# expose every core as an XLA host device — plus the spare(s) the O2
# service adopts as its learner/assessment annex slice — before jax
# initializes (no-op if the operator already set the flag)
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count="
        + str(os.cpu_count() + max(_ANNEX_WIDTHS, default=1)))

import jax
import numpy as np

from repro.core.ddpg import DDPGConfig
from repro.core.litune import LITune, LITuneConfig
from repro.core.o2 import O2Config
from repro.index.workloads import sample_keys, wr_workload
from repro.launch.serving import (DeviceSlice, O2ServiceConfig,
                                  ServeConfig, ServingTopology,
                                  TuningService)
from repro.launch.serving.topology import _largest_divisor_leq
from repro.runtime import compile_cache


def make_requests(n: int, n_keys: int, seed: int = 1):
    """A drifting wave: the key distribution cycles so the divergence
    monitor actually fires (the O2 path's worst case — every window may
    trigger an assessment)."""
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


def bench_once(tuner: LITune, requests, budget: int, slots: int,
               o2: O2ServiceConfig | None, topology=None):
    service = TuningService(tuner, config=ServeConfig(
        slots=slots, o2=o2 if o2 is not None else O2ServiceConfig(),
        topology=topology))
    t0 = time.perf_counter()
    for data, wl, wr in requests:
        service.submit(data, wl, wr, budget_steps=budget, noise_scale=0.02)
    results = service.run()
    dt = time.perf_counter() - t0
    # settle the trailing learner + assessment verdicts outside the timed
    # window so the next run starts from a quiet machine
    service.flush_o2()
    assert len(results) == len(requests)
    return len(requests) / dt, service


def bench(mk_tuner, requests, budget, slots, o2, repeats: int):
    """Best-of-`repeats` req/s, with the stats of the *best* run — the
    JSON artifact's ratio and its phase breakdown describe one run."""
    best, service = 0.0, None
    for _ in range(repeats):
        rps, svc = bench_once(mk_tuner(), requests, budget, slots, o2)
        if rps > best:
            best, service = rps, svc
    return best, service


def annex_sweep(mk_tuner, requests, budget: int, slots: int,
                o2_cfg: O2ServiceConfig, widths: list[int],
                repeats: int) -> list[dict]:
    """Serve the same O2 stream once per annex slice width, keeping the
    serving slice fixed, and report the host-side assessment phase time
    (dispatch + blocking verdict fetches — the part of the O2 tax the
    annex slice actually absorbs).  Widths shard the pooled assessment
    waves across 1..w annex devices; per-lane math is identical, so the
    verdicts are bitwise equal and the only thing that moves is time.
    Min across `repeats` runs per width (noise floor)."""
    import jax
    ids = tuple(d.id for d in jax.devices())
    # the serving slice stays fixed across the sweep (the comparison is
    # annex-width-only): the largest divisor of `slots` that leaves the
    # widest requested annex room
    serve_n = _largest_divisor_leq(slots, len(ids) - max(widths))
    if serve_n + max(widths) > len(ids):
        raise SystemExit(
            f"annex sweep needs {serve_n}+{max(widths)} devices but the "
            f"host exposes {len(ids)} — unset any operator "
            f"xla_force_host_platform_device_count or lower the widths")
    serve = DeviceSlice(ids[:serve_n], name="serve")
    rows = []
    for w in widths:
        topo = ServingTopology(
            (serve,), DeviceSlice(ids[serve_n:serve_n + w], name="annex"),
            name=f"host+annex{w}")
        # one warm pass binds this width's programs outside the timing
        bench_once(mk_tuner(), requests, budget, slots, o2_cfg,
                   topology=topo)
        best_assess, best_rps = float("inf"), 0.0
        for _ in range(repeats):
            rps, svc = bench_once(mk_tuner(), requests, budget, slots,
                                  o2_cfg, topology=topo)
            st = svc.stats()["o2"]
            best_assess = min(best_assess, st["phase_ms"]["assess"])
            best_rps = max(best_rps, rps)
        rows.append({"annex_width": w, "assess_ms": round(best_assess, 3),
                     "req_per_s": best_rps,
                     "assessments": st["assessments"]})
    return rows


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--budget", type=int, default=16)
    ap.add_argument("--n-keys", type=int, default=1024)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--updates-per-tick", type=int, default=2)
    ap.add_argument("--assess-every", type=int, default=2,
                    help="assess every Nth diverged window (1 = worst "
                         "case: one offline episode per served episode)")
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed runs per mode; best is reported")
    ap.add_argument("--swap-reps", type=int, default=20,
                    help="direct hot-swap latency measurements")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--annex-width", default=None, metavar="W1,W2,...",
                    help="sweep the O2 annex slice width instead of the "
                         "frozen-vs-o2 compare: serve the same stream "
                         "once per width and report the assessment "
                         "phase_ms scaling (JSON artifact: o2_annex)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as a JSON artifact (CI gate)")
    args = ap.parse_args()

    cfg = LITuneConfig(
        index_type="alex", episode_len=args.budget,
        lstm_hidden=32, mlp_hidden=64,
        ddpg=DDPGConfig(batch_size=16, seq_len=4, burn_in=1),
        o2=O2Config(divergence_threshold=0.10,
                    assess_every=args.assess_every,
                    offline_updates_per_window=args.updates_per_tick))
    o2_cfg = O2ServiceConfig(
        enabled=True, o2=cfg.o2,
        offline_updates_per_tick=args.updates_per_tick)
    requests = make_requests(args.requests, args.n_keys, seed=args.seed + 1)
    mk = lambda: LITune(cfg, seed=args.seed)  # noqa: E731

    if args.annex_width:
        widths = sorted(int(w) for w in args.annex_width.split(",") if w)
        if widths != _ANNEX_WIDTHS:
            # the pre-jax peek sized the forced device count; if argparse
            # saw something else (abbreviated flag, exotic quoting), the
            # device layout would not match the sweep — refuse instead
            raise SystemExit(
                f"--annex-width must be passed as the exact flag: the "
                f"pre-jax device sizing saw {_ANNEX_WIDTHS or 'nothing'} "
                f"but argparse parsed {widths}")
        assert widths, "--annex-width needs at least one width"
        rows = annex_sweep(mk, requests, args.budget, args.slots, o2_cfg,
                           widths, args.repeats)
        base = rows[0]["assess_ms"]
        speedup = base / max(rows[-1]["assess_ms"], 1e-9)
        print(f"# o2_annex  requests={args.requests} budget={args.budget} "
              f"n_keys={args.n_keys} slots={args.slots} "
              f"assess_every={args.assess_every} repeats={args.repeats} "
              f"devices={len(jax.devices())} widths={widths}")
        print("benchmark,annex_width,slots,assess_ms,speedup_vs_w"
              + str(widths[0]))
        for r in rows:
            print(f"o2_annex,{r['annex_width']},{args.slots},"
                  f"{r['assess_ms']:.2f},"
                  f"{base / max(r['assess_ms'], 1e-9):.2f}")
        if args.json:
            with open(args.json, "w") as f:
                json.dump({"benchmark": "o2_annex",
                           "config": {"requests": args.requests,
                                      "budget": args.budget,
                                      "n_keys": args.n_keys,
                                      "slots": args.slots,
                                      "assess_every": args.assess_every,
                                      "repeats": args.repeats,
                                      "widths": widths,
                                      "devices": len(jax.devices())},
                           "rows": rows,
                           "assess_speedup": speedup}, f, indent=2)
            print(f"# wrote {args.json}")
        return

    # warm both paths so compile time is excluded (programs are cached
    # process-wide; a real service binds them once at startup)
    bench_once(mk(), requests, args.budget, args.slots, None)
    bench_once(mk(), requests, args.budget, args.slots, o2_cfg)

    frozen_rps, _ = bench(mk, requests, args.budget, args.slots, None,
                          args.repeats)
    o2_rps, service = bench(mk, requests, args.budget, args.slots, o2_cfg,
                            args.repeats)

    st = service.stats()["o2"]
    tstats = st["alex"]
    phase = st["phase_ms"]

    # hot-swap latency, measured directly: promote the offline model over
    # the service's (already live) pools `swap_reps` times
    from repro.launch.serving import TuneRequest
    data, wl, wr = requests[-1]
    last_req = TuneRequest(
        rid=-1, data_keys=np.asarray(data),
        workload={"reads": np.asarray(wl["reads"]),
                  "inserts": np.asarray(wl["inserts"])},
        wr_ratio=wr, budget_steps=args.budget)
    tenant = service.tenants["alex"]
    n0 = len(tenant.swap_times_s)
    for _ in range(args.swap_reps):
        service._hot_swap("alex", last_req)
    swap_ms = 1e3 * float(np.mean(tenant.swap_times_s[n0:]))
    print(f"# o2_serve  requests={args.requests} budget={args.budget} "
          f"n_keys={args.n_keys} slots={args.slots} "
          f"updates_per_tick={args.updates_per_tick} "
          f"assess_every={args.assess_every} repeats={args.repeats} "
          f"devices={len(jax.devices())} "
          f"windows={tstats['windows']} diverged={tstats['diverged']} "
          f"assessed={st['assessments']} swaps={tstats['swaps']} "
          f"offline_updates={tstats['offline_updates']} "
          f"finetune_skipped={tstats['finetune_skipped']}")
    print("benchmark,mode,slots,req_per_s,vs_frozen")
    print(f"o2_serve,frozen,{args.slots},{frozen_rps:.3f},1.00")
    print(f"o2_serve,o2,{args.slots},{o2_rps:.3f},"
          f"{o2_rps / frozen_rps:.2f}")
    print(f"o2_serve,swap,{args.slots},{swap_ms:.3f} ms,-")
    print(f"o2_serve,phase_ms,{args.slots},"
          f"capture={phase['capture']:.2f}|finetune={phase['finetune']:.2f}"
          f"|assess={phase['assess']:.2f},-")

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"benchmark": "o2_serve",
                       "config": {"requests": args.requests,
                                  "budget": args.budget,
                                  "n_keys": args.n_keys,
                                  "slots": args.slots,
                                  "updates_per_tick": args.updates_per_tick,
                                  "assess_every": args.assess_every,
                                  "repeats": args.repeats,
                                  "devices": len(jax.devices())},
                       "rows": [
                           {"mode": "frozen", "req_per_s": frozen_rps,
                            "vs_frozen": 1.0},
                           {"mode": "o2", "req_per_s": o2_rps,
                            "vs_frozen": o2_rps / frozen_rps},
                       ],
                       "swap_latency_ms": swap_ms,
                       "phase_ms": phase,
                       "o2_stats": tstats}, f, indent=2)
        print(f"# wrote {args.json}")


if __name__ == "__main__":
    main()
