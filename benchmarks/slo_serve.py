"""Tail latency under bursty arrivals: static vs adaptive slot
scheduling.

    PYTHONPATH=src python -m benchmarks.slo_serve
    PYTHONPATH=src python -m benchmarks.slo_serve --bursts 4 \
        --burst-mean 10 --budget 8 --n-keys 512 --json BENCH_slo_serve.json

The other serving benches measure throughput on a pre-loaded queue; this
one measures what a *tenant* feels — queue-wait and serve-time
percentiles — under the arrival pattern that breaks static pools:
Poisson-sized bursts separated by idle gaps.  The same arrival trace is
replayed against two services:

  static   — fixed pool width (`--slots`), PR 1–3 behavior: a burst
             deeper than the pool waits out earlier waves slot by slot;
  adaptive — `AdaptiveSlotPolicy`: the scheduler grows the pool to the
             burst (ladder widths up to `--max-slots`, one cached gather
             per resize, zero program re-traces) and shrinks it back in
             the gaps.

``--policy edf`` appends a third row: `EDFSlotPolicy` admission —
tight-deadline requests enter free slots first and requests whose
budget provably cannot fit their deadline at the measured tick rate are
pre-dropped from the queue (arm ``--deadline-ms`` so deadlines exist;
the `pre_dropped` breach count shows the feasibility cut working).

Reported per mode: p50/p95/p99 queue-wait and serve-time from
`stats()["slo"]`, plus breach counts when `--deadline-ms` arms
per-request deadlines.  The headline number — static p95 queue-wait over
adaptive p95 queue-wait (>1 means adaptive wins) — is dimensionless so
the committed baseline survives runner-hardware drift; ``--json`` writes
it for the CI gate (benchmarks/check_bench.py).  Best of ``--repeats``
runs per mode (max ratio paired from per-mode minima) since CI hosts are
noisy.

``--scenario poisoned`` runs a different drill — the swap-safety smoke:

    PYTHONPATH=src python -m benchmarks.slo_serve --scenario poisoned \
        --json BENCH_swap_safety.json

A canary-armed O2 service serves a steady stream (building the tenant's
score baseline), then its offline learner is *poisoned* (params negated,
fine-tuning frozen) while the verdict seam is patched to report the
poisoned model winning every assessment — the exact failure mode the
staged swap pipeline exists to contain.  Drifted waves then fire the
divergence monitor; every forced win must die in the canary stage
(`canary_tolerance` is pinned so promotion is impossible: the drill
measures the containment machinery, not the scorer's judgment).  The
artifact reports `stats()["swaps"]`, a deterministic pre/post probe
ratio (1.0 — the incumbent was never touched), and the step-program
bind delta across the whole cycle (0 — canary lanes ride resident
executables).  check_bench.py gates all three as `swap_safety`.

``--scenario chaos`` runs the full fault battery through the health
layer (`launch/serving/health.py`):

    PYTHONPATH=src python -m benchmarks.slo_serve --scenario chaos \
        --json BENCH_chaos.json

One O2+canary service lives through six phases driven by deterministic
`FaultSite` injection: (1) healthy timed traffic (the RPS yardstick);
(2) NaN fine-tune rounds — every poisoned round must be rejected at the
publish gate until the tenant's circuit breaker quarantines it, then
clean traffic must release the breaker; (3) failed assessment
dispatches — retries exhaust, the annex demotes into degraded mode, a
timed phase shows serving continues on frozen params, then a half-open
probe recovers it; (4) a hung dispatch — the drain watchdog abandons
it and `flush_o2` returns a bounded partial-flush report; (5) forced
canary losses — rollbacks fire and strike the breaker; (6) after every
wave of every phase, a finiteness probe over all pool params and the
published snapshot (`nonfinite_served` must end at 0).  The headline
number is the degraded-over-healthy RPS ratio (~1: a demoted annex
costs serving nothing); check_bench.py gates it as `chaos` after
enforcing the hard invariants (each fault was seen, contained, and
recovered from — violations fail outright, regardless of tolerance).
"""
from __future__ import annotations

import argparse
import json
import os
import time

# expose every core as an XLA host device so pools shard; must happen
# before jax initializes (no-op if the operator already set it)
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={os.cpu_count()}")

import jax
import numpy as np

from repro.core.litune import LITune, LITuneConfig
from repro.index.workloads import sample_keys, wr_workload
from repro.launch.serving import (AdaptiveSlotPolicy, EDFSlotPolicy,
                                  ServeConfig, TuningService)
from repro.runtime import compile_cache


def make_arrivals(n_bursts: int, burst_mean: int, gap_s: float,
                  n_keys: int, seed: int):
    """One fixed trace of (arrival_time_s, data, workload, wr): bursts of
    Poisson(burst_mean) simultaneous requests, `gap_s` apart.  The trace
    is generated once and replayed against every mode, so the comparison
    is paired."""
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    arrivals, t, i = [], 0.0, 0
    for b in range(n_bursts):
        size = max(1, int(rng.poisson(burst_mean)))
        for _ in range(size):
            k = jax.random.fold_in(key, i)
            data = sample_keys(k, n_keys, "mix")
            wl, _ = wr_workload(jax.random.fold_in(k, 1), data, 1.0,
                                total=n_keys, dist="mix")
            arrivals.append((t, data, wl, 1.0))
            i += 1
        t += gap_s
    return arrivals


def drive(service: TuningService, arrivals, budget: int,
          deadline_s: float | None) -> float:
    """Replay the arrival trace in real time: submit each request at its
    arrival instant, tick the service whenever there is work, sleep
    through idle gaps.  Returns the wall-clock span."""
    t0 = time.perf_counter()
    i = 0
    while True:
        now = time.perf_counter() - t0
        while i < len(arrivals) and arrivals[i][0] <= now:
            _, data, wl, wr = arrivals[i]
            service.submit(data, wl, wr, budget_steps=budget,
                           deadline_s=deadline_s)
            i += 1
        busy = service.queue or \
            any(p.n_active for p in service.pools.values())
        if busy:
            service.step()
        elif i < len(arrivals):
            time.sleep(max(0.0, min(arrivals[i][0] - now, 0.05)))
        else:
            break
    return time.perf_counter() - t0


def bench_mode(mk_tuner, arrivals, budget: int, slots: int,
               policy_fn, deadline_s, repeats: int):
    """Best-of-`repeats` run of one mode: keep the run with the lowest
    p95 queue-wait (CI hosts are noisy; the floor is the capability)."""
    best = None
    for _ in range(repeats):
        service = TuningService(mk_tuner(), config=ServeConfig(
            slots=slots, policy=policy_fn()))
        span = drive(service, arrivals, budget, deadline_s)
        st = service.stats()
        slo = st["slo"]
        row = {"span_s": span, "slo": slo, "stats": st}
        if best is None or slo["queue_wait_ms"]["p95"] < \
                best["slo"]["queue_wait_ms"]["p95"]:
            best = row
    return best


def run_poisoned(args):
    """The swap-safety drill: a poisoned offline model, a verdict seam
    forced to declare it the winner, and a canary stage that must contain
    it.  Every knob that decides an outcome is pinned so the run is
    deterministic given `--seed` — the committed baseline is exact."""
    import dataclasses

    from repro.core.o2 import O2Config
    from repro.index.workloads import StreamConfig, stream_windows
    from repro.launch.serving import O2ServiceConfig, SwapConfig
    from repro.launch.serving import o2_runtime as o2_mod

    budget = args.budget
    slots = max(args.slots, 4)           # >=4: a canary lane + controls
    # 2048-key windows on a 64-point quantile grid separate steady from
    # drifted KS cleanly (steady noise <= ~0.13, drift >= ~0.17); smaller
    # windows drown the drift signal in sampling noise
    n_keys = max(args.n_keys, 2048)
    cfg = LITuneConfig(
        index_type="alex", episode_len=budget,
        lstm_hidden=32, mlp_hidden=64,
        o2=O2Config(divergence_threshold=0.15, n_quantiles=64,
                    assess_every=1,
                    # the poison must persist: no fine-tune rounds may
                    # move the offline tree off the negated params
                    offline_updates_per_window=0))
    service = TuningService(LITune(cfg, seed=args.seed), config=ServeConfig(
        slots=slots, seed=args.seed,
        o2=O2ServiceConfig(enabled=True, o2=cfg.o2,
                           offline_updates_per_tick=0),
        swap=SwapConfig(canary=True, canary_fraction=0.25,
                        canary_min_episodes=1,
                        # strictly negative tolerance: promotion would
                        # need the canary mean <= 0 x control, impossible
                        # for positive scores — the drill pins the
                        # decision so only the containment machinery
                        # (not the scorer's judgment) is under test
                        canary_tolerance=-1.0,
                        canary_timeout_ticks=64)))
    key = jax.random.PRNGKey(args.seed + 1)
    steady = StreamConfig(n_windows=2 * slots, base_per_window=n_keys,
                          updates_per_window=n_keys, dist="mix",
                          drift_per_window=0.0, wr_start=1.0, wr_end=1.0)
    # constant wr on purpose: the workload split depends on it, and a
    # second (reads, inserts) shape would open a second pool mid-drill
    drifted = dataclasses.replace(steady, drift_per_window=1.0)

    def serve_wave(stream_cfg, fold):
        for _, data, wl, wr in stream_windows(jax.random.fold_in(key, fold),
                                              stream_cfg):
            service.submit(data, wl, wr, budget_steps=budget,
                           noise_scale=0.02)
        service.run()
        service.flush_o2()

    # the deterministic probe: a fixed steady window under a fixed key,
    # zero noise — bitwise repeatable whenever the incumbent params are
    # untouched (post-rollback it must reproduce the pre-poison result)
    probe_cfg = dataclasses.replace(steady, n_windows=1)
    _, pdata, pwl, pwr = next(iter(stream_windows(
        jax.random.fold_in(key, 99), probe_cfg)))
    probe_key = jax.random.PRNGKey(args.seed + 7)

    def probe():
        rid = service.submit(pdata, pwl, pwr, budget_steps=budget,
                             deterministic=True, key=probe_key)
        service.run()
        return float(service.results[rid]["best_runtime_ns"])

    # phase A: steady traffic, twice (program warmup: admission-wave
    # widths are staggering-dependent, one pass can miss one), then the
    # pre-poison probe and the bind-accounting snapshot
    print("# swap_safety: steady warmup ...")
    serve_wave(steady, fold=0)
    serve_wave(steady, fold=1)
    r_pre = probe()
    st0 = service.stats()
    binds0 = st0["program_misses"] + st0["programs_resident"]

    # phase B: poison the offline model (a catastrophically bad
    # fine-tune) and force every pooled assessment to declare it the
    # winner; drifted waves fire the divergence monitor until the canary
    # stage has rolled the candidate back
    print("# swap_safety: poisoning offline model, serving drift ...")
    tenant = service.tenants["alex"]
    tenant.offline["params"] = jax.tree.map(lambda x: -x,
                                            tenant.offline["params"])
    tenant.ready_params = jax.tree.map(lambda x: -x, tenant.ready_params)
    real_pooled_best = o2_mod._pooled_best
    o2_mod._pooled_best = lambda r0, runtimes: 0.0
    rounds = 0
    try:
        while service.stats()["swaps"]["rolled_back"] < 1 and rounds < 8:
            serve_wave(drifted, fold=10 + rounds)
            rounds += 1
    finally:
        o2_mod._pooled_best = real_pooled_best

    # phase C: the post-rollback probe — same window, same key; a lane
    # fraction carried the poison briefly, the incumbent never moved
    r_post = probe()
    st1 = service.stats()
    new_binds = st1["program_misses"] + st1["programs_resident"] - binds0
    sw = st1["swaps"]
    ratio = r_pre / max(r_post, 1e-9)

    print(f"# swap_safety  slots={slots} budget={budget} n_keys={n_keys} "
          f"rounds={rounds} seed={args.seed}")
    print("benchmark,candidates,canaried,rolled_back,promoted,deferred,"
          "probe_ratio,new_binds")
    print(f"swap_safety,{sw['candidates']},{sw['canaried']},"
          f"{sw['rolled_back']},{sw['promoted']},{sw['deferred']},"
          f"{ratio:.6f},{new_binds}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"benchmark": "swap_safety",
                       "config": {"slots": slots, "budget": budget,
                                  "n_keys": n_keys, "seed": args.seed,
                                  "rounds": rounds,
                                  "devices": len(jax.devices())},
                       "swaps": sw,
                       "o2": {"windows": st1["o2"]["alex"]["windows"],
                              "diverged": st1["o2"]["alex"]["diverged"],
                              "assessments": st1["o2"]["assessments"]},
                       "r_pre_ns": r_pre, "r_post_ns": r_post,
                       "post_rollback_ns_ratio": ratio,
                       "new_binds": new_binds}, f, indent=2)
        print(f"# wrote {args.json}")


def run_chaos(args):
    """The fault battery: every failure mode health.py contains, in one
    continuous service lifetime, with hard invariants on the artifact.
    Faults are injected per-site (`guard.sites[...] = FaultSite(...)`)
    so each phase arms exactly the fault it is about and nothing else."""
    import time as _time

    from repro.core.o2 import O2Config
    from repro.launch.serving import (HealthConfig, O2ServiceConfig,
                                      SwapConfig)
    from repro.launch.serving import o2_runtime as o2_mod
    from repro.runtime.fault import FaultSite

    budget = args.budget
    slots = max(args.slots, 4)           # >=4: a canary lane + controls
    n_keys = args.n_keys
    # KS effectively off: divergence fires purely on W/R shift, which is
    # exact — every assessment trigger in the drill is deterministic.
    # The tiny DDPG shape matters: fine-tune rounds must actually
    # complete on budget-4 episodes or the learner-side fault sites
    # (NaN rounds, publish gates) never execute
    from repro.core.ddpg import DDPGConfig
    cfg = LITuneConfig(
        index_type="alex", episode_len=budget,
        lstm_hidden=16, mlp_hidden=32,
        ddpg=DDPGConfig(seq_len=3, burn_in=1, batch_size=8),
        o2=O2Config(divergence_threshold=10.0, wr_shift_threshold=0.5,
                    assess_every=1, offline_updates_per_window=2))
    health = HealthConfig(
        dispatch_timeout_s=2.0,          # hang phase: watchdog horizon
        dispatch_retries=1, retry_backoff_s=0.01, backoff_seed=args.seed,
        annex_failure_threshold=1,
        annex_cooloff_s=30.0,            # spans the degraded timed phase
        quarantine_threshold=2, quarantine_windows=2,
        flush_deadline_s=30.0)
    service = TuningService(LITune(cfg, seed=args.seed), config=ServeConfig(
        slots=slots, seed=args.seed,
        o2=O2ServiceConfig(enabled=True, o2=cfg.o2),
        swap=SwapConfig(canary=True, canary_fraction=0.25,
                        canary_min_episodes=1, canary_timeout_ticks=64),
        health=health))
    guard = service.o2rt.health
    key = jax.random.PRNGKey(args.seed + 1)
    fold = 0
    nonfinite_served = 0
    # alternating W/R so every wave past the first carries divergence
    # triggers (the reference anchors at wr=1)
    wave_wrs = [1.0, 3.0, 1.0, 3.0]
    timed_waves = 4

    def _finite(tree):
        return all(bool(np.all(np.isfinite(np.asarray(leaf))))
                   for leaf in jax.tree.leaves(jax.device_get(tree)))

    def serve_wave(flush=True):
        nonlocal fold, nonfinite_served
        for i, wr in enumerate(wave_wrs):
            k = jax.random.fold_in(key, 131 * fold + i)
            data = sample_keys(k, n_keys, "mix")
            wl, _ = wr_workload(jax.random.fold_in(k, 1), data, wr,
                                total=n_keys, dist="mix")
            service.submit(data, wl, wr, budget_steps=budget)
        fold += 1
        service.run()
        if flush:
            service.flush_o2()
        # the drill's core invariant, probed after EVERY wave: nothing
        # non-finite ever reaches a pool or the published snapshot
        for pool in service.pools.values():
            if not _finite(pool.params):
                nonfinite_served += 1
        if not _finite(service.tenants["alex"].ready_params):
            nonfinite_served += 1

    def hp():
        return service.stats()["health"]

    def timed_phase():
        t0 = _time.perf_counter()
        for _ in range(timed_waves):
            serve_wave()
        return timed_waves * len(wave_wrs) / (_time.perf_counter() - t0)

    # phase 1: warmup (program binds) + the healthy RPS yardstick
    print("# chaos: healthy traffic ...")
    serve_wave()
    serve_wave()
    rps_healthy = timed_phase()

    # phase 2: NaN fine-tune rounds until the publish gate has rejected
    # enough to quarantine the tenant; then clean traffic releases it
    print("# chaos: NaN fine-tune rounds -> quarantine ...")
    guard.sites["nan_round"] = FaultSite(fire_at=tuple(range(64)))
    rounds = 0
    while hp()["quarantines"] < 1 and rounds < 8:
        serve_wave()
        rounds += 1
    guard.sites["nan_round"] = FaultSite()      # disarm
    print("# chaos: clean traffic -> quarantine release ...")
    while hp()["quarantine_releases"] < 1 and rounds < 16:
        serve_wave()
        rounds += 1

    # phase 3: failed assessment dispatches exhaust their retries and
    # demote the annex; serving continues (timed) on frozen params;
    # after the cooloff a half-open probe recovers it.  The cooloff is
    # rewound rather than slept through — the drill injects time the
    # same way it injects faults
    print("# chaos: failed dispatches -> annex demotion ...")
    guard.sites["assess_fail"] = FaultSite(fire_at=(0, 1))
    while hp()["annex_demotions"] < 1 and rounds < 24:
        serve_wave()
        rounds += 1
    print("# chaos: degraded serving (timed) ...")
    rps_degraded = timed_phase()
    state_during_degraded = hp()["state"]
    guard._degraded_at -= health.annex_cooloff_s     # cooloff elapses
    print("# chaos: half-open probe -> recovery ...")
    while hp()["annex_recoveries"] < 1 and rounds < 32:
        serve_wave()
        rounds += 1

    # phase 4: one hung dispatch; the drain watchdog abandons it and
    # flush_o2 comes back bounded with a truthful report
    print("# chaos: hung dispatch -> bounded flush ...")
    guard.sites["assess_hang"] = FaultSite(fire_at=(0,))
    serve_wave(flush=False)
    t0 = _time.perf_counter()
    flush_report = service.flush_o2()
    flush_s = _time.perf_counter() - t0
    guard.sites["assess_hang"] = FaultSite()
    # the abandon was (correctly) an annex failure: the annex is demoted
    # again.  Elapse this cooloff too, so phase 5's assessments dispatch
    if guard._degraded_at is not None:
        guard._degraded_at -= health.annex_cooloff_s

    # phase 5: forced canary losses — the rollback arm of the breaker
    print("# chaos: forced canary losses -> rollbacks ...")
    guard.sites["canary_loss"] = FaultSite(fire_at=(0, 1))
    real_pooled_best = o2_mod._pooled_best
    o2_mod._pooled_best = lambda r0, runtimes: -1.0
    try:
        while service.stats()["swaps"]["rolled_back_canary"] < 1 \
                and rounds < 40:
            serve_wave()
            rounds += 1
    finally:
        o2_mod._pooled_best = real_pooled_best

    st = service.stats()
    h = st["health"]
    sw = st["swaps"]
    ratio = rps_degraded / max(rps_healthy, 1e-9)
    print(f"# chaos  slots={slots} budget={budget} n_keys={n_keys} "
          f"waves={fold} seed={args.seed} "
          f"state_during_degraded={state_during_degraded}")
    print("benchmark,nonfinite_served,rejected_params,quarantines,"
          "releases,demotions,recoveries,dropped,rolled_back_canary,"
          "degraded_over_healthy_rps,flush_s")
    print(f"chaos,{nonfinite_served},{h['rejected_params']},"
          f"{h['quarantines']},{h['quarantine_releases']},"
          f"{h['annex_demotions']},{h['annex_recoveries']},"
          f"{h['dropped_dispatches']},{sw['rolled_back_canary']},"
          f"{ratio:.3f},{flush_s:.3f}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"benchmark": "chaos",
                       "config": {"slots": slots, "budget": budget,
                                  "n_keys": n_keys, "seed": args.seed,
                                  "waves": fold,
                                  "timed_waves": timed_waves,
                                  "devices": len(jax.devices()),
                                  "flush_deadline_s":
                                      health.flush_deadline_s},
                       "health": h,
                       "swaps": sw,
                       "nonfinite_served": nonfinite_served,
                       "state_during_degraded": state_during_degraded,
                       "rps_healthy": rps_healthy,
                       "rps_degraded": rps_degraded,
                       "degraded_over_healthy_rps": ratio,
                       "flush_s": flush_s,
                       "flush_report": flush_report}, f, indent=2)
        print(f"# wrote {args.json}")


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--bursts", type=int, default=4)
    ap.add_argument("--burst-mean", type=int, default=8,
                    help="Poisson mean burst size")
    ap.add_argument("--gap-s", type=float, default=0.5,
                    help="idle gap between bursts (seconds)")
    ap.add_argument("--budget", type=int, default=4)
    ap.add_argument("--n-keys", type=int, default=256)
    ap.add_argument("--slots", type=int, default=2,
                    help="static pool width (and the adaptive floor)")
    ap.add_argument("--max-slots", type=int, default=8,
                    help="adaptive pool ceiling (keep near the burst "
                         "size: wider pools pay idle-lane compute)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="arm per-request deadlines (breaches reported)")
    ap.add_argument("--policy", default=None, choices=["edf"],
                    help="append an EDF admission row (earliest deadline "
                         "first + feasibility pre-drops)")
    ap.add_argument("--repeats", type=int, default=2,
                    help="runs per mode; best p95 queue-wait is reported")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as a JSON artifact (CI gate)")
    ap.add_argument("--scenario", default="bursts",
                    choices=["bursts", "poisoned", "chaos"],
                    help="'bursts' races static vs adaptive scheduling; "
                         "'poisoned' runs the swap-safety drill (a forced"
                         "-win poisoned model must die in the canary "
                         "stage); 'chaos' runs the health-layer fault "
                         "battery (see module docstring)")
    args = ap.parse_args()

    if args.scenario == "poisoned":
        return run_poisoned(args)
    if args.scenario == "chaos":
        return run_chaos(args)

    cfg = LITuneConfig(index_type="alex", episode_len=args.budget,
                       lstm_hidden=32, mlp_hidden=64)
    mk = lambda: LITune(cfg, seed=args.seed)  # noqa: E731
    arrivals = make_arrivals(args.bursts, args.burst_mean, args.gap_s,
                             args.n_keys, args.seed + 1)
    deadline_s = (args.deadline_ms / 1e3
                  if args.deadline_ms is not None else None)
    static_policy = lambda: None  # noqa: E731  (service default: static)
    adaptive_policy = lambda: AdaptiveSlotPolicy(  # noqa: E731
        min_slots=args.slots, max_slots=args.max_slots, shrink_patience=2)
    edf_policy = lambda: EDFSlotPolicy()  # noqa: E731

    def run_static():
        return bench_mode(mk, arrivals, args.budget, args.slots,
                          static_policy, deadline_s, args.repeats)

    def run_adaptive():
        return bench_mode(mk, arrivals, args.budget, args.slots,
                          adaptive_policy, deadline_s, args.repeats)

    def run_edf():
        return bench_mode(mk, arrivals, args.budget, args.slots,
                          edf_policy, deadline_s, args.repeats)

    # warm both modes with the full trace so every pool width's programs
    # are resident before the timed runs (a real service binds them at
    # startup; the cache is process-wide).  Two warm drives per mode:
    # admission wave widths depend on timing, so a single pass can miss
    # a width whose first-compile would then land mid-measurement
    bench_mode(mk, arrivals, args.budget, args.slots, static_policy,
               deadline_s, 2)
    bench_mode(mk, arrivals, args.budget, args.slots, adaptive_policy,
               deadline_s, 2)

    modes = [("static", run_static), ("adaptive", run_adaptive)]
    if args.policy == "edf":
        modes.append(("edf", run_edf))
    rows = []
    for mode, run in modes:
        best = run()
        slo = best["slo"]
        st = best["stats"]
        rows.append({
            "mode": mode,
            "queue_wait_ms": slo["queue_wait_ms"],
            "serve_ms": slo["serve_ms"],
            "breaches": slo["breaches"],
            "span_s": best["span_s"],
            "requests": slo["tracked"],
            "resize_events": st["scheduler"]["resize_events"],
            "peak_slots": max(p["peak_slots"]
                              for p in st["per_pool"].values()),
        })

    p95_static = rows[0]["queue_wait_ms"]["p95"]
    p95_adaptive = rows[1]["queue_wait_ms"]["p95"]
    ratio = p95_static / max(p95_adaptive, 1e-9)

    print(f"# slo_serve  bursts={args.bursts} burst_mean={args.burst_mean} "
          f"gap_s={args.gap_s} budget={args.budget} n_keys={args.n_keys} "
          f"slots={args.slots} max_slots={args.max_slots} "
          f"deadline_ms={args.deadline_ms} repeats={args.repeats} "
          f"devices={len(jax.devices())}")
    print("benchmark,mode,slots,p50_wait_ms,p95_wait_ms,p99_wait_ms,"
          "p95_serve_ms,resizes,peak_slots")
    for r in rows:
        print(f"slo_serve,{r['mode']},{args.slots},"
              f"{r['queue_wait_ms']['p50']:.1f},"
              f"{r['queue_wait_ms']['p95']:.1f},"
              f"{r['queue_wait_ms']['p99']:.1f},"
              f"{r['serve_ms']['p95']:.1f},"
              f"{r['resize_events']},{r['peak_slots']}")
    print(f"slo_serve,p95_wait_static_over_adaptive,{args.slots},"
          f"{ratio:.2f},,,,,")
    if args.deadline_ms is not None:
        for r in rows:
            print(f"slo_serve,{r['mode']}_breaches,{args.slots},"
                  f"{r['breaches']},,,,,")

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"benchmark": "slo_serve",
                       "config": {"bursts": args.bursts,
                                  "burst_mean": args.burst_mean,
                                  "gap_s": args.gap_s,
                                  "budget": args.budget,
                                  "n_keys": args.n_keys,
                                  "slots": args.slots,
                                  "max_slots": args.max_slots,
                                  "deadline_ms": args.deadline_ms,
                                  "repeats": args.repeats,
                                  "devices": len(jax.devices())},
                       "rows": rows,
                       "p95_wait_static_over_adaptive": ratio}, f,
                      indent=2)
        print(f"# wrote {args.json}")


if __name__ == "__main__":
    main()
