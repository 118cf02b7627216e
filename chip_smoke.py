#!/usr/bin/env python3
"""Chip smoke: the LITune tuning service end to end on a TPU.

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # the 4-chip serving path vs 1 device

Size: ALEX (`configs/litune_alex.py`) with the paper agent's widths
(LSTM 128, MLP 256).  Each request holds 2^20 keys from the SOSD-style
`mix` generator and 2^20 operations at the paper's read-heavy (W/R 1/3),
balanced (1) and write-heavy (3) mixes; the service runs 4 slots and 10
tuning steps per request.  2^20 keys is a cut: it is the largest key set
the float32 key representation holds (99.2% of `mix` keys stay
distinct; larger sets collapse distinct keys).

Phases, one line each:
  (a) the device: fails unless JAX's first device is a TPU;
  (b) the kernels at 2^20 keys: the compiled probe against `searchsorted`
      (positions equal, on `mix` and on `osm`, whose overflowing reads
      go to searchsorted in bounded chunks) and the compiled fused
      capture against its jnp reference (bitwise);
  (c) a frozen service: 6 requests (2 per mix) through `TuningService`,
      each request's default runtime against the serial `LITune.tune`
      (1e-5 relative) and its best params re-evaluated under the jnp
      reference probe against its fastest step's runtime (1e-4 relative);
  (d) an O2 service over 4 drifting windows: fine-tune rounds and
      assessments ran, every parameter tree is finite, and capture ran
      through the fused kernel.
`--chips 4` runs only the multi-chip comparison: the same request
stream on a 4-device topology (slots=8) and on one device.

Any failed check raises, so the exit code is non-zero.  The last line of
standard output is one JSON object naming the device; without a TPU the
script exits non-zero before printing one.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.runtime import compile_cache  # noqa: E402  (needs the repo's src/)

LOG2_KEYS = 20
N_KEYS = 1 << LOG2_KEYS
N_OPS = 1 << LOG2_KEYS
MIXES = {"read_heavy": 1.0 / 3.0, "balanced": 1.0, "write_heavy": 3.0}
PER_MIX = 2
SLOTS = 4
BUDGET = 10
HORIZON = 16                 # per-pool step horizon (>= BUDGET)
R0_RTOL = 1e-5
BEST_RTOL = 1e-4
SEED = 0


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


@dataclasses.dataclass
class Request:
    mix: str
    wr: float
    data: object
    workload: dict


def require_tpu(count: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is "
                         f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < count:
        raise SystemExit(f"need {count} TPU devices, JAX sees {len(devs)}")
    return devs


def agent():
    """ALEX at `LITuneConfig`'s widths, with the ET-MDP runtime budget
    held at its per-operation value.

    `index.cost.RUNTIME_BUDGET_NS` is a total over one step's operations,
    set for the 8,192 operations of a `repro.launch.tune` request
    (12,207 ns/op).  At 2^20 operations the default parameters alone
    exceed it (415-3,059 ns/op), so every step costs 1, every episode
    ends at its second step, and no replay sequence is long enough for
    an O2 fine-tune round.  The smoke scales the budget to its 2^20
    operations; the memory budget stays as it is."""
    from repro.configs.litune_alex import CONFIG
    from repro.core.litune import LITune, LITuneConfig
    from repro.index import cost

    @dataclasses.dataclass(frozen=True)
    class SmokeConfig(LITuneConfig):
        def env_cfg(self):
            return dataclasses.replace(
                super().env_cfg(),
                runtime_budget=cost.RUNTIME_BUDGET_NS * N_OPS / 8192)

    cfg = SmokeConfig(**{f.name: getattr(CONFIG, f.name)
                         for f in dataclasses.fields(CONFIG)})
    return LITune(dataclasses.replace(cfg, episode_len=BUDGET), seed=SEED)


def make_requests(seed: int) -> list[Request]:
    """PER_MIX requests per mix, data made on the device from `seed`."""
    import jax
    import numpy as np
    from repro.index.workloads import sample_keys, wr_workload
    key = jax.random.PRNGKey(seed)
    out = []
    for i, (mix, wr) in enumerate((m, w) for m, w in MIXES.items()
                                  for _ in range(PER_MIX)):
        k = jax.random.fold_in(key, i)
        data = sample_keys(k, N_KEYS, "mix")
        wl, _ = wr_workload(jax.random.fold_in(k, 1), data, wr,
                            total=N_OPS, dist="mix")
        out.append(Request(mix, wr, np.asarray(data),
                           {f: np.asarray(v) for f, v in wl.items()}))
    return out


def bytes_in_use(device) -> int:
    return device.memory_stats()["bytes_in_use"]


def custom_calls(compiled_text: str) -> int:
    return compiled_text.count('custom_call_target="tpu_custom_call"')


# ------------------------------------------------------------ (b) kernels
def phase_kernels(req: Request, tuner):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.replay import wide_dim
    from repro.kernels import dispatch
    from repro.kernels.fused_tick.ops import fused_capture
    from repro.kernels.fused_tick.ref import FIELD_ORDER, fused_capture_ref
    from repro.index.workloads import sample_keys, wr_workload
    from repro.kernels.index_probe.ops import (auto_ocap, auto_qcap,
                                               auto_tile,
                                               predecessor_positions,
                                               probe_ranks)

    mode = dispatch.resolve(None)
    check(mode == "compiled", f"kernel mode resolved to {mode!r}")
    keys = jnp.asarray(req.data)
    reads = jnp.asarray(req.workload["reads"])
    m, tile = reads.shape[0], auto_tile(N_KEYS)
    ranks, n_over = probe_ranks(keys, reads, tile=tile, mode=mode)
    want = jnp.searchsorted(keys, reads, side="right").astype(jnp.int32)
    check(bool(jnp.array_equal(ranks, want)),
          "probe ranks differ from searchsorted")
    pos = predecessor_positions(keys, reads)
    check(bool(jnp.array_equal(pos, jnp.clip(want - 1, 0, N_KEYS - 1))),
          "predecessor positions differ from searchsorted - 1")
    n_over = int(n_over)
    print(f"[b] probe ({mode}): {m} {req.mix} reads x 2^{LOG2_KEYS} keys, "
          f"tile {tile}, qcap {auto_qcap(m, -(-N_KEYS // tile))}: "
          f"positions equal searchsorted; kernel answered "
          f"{1 - n_over / m:.6f} of reads ({n_over} over capacity, "
          f"answered in overflow chunks)", flush=True)
    # the same shapes on the `osm` set, whose misses pile into the tiles
    # that span gaps between its clusters: the overflow goes through
    # chunks of `auto_ocap`, not through searchsorted over every read
    k = jax.random.PRNGKey(SEED + 1)
    keys = sample_keys(k, N_KEYS, "osm")
    wl, _ = wr_workload(jax.random.fold_in(k, 1), keys, req.wr,
                        total=N_OPS, dist="osm")
    reads = wl["reads"]
    ranks, n_over = probe_ranks(keys, reads, tile=tile, mode=mode)
    want = jnp.searchsorted(keys, reads, side="right").astype(jnp.int32)
    check(bool(jnp.array_equal(ranks, want)),
          "osm probe ranks differ from searchsorted")
    n_over, ocap = int(n_over), auto_ocap(m, tile)
    print(f"[b] probe ({mode}): {m} osm reads x 2^{LOG2_KEYS} keys: "
          f"positions equal searchsorted; kernel answered "
          f"{1 - n_over / m:.6f} of reads ({n_over} over capacity, "
          f"answered in {-(-n_over // ocap)} overflow chunk(s) of "
          f"{ocap})", flush=True)

    net = tuner.cfg.net_cfg()
    dims = {"obs": net.obs_dim, "next_obs": net.obs_dim}
    dims.update({f: net.lstm_hidden for f in ("h_a", "c_a", "h_q", "c_q")})
    wide, k_steps = wide_dim(net.obs_dim, net.lstm_hidden), 8
    ks = jax.random.split(jax.random.PRNGKey(SEED), len(FIELD_ORDER) + 1)
    new = {f: jax.random.normal(ks[i], (k_steps, SLOTS, dims[f]))
           for i, f in enumerate(FIELD_ORDER)}
    cap = jax.random.normal(ks[-1], (SLOTS, HORIZON, wide))
    off = jnp.asarray([0, 3, HORIZON - k_steps, HORIZON], jnp.int32)
    got = np.asarray(fused_capture(cap, new, off, mode=mode))
    ref = np.asarray(fused_capture_ref(cap, new, off))
    check(np.array_equal(got.view(np.uint32), ref.view(np.uint32)),
          "fused capture differs from fused_capture_ref")
    print(f"[b] fused capture ({mode}): B={SLOTS} H={HORIZON} K={k_steps} "
          f"wide={wide}: bitwise equal to fused_capture_ref", flush=True)


# ---------------------------------------------------------- (c) serving
def serve(tuner, topology, slots: int, reqs: list[Request], o2=None):
    """One service over `reqs`; returns (service, summaries, seconds)."""
    from repro.launch.serving import ServeConfig, TuningService
    kw = {"o2": o2} if o2 is not None else {}
    svc = TuningService(tuner, config=ServeConfig(
        slots=slots, horizon_cap=HORIZON, seed=SEED, topology=topology,
        **kw))
    t0 = time.perf_counter()
    rids = [svc.submit(r.data, r.workload, r.wr, budget_steps=BUDGET)
            for r in reqs]
    res = svc.run()
    return svc, [res[rid] for rid in rids], time.perf_counter() - t0


@functools.lru_cache(maxsize=None)
def _reeval_program():
    import jax
    from repro.index import env as E
    return jax.jit(lambda cfg, *a: E.evaluate_params(cfg, *a)[0],
                   static_argnums=0)


def reevaluate(tuner, req: Request, best_params: dict) -> float:
    """Runtime of `best_params` on the request, under the jnp reference
    probe (`searchsorted`), on the chip."""
    import jax.numpy as jnp
    from repro.kernels.dispatch import KernelConfig
    cfg = dataclasses.replace(tuner.cfg.env_cfg(),
                              kernel=KernelConfig(mode="ref"))
    p = {k: jnp.float32(v) for k, v in best_params.items()}
    return float(_reeval_program()(cfg, p, req.data, req.workload,
                                   jnp.float32(req.wr)))


def check_best(tuner, req: Request, res: dict, tag: str) -> float:
    """`best_params` is the action of the episode's fastest step: its
    runtime re-evaluated under the reference probe must equal that
    step's runtime (`best_runtime_ns` is the lesser of it and r0)."""
    best_step = min(res["runtimes"])
    check(res["best_runtime_ns"] == min(res["r0_ns"], best_step),
          f"{tag}: best_runtime_ns is not min(r0, step runtimes)")
    re = reevaluate(tuner, req, res["best_params"])
    err = rel(re, best_step)
    check(err <= BEST_RTOL, f"{tag}: re-evaluated best params {re} vs "
          f"the fastest step's runtime {best_step} (rel {err:.2e})")
    return err


def serial_reference(tuner, reqs: list[Request]) -> list[dict]:
    """The serial `LITune.tune` summary of every request; then one
    re-evaluation per request shape at the default params, which
    compiles the re-evaluation programs ahead of their use."""
    from repro.index import alex
    out = [tuner.tune(r.data, r.workload, r.wr, budget_steps=BUDGET)
           for r in reqs]
    for r in {r.mix: r for r in reqs}.values():
        reevaluate(tuner, r, alex.DEFAULTS)
    return out


def phase_frozen(tuner, topology, reqs: list[Request]):
    # the serial reference runs in a second thread: its programs compile
    # while the service's first pass compiles its own (it reads the
    # agent's params and advances only the agent's own PRNG key, which
    # the service never reads)
    with ThreadPoolExecutor(1) as ex:
        serial_f = ex.submit(serial_reference, tuner, reqs)
        svc, first, t_first = serve(tuner, topology, SLOTS, reqs)
        serials = serial_f.result()
    # steady pass: same requests, every program already compiled
    misses = svc.program_misses
    t0 = time.perf_counter()
    rids = [svc.submit(r.data, r.workload, r.wr, budget_steps=BUDGET)
            for r in reqs]
    res = svc.run()
    t_steady = time.perf_counter() - t0
    misses = svc.program_misses - misses
    for r, rid in zip(first, rids):
        check(res[rid]["r0_ns"] == r["r0_ns"],
              "steady pass changed a default runtime")
    worst_r0 = worst_best = 0.0
    for i, (req, r, serial) in enumerate(zip(reqs, first, serials)):
        err = rel(r["r0_ns"], serial["r0_ns"])
        check(err <= R0_RTOL, f"request {i}: r0 {r['r0_ns']} vs serial "
              f"{serial['r0_ns']} (rel {err:.2e})")
        worst_r0 = max(worst_r0, err)
        worst_best = max(worst_best, check_best(tuner, req, r, f"req {i}"))
        print(f"[c] req {i} {req.mix:11s} reads={req.workload['reads'].shape[0]:7d}"
              f" default {r['r0_ns']:.6f} ns/op  tuned "
              f"{r['best_runtime_ns']:.6f} ns/op  steps {r['steps']} "
              f"violations {r['violations']:g}",
              flush=True)
    pk = next(iter(svc.pools))
    pool = svc.pools[pk]
    text = svc._pool_step_program(pk, pool, 8).lower(
        pool.params, pool.carry, pool.noise_dev()).compile().as_text()
    n_cc = custom_calls(text)
    check(n_cc >= 1, "the serving step program holds no tpu_custom_call")
    ratio = sum(r["best_runtime_ns"] / r["r0_ns"] for r in first) / len(first)
    print(f"[c] frozen service: {len(reqs)} requests, {len(svc.pools)} pools"
          f" x {SLOTS} slots, budget {BUDGET}: r0 vs serial LITune.tune "
          f"max rel {worst_r0:.3e} (<= {R0_RTOL}), re-evaluated best max rel"
          f" {worst_best:.3e} (<= {BEST_RTOL}); step program holds {n_cc} "
          f"tpu_custom_call", flush=True)
    print(f"[c] first pass {t_first:.3f} s (compile + run, the serial "
          f"reference compiling alongside), steady pass "
          f"{t_steady:.3f} s ({misses} program misses), compile "
          f"~{t_first - t_steady:.3f} s, "
          f"{len(reqs) / t_steady:.4f} req/s steady, mean tuned/default "
          f"{ratio:.6f}", flush=True)


# ---------------------------------------------------------------- (d) O2
def phase_o2(tuner, topology):
    import jax
    import numpy as np
    from repro.index.workloads import StreamConfig, stream_windows
    from repro.launch.serving import O2ServiceConfig

    # W/R held at 1 so every window shares one pool; the key
    # distribution drifts far enough that the monitor fires
    scfg = StreamConfig(n_windows=4, base_per_window=N_KEYS,
                        updates_per_window=N_OPS, dist="mix",
                        drift_per_window=1.0, wr_start=1.0, wr_end=1.0)
    reqs = [Request("balanced", wr, np.asarray(d),
                    {f: np.asarray(v) for f, v in wl.items()})
            for _, d, wl, wr in stream_windows(
                jax.random.PRNGKey(SEED + 7), scfg)]
    svc, res, t_run = serve(tuner, topology, SLOTS, reqs,
                            o2=O2ServiceConfig(enabled=True))
    print(f"[d] steps per window {[r['steps'] for r in res]}, violations "
          f"{[r['violations'] for r in res]}", flush=True)
    t0 = time.perf_counter()
    report = svc.flush_o2()
    t_flush = time.perf_counter() - t0
    st = svc.stats()
    o2, health = st["o2"], st["health"]
    tenant = o2[tuner.cfg.index_type]
    check(tenant["offline_updates"] > 0, "no fine-tune round ran")
    check(o2["assessments"] > 0, "no assessment ran")
    check(not report["deadline_hit"], f"O2 flush hit its deadline: {report}")
    check(health["rejected_params"] == 0 and health["quarantines"] == 0
          and health["state"] == "healthy", f"health: {health}")
    for t in svc.tenants.values():
        for tree in (t.online, t.offline):
            check(tree is not None and all(
                bool(np.all(np.isfinite(np.asarray(x))))
                for x in jax.tree.leaves(tree)), "a non-finite O2 tree")
    pk = next(iter(svc.pools))
    pool = svc.pools[pk]
    check(svc._fused(pool), "the O2 pool does not run the fused tick")
    text = svc._pool_step_program(pk, pool, 8).lower(
        pool.params, pool.carry, pool.noise_dev(), pool.ensure_cap(),
        pool.steps_taken.astype(np.int32)).compile().as_text()
    n_cc = custom_calls(text)
    check(n_cc >= 2, f"fused step program holds {n_cc} tpu_custom_call "
          f"(probe + capture expected)")
    print(f"[d] O2 service: {len(reqs)} drifting windows x 2^{LOG2_KEYS} "
          f"keys: {tenant['offline_updates']} offline updates, "
          f"{o2['assessments']} assessments, {tenant['diverged']} diverged,"
          f" {tenant['swaps']} swaps, health {health['state']} "
          f"(rejected {health['rejected_params']}); fused step program "
          f"holds {n_cc} tpu_custom_call; run {t_run:.3f} s, flush "
          f"{t_flush:.3f} s", flush=True)
    for i, r in enumerate(res):
        check(np.isfinite(r["best_runtime_ns"]), f"window {i} non-finite")


# ------------------------------------------------------------ four chips
def four_chips(tuner, devs):
    """The 4-device serving path against one device, same stream: each
    (topology, mix) runs in its own service and thread, so their
    compiles overlap.  Every lane of a pool computes each tick, active
    or not, so the 1-device services hold only the PER_MIX slots their
    requests fill (a lane's result does not depend on the slot count)."""
    from repro.launch.serving import ServingTopology
    slots = {"4dev": 2 * len(devs[:4]), "1dev": PER_MIX}
    topos = {"4dev": ServingTopology.host(slots["4dev"], devices=devs[:4]),
             "1dev": ServingTopology.host(slots["1dev"], devices=devs[:1])}
    reqs = make_requests(SEED)
    jobs = [(name, mix) for name in topos for mix in MIXES]
    by_mix = {mix: [r for r in reqs if r.mix == mix] for mix in MIXES}
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(jobs)) as ex:
        futs = {job: ex.submit(serve, tuner, topos[job[0]], slots[job[0]],
                               by_mix[job[1]]) for job in jobs}
        out = {job: f.result() for job, f in futs.items()}
    t_all = time.perf_counter() - t0
    worst_r0 = worst_best = worst_x = 0.0
    for mix in MIXES:
        _, res4, _ = out[("4dev", mix)]
        _, res1, _ = out[("1dev", mix)]
        for j, (req, a, b) in enumerate(zip(by_mix[mix], res4, res1)):
            err = rel(a["r0_ns"], b["r0_ns"])
            check(err <= R0_RTOL, f"{mix} {j}: r0 4dev {a['r0_ns']} vs "
                  f"1dev {b['r0_ns']}")
            worst_r0 = max(worst_r0, err)
            worst_best = max(worst_best,
                             check_best(tuner, req, a, f"{mix} {j} 4dev"))
            worst_x = max(worst_x, rel(a["best_runtime_ns"],
                                       b["best_runtime_ns"]))
    t4 = topos["4dev"]
    print(f"[4] {len(reqs)} requests, slots={slots}: 4dev pools "
          f"{[list(s.device_ids) for s in t4.pool_slices]}, annex "
          f"{list(t4.annex.device_ids)} (shared={t4.annex_shared}); 1dev "
          f"pools {[list(s.device_ids) for s in topos['1dev'].pool_slices]}",
          flush=True)
    slowest = {n: round(max(out[(n, m)][2] for m in MIXES), 3)
               for n in topos}
    in_use = {d.id: bytes_in_use(d) for d in devs[:4]}
    check(all(v > 0 for v in in_use.values()),
          f"a serving device holds no bytes: {in_use}")
    print(f"[4] bytes_in_use per device (services alive): {in_use}",
          flush=True)
    print(f"[4] r0 4dev vs 1dev max rel {worst_r0:.3e} (<= {R0_RTOL}); 4dev"
          f" re-evaluated best max rel {worst_best:.3e} (<= {BEST_RTOL}); "
          f"best 4dev vs 1dev max rel {worst_x:.3e}; {t_all:.3f} s for both"
          f" topologies (compile + run; slowest service {slowest} s)",
          flush=True)
    del out


def main():
    ap = argparse.ArgumentParser(
        description="LITune tuning service end to end on a TPU")
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the multi-chip path and its "
                         "one-device comparison")
    args = ap.parse_args()
    cache = compile_cache.enable()
    devs = require_tpu(args.chips)
    warm = sum(1 for _ in Path(cache).glob("*")) if Path(cache).is_dir() else 0
    import jax
    from repro.launch.serving import ServingTopology

    print(f"[a] device: platform {devs[0].platform}, kind "
          f"{devs[0].device_kind!r}, {len(devs)} visible; JAX "
          f"{jax.__version__}; compile cache {cache} ({warm} entries at "
          f"start)", flush=True)
    tuner = agent()
    print(f"[a] agent LSTM {tuner.cfg.lstm_hidden} MLP "
          f"{tuner.cfg.mlp_hidden}, runtime budget "
          f"{tuner.cfg.env_cfg().runtime_budget:.6g} ns per step; "
          f"2^{LOG2_KEYS} keys per request (cut: the float32 key limit), "
          f"{N_OPS} operations", flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(tuner, devs)
    else:
        topology = ServingTopology.host(SLOTS, devices=devs[:1])
        print(f"[a] topology {topology}", flush=True)
        reqs = make_requests(SEED)
        phase_kernels(next(r for r in reqs if r.mix == "read_heavy"), tuner)
        phase_frozen(tuner, topology, reqs)
        phase_o2(tuner, topology)
    print(f"[a] all phases passed in {time.perf_counter() - t0:.3f} s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
