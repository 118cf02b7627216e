#!/usr/bin/env python3
"""Chip benchmark of the LITune tuning service: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the cell's
chips.  The run, in order: turns on JAX's persistent compilation cache
at the checkout's fixed path (`repro.runtime.compile_cache`), fails
unless JAX's devices are TPUs (as many as the cell asks for), builds the
agent and a ring of requests from the seed, warms up every program the
window uses, then drives the service in a closed loop for `--seconds`
and closes the window at the next tick boundary.  After the window it
reads the device's peak memory, compares a sample of the served answers
with the plain reference (`check.py`), and prints, as the last line of
standard output, one JSON object: `correct`, `attempted`, `failed`,
`metrics` (the cell's end-to-end metrics, or with `--trace 1` its
per-layer metrics, read from a profiler trace of the window), `device`,
and last `checks`, each compared number beside its limit; the same
numbers end standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import json          # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

RUNS_DIR = HERE / ".runs"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(SystemExit):
    pass


def require_chips(count: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform!r}"
                     f" ({devs[0].device_kind}); device numbers need the "
                     f"chip")
    if len(devs) < count:
        raise NoChip(f"the cell needs {count} TPU chips, JAX sees "
                     f"{len(devs)}")
    return devs


class CompileCounter:
    """Programs compiled or loaded from the persistent cache while
    `armed`: none should be, inside the window."""

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **kw):
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1

    def _ev(self, event, **kw):
        if self.armed and event == CACHE_HIT_EVENT:
            self.count += 1


def _active(svc) -> int:
    return sum(p["active"] for p in svc.stats()["per_pool"].values())


def _o2(svc) -> dict | None:
    st = svc.stats().get("o2")
    if st is None:
        return None
    it = next(iter(svc.agents))
    return {"phase_ms": dict(st["phase_ms"]),
            "assessments": st["assessments"],
            "updates": st[it]["offline_updates"]}


class Context:
    """What a per-layer metric reader sees: the reduced trace and the
    host's spans, the run's counters, the cell's shapes and the chip's
    peaks."""

    def __init__(self, reduced, spans, counters: dict, shapes: dict,
                 peaks):
        self.trace = reduced
        self.spans = spans
        self.counters = counters
        self.shapes = shapes
        self.peaks = peaks


def run(cell, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, devices=None, log=print) -> dict:
    """One run of `cell` (a `spec.Cell`); returns the result object."""
    import jax

    import cell as C
    import check as K
    import peaks as P
    import trace_reduce as TR

    if require_tpu:
        devices = require_chips(cell.chips)[:cell.chips]
    elif devices is None:
        devices = jax.devices()[:cell.chips]
    counter = CompileCounter()
    cfg, tr = cell.config, cell.traffic
    agent = C.make_agent(cfg)
    svc = C.make_service(agent, cfg, tr, seed, devices)
    ring = C.make_requests(cfg, tr, seed)
    loop = C.ClosedLoop(svc, ring, cfg["budget_steps"])
    C.warm_up(loop, tr["warmup"])
    log(f"[setup] {len(ring)} requests x {cfg['n_keys']} keys, "
        f"{tr['reads']} reads + {tr['inserts']} inserts; warm-up waves "
        f"{tr['warmup']}; {svc.program_misses} step-program binds")

    trace_dir = RUNS_DIR / "trace"
    o2_before = {}
    active0 = [0]

    def on_start():
        nonlocal o2_before
        o2_before = _o2(svc)
        active0[0] = _active(svc)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
        # a compile inside the window is a fault of the warm-up: name it
        jax.config.update("jax_log_compiles", True)
        counter.armed = True

    setup_s = None

    def started():
        nonlocal setup_s
        on_start()
        setup_s = time.perf_counter() - T_START

    win = C.run_window(loop, tr["outstanding"], seconds, on_start=started)
    counter.armed = False
    jax.config.update("jax_log_compiles", False)
    if trace:
        jax.profiler.stop_trace()
    o2_after = _o2(svc)
    admitted = len(win.completed) + _active(svc) - active0[0]
    mem = max(d.memory_stats().get("peak_bytes_in_use", 0)
              for d in devices) if devices[0].platform != "cpu" else 0
    log(f"[window] {win.seconds:.6f} s, {win.ticks} ticks, {win.steps} "
        f"steps, {len(win.completed)} requests completed, {admitted} "
        f"admitted, {counter.count} compiles")

    # the reference check, after the window and the memory reading
    results = {rid: svc.results[rid] for rid in win.completed}
    served = [r for r, v in results.items() if v.get("runtimes")]
    failed = len(results) - len(served)         # dropped or empty
    picked = K.sample(served, seed, tr["check_requests"])
    with jax.profiler.TraceAnnotation(C.SPAN_CHECK):
        values = K.numbers({r: results[r] for r in picked},
                           {r: ring[loop.done[r]] for r in picked},
                           cfg["index_type"])
    if o2_after is not None:
        values["o2_updates"] = o2_after["updates"] - o2_before["updates"]
        values["o2_assessed"] = (o2_after["assessments"]
                                 - o2_before["assessments"])
        values["o2_nonfinite"] = sum(
            not C.tree_finite(t) for ten in svc.tenants.values()
            for t in (ten.online, ten.offline) if t is not None)
    correct, checks = K.judge(values, cell.limits)
    correct &= bool(win.completed) and failed == 0
    log(f"[check] {len(picked)} requests, {values['answers']} answers "
        f"against the float64 reference")

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(mem)}
    metrics = {}
    breakdown = None
    if not trace:
        for m in cell.end_to_end:
            if m.name == "setup_s":
                metrics[m.name] = {"value": setup_s, "unit": m.unit}
            elif m.name == "tune_steps_per_s":
                metrics[m.name] = {"value": win.steps / win.seconds,
                                   "unit": m.unit}
    else:
        events = TR.load(TR.find_xplane(trace_dir))
        reduced, spans = TR.reduce(events)
        counters = {"steps": win.steps, "ticks": win.ticks,
                    "admitted": admitted, "compiles": counter.count,
                    "window_s": win.seconds}
        if o2_after is not None:
            counters["assessed"] = values["o2_assessed"]
            counters["o2_host_ms"] = sum(
                o2_after["phase_ms"][k] - o2_before["phase_ms"][k]
                for k in o2_after["phase_ms"])
        shapes = {"n_keys": cfg["n_keys"], "reads": tr["reads"],
                  "inserts": tr["inserts"], "slots": cfg["slots"]}
        ctx = Context(reduced, spans, counters, shapes,
                      P.peaks(dev.device_kind)
                      if dev.platform != "cpu" else None)
        for m in cell.per_layer:
            v = m.read(ctx)
            if v is not None:
                metrics[m.name] = {"value": v, "unit": m.unit}
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        breakdown = {"device_ops": reduced.top_ops(10),
                     "idle_gaps": reduced.idle_gaps(spans, 10)}
    out = {"correct": bool(correct), "attempted": len(win.completed),
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from repro.runtime import compile_cache
    cache = compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import spec
    cell = spec.load_cell(args.workload)
    print(f"[start] {args.workload} seed {args.seed} seconds "
          f"{args.seconds} trace {args.trace}; compile cache {cache}",
          flush=True)
    out = run(cell, args.seed, args.seconds, bool(args.trace),
              log=lambda s: print(s, flush=True))
    for name, c in out["checks"].items():
        print(f"{name} {c['value']} {c['op']} {c['limit']}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    try:
        main()
    except NoChip as e:
        print(e, file=sys.stderr)
        sys.exit(2)
