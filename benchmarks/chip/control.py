#!/usr/bin/env python3
"""Readings that the limits of `check.py` are set from, on the chip.

    python3 benchmarks/chip/control.py --config alex-mix-1m \
        --traffic readheavy-batch --seeds 1,2,3 --seconds 20 \
        [--fault half_batch]

For each seed, in one process (the programs compile once): the cell's
agent, service and requests from that seed, the warm-up (first seed
only), a short window at the cell's own load, and then the compared
numbers twice over the same sampled answers: once for what the program
served, once for the control, the float64 reference's own arithmetic in
bfloat16 put in the program's place.  With `--fault <name>` one fault
of `faults.py` is planted in the program before anything is traced, and
the numbers are those of the faulted program (the control is not
computed).  One JSON line per seed goes to standard output and to
`--out`.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default="")
    ap.add_argument("--fault", default="")
    args = ap.parse_args()

    from repro.runtime import compile_cache
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import cell as C
    import check as K
    import run as RUN
    import spec
    from faults import FAULTS

    devices = RUN.require_chips(1)[:1]
    if args.fault:
        from repro.index import env as E
        name, broken = FAULTS[args.fault](E)
        setattr(E, name, broken)
    config = spec.load_json(HERE / "configs" / f"{args.config}.json")
    traffic = spec.load_json(HERE / "traffic" / f"{args.traffic}.json")
    out = open(args.out, "a") if args.out else None
    warm = False
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        agent = C.make_agent(config)
        svc = C.make_service(agent, config, traffic, seed, devices)
        ring = C.make_requests(config, traffic, seed)
        loop = C.ClosedLoop(svc, ring, config["budget_steps"])
        if not warm:
            C.warm_up(loop, traffic["warmup"])
            warm = True
        win = C.run_window(loop, traffic["outstanding"], args.seconds)
        picked = K.sample(win.completed, seed, traffic["check_requests"])
        res = {r: svc.results[r] for r in picked}
        reqs = {r: ring[loop.done[r]] for r in picked}
        it = config["index_type"]
        g = K.gaps(res, reqs, it)
        prog = K.summarize(g, res, it)
        ctrl = None if args.fault else K.numbers(
            res, reqs, it, dtype="bfloat16", served=False)
        row = {"config": args.config, "traffic": args.traffic,
               "fault": args.fault or None,
               "seed": seed, "device": devices[0].device_kind,
               "window_s": win.seconds, "steps": win.steps,
               "completed": len(win.completed), "program": prog,
               "control": ctrl, "program_gaps": g,
               "seconds": time.perf_counter() - t0}
        line = json.dumps(row)
        print(line, flush=True)
        if out is not None:
            out.write(line + "\n")
            out.flush()
        if svc.o2rt is not None:
            svc.flush_o2()
        del svc, loop, agent
    if out is not None:
        out.close()


if __name__ == "__main__":
    main()
