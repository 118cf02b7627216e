"""Benchmark entrypoint: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run                 # paper scale
    REPRO_BENCH_SCALE=smoke ... -m benchmarks.run           # CI scale
    ... -m benchmarks.run --only fig5_efficiency,table3_costs

Prints CSV (``benchmark,<cols...>``) to stdout.  The roofline table itself
comes from the separate 512-device process:
    PYTHONPATH=src python -m repro.launch.dryrun --out roofline.jsonl
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.runtime import compile_cache


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default="",
                    help="comma-separated benchmark names")
    ap.add_argument("--skip", default="", help="comma-separated names")
    args = ap.parse_args()

    from benchmarks import paper_figs, roofline
    from benchmarks.common import SCALE

    benches = dict(paper_figs.ALL)
    benches["micro_steps"] = roofline.micro_steps
    benches["kernel_micro"] = roofline.kernel_micro
    benches["kernel_roofline"] = roofline.kernel_roofline

    only = [s for s in args.only.split(",") if s]
    skip = set(s for s in args.skip.split(",") if s)
    names = only or [n for n in benches if n not in skip]

    print(f"# repro benchmarks  scale={SCALE}", flush=True)
    failed = []
    for name in names:
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        try:
            for row in benches[name]():
                print(row, flush=True)
            print(f"# {name} done in {time.time()-t0:.0f}s", flush=True)
        except Exception as e:  # keep going; report at the end
            failed.append(name)
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    if failed:
        print(f"# FAILED benchmarks: {failed}", flush=True)
        sys.exit(1)
    print("# all benchmarks complete", flush=True)


if __name__ == "__main__":
    main()
