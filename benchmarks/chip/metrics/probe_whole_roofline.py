"""The whole probe's share of its roofline, in percent: the probe
kernel's work (`probe_roofline.min_bytes`, 4n + 8m bytes from the cell's
shapes) at the chip's HBM bandwidth, over the mean device time of one
whole probe.

A whole probe is everything under the scope `probe`
(`kernels/index_probe/ops.predecessor_positions`): the routing of reads
to tiles, the grouping, the Pallas kernel and the overflow chunks.  Its
time is the self time of the operations under `pool_tick` and `probe`
inside the window, over the probe-kernel calls under `pool_tick` there
(one per environment step).  `probe_roofline` times the kernel alone.
None on a trace whose operations carry no such scope."""
import importlib.util
from pathlib import Path

import program_trace

UNIT = "%"
LAYER = "probe kernel"
MOVES = "tune_steps_per_s"
SOURCE = "device_trace"


def _kernel():
    path = Path(__file__).with_name("probe_roofline.py")
    spec = importlib.util.spec_from_file_location("_probe_reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(ctx):
    if ctx.peaks is None:
        return None
    kernel = _kernel()
    pt = program_trace.of(ctx)
    s = pt.scoped_s(ctx.trace, "pool_tick", "probe")
    calls = pt.scoped_count(ctx.trace, kernel.PATTERN, "pool_tick")
    if s is None or calls == 0:
        return None
    return kernel.share(ctx.shapes["n_keys"], ctx.shapes["reads"],
                        ctx.peaks.hbm_bw, s / calls)
