"""Device milliseconds of one environment reset: the device time of the
batched reset programs (`programs._reset_program`, run by
`service._admit` for each admission wave and, under O2, by each pooled
assessment: one evaluation of the default parameters per lane) inside
the window, over the requests admitted plus the windows assessed in it.

The reset traces as `jit_core`, like the fused serving step; the step is
the module `service.step` waits for (see `tick_device_ms_per_step`), and
every other `jit_core` module is a reset.
"""
import importlib.util
from pathlib import Path

PATTERN = r"^jit_core\("
LINE = "XLA Modules"
UNIT = "ms/request"
LAYER = "admission"
MOVES = "tune_steps_per_s"
SOURCE = "device_trace"


def _tick():
    path = Path(__file__).with_name("tick_device_ms_per_step.py")
    spec = importlib.util.spec_from_file_location("_tick_reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(ctx):
    n = ctx.counters["admitted"] + ctx.counters.get("assessed", 0)
    ticks = _tick().tick_modules(ctx)
    names = {e.name for e in ctx.trace.matching(PATTERN, LINE)} - ticks
    if n <= 0 or not names:
        return None
    return 1e3 * ctx.trace.device_s_of(names) / n
