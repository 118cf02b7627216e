"""Device milliseconds of the pool tick per useful tuning step: the
device time of the serving K-step programs (`programs._step_program`,
dispatched by `service.step`; with O2 on, the fused variant that also
appends the capture rows) inside the window, over the steps the window
completed.  Idle lanes compute too, so a tick's time covers every lane
of the pool.

The serving programs carry no name of their own yet: the step, the
fused step and the reset all trace as `jit_core` or `jit_scan_core`.
The tick is told apart as the program that `service.step` waits for:
in each `bench.step` span, the last of these modules to end.
"""
FAMILY = r"^jit_(scan_)?core\("
UNIT = "ms/step"
LAYER = "pool tick"
MOVES = "tune_steps_per_s"
SOURCE = "device_trace"


def tick_modules(ctx) -> set:
    return ctx.trace.closing_modules(ctx.spans, "bench.step", FAMILY)


def read(ctx):
    steps = ctx.counters["steps"]
    names = tick_modules(ctx)
    if steps <= 0 or not names:
        return None
    return 1e3 * ctx.trace.device_s_of(names) / steps
