"""Host milliseconds per pool tick: the time of the service's own
`litune.step` spans inside the window less the time in the spans that
wait on the device (`litune.tick.fetch`, the narrow-field fetch, and
`litune.admit.sync`, admission's `r_best` fetch), over the
`litune.tick` spans that start in the window.  What is left is host
work: staging an admission's keys, dispatching, collecting each slot's
steps, retiring.  None on a trace without the service's spans.

It holds for cells whose device waits are those two spans: a frozen
agent on one chip.  Under O2 the drain's fetch of each assessment's
runtimes (`litune.o2.drain`), and on a sub-slice admission width the
reset's move to the host (in `litune.admit.reset`), also wait on the
device and would count here as host time."""
import program_trace

UNIT = "ms/tick"
LAYER = "service host"
MOVES = "tune_steps_per_s"
SOURCE = "program_span"
WAITS = ("litune.tick.fetch", "litune.admit.sync")


def read(ctx):
    pt = program_trace.of(ctx)
    window = ctx.trace.window
    ticks = pt.span_count(window, "litune.tick")
    if ticks == 0:
        return None
    host = pt.span_s(window, "litune.step") - pt.span_s(window, *WAITS)
    return 1e3 * host / ticks
