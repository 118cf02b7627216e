"""Device milliseconds per useful tuning step spent answering the probe's
overflow: `kernels/index_probe/ops.probe_ranks`' chunks of reads (and,
for CARMI, inserts) beyond their key tile's query capacity.  Clustered
keys pile queries into few tiles, so this is where their probe differs
from a spread-out mix's.

The self time (an operation's time less that of the operations nested
in it) of the operations under the scopes `pool_tick` and
`probe_overflow`, inside the window, over the steps the window
completed.  Only the serving K-step programs carry `pool_tick`, so
admission's resets stay out.  Idle lanes compute too.  None on a trace
whose operations carry no such scope, as the program's before the
overflow was scoped."""
import program_trace

UNIT = "ms/step"
LAYER = "probe kernel"
MOVES = "tune_steps_per_s"
SOURCE = "device_trace"
SCOPES = ("pool_tick", "probe_overflow")


def read(ctx):
    s = program_trace.of(ctx).scoped_s(ctx.trace, *SCOPES)
    steps = ctx.counters["steps"]
    if s is None or steps <= 0:
        return None
    return 1e3 * s / steps
