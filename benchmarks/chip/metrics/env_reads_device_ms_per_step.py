"""Device milliseconds per useful tuning step spent in the reads: the probe
(`predecessor_positions`: routing of reads to tiles, the Pallas kernel,
the overflow chunks) and the reads' cost model (`run_reads`).

The self time (an operation's time less that of the operations nested
in it) of the operations under the scopes `pool_tick` and `env_reads`
(`index/env.py`), inside the window, over the steps the window
completed.  Only the serving K-step programs carry `pool_tick`, so
admission's resets, which run the same phase, stay in
`reset_device_ms_per_request`.  Idle lanes compute too.  None on a trace
whose operations carry no such scope."""
import program_trace

UNIT = "ms/step"
LAYER = "environment"
MOVES = "tune_steps_per_s"
SOURCE = "device_trace"
SCOPES = ("pool_tick", "env_reads")


def read(ctx):
    s = program_trace.of(ctx).scoped_s(ctx.trace, *SCOPES)
    steps = ctx.counters["steps"]
    if s is None or steps <= 0:
        return None
    return 1e3 * s / steps
