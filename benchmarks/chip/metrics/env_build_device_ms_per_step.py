"""Device milliseconds per useful tuning step spent in the index build:
decoding the action into parameters and fitting the index's models and
leaves over the keys (`alex.build`, `carmi.build`).

The self time (an operation's time less that of the operations nested
in it) of the operations under the scopes `pool_tick` and `env_build`
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
SCOPES = ("pool_tick", "env_build")


def read(ctx):
    s = program_trace.of(ctx).scoped_s(ctx.trace, *SCOPES)
    steps = ctx.counters["steps"]
    if s is None or steps <= 0:
        return None
    return 1e3 * s / steps
