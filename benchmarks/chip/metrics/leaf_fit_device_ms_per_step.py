"""Device milliseconds per useful tuning step spent fitting the index's
linear models by least squares: `index/linear_model.fit_segments_exact`,
the exact leaf fits and the root fit of `alex.build` and `carmi.build`.

The self time (an operation's time less that of the operations nested
in it) of the operations under the scopes `pool_tick` and `leaf_fit`,
inside the window, over the steps the window completed; part of
`env_build_device_ms_per_step`.  Only the serving K-step programs carry
`pool_tick`, so admission's resets stay out.  Idle lanes compute too.
None on a trace whose operations carry no such scope, as the program's
before the fit was scoped."""
import program_trace

UNIT = "ms/step"
LAYER = "environment"
MOVES = "tune_steps_per_s"
SOURCE = "device_trace"
SCOPES = ("pool_tick", "leaf_fit")


def read(ctx):
    s = program_trace.of(ctx).scoped_s(ctx.trace, *SCOPES)
    steps = ctx.counters["steps"]
    if s is None or steps <= 0:
        return None
    return 1e3 * s / steps
