"""Device milliseconds per useful tuning step spent routing the probe's
reads to key tiles: `kernels/index_probe/ops._route`, the count of tile
boundaries <= each read (or, above `ROUTE_COUNT_MAX_TILES` tiles, the
binary search), one call per whole probe.

The self time (an operation's time less that of the operations nested
in it) of the operations under the scopes `pool_tick` and `probe_route`,
inside the window, over the steps the window completed.  Only the
serving K-step programs carry `pool_tick`, so admission's resets stay
out.  Idle lanes compute too.  None on a trace whose operations carry no
such scope, as the program's before the routing was scoped."""
import program_trace

UNIT = "ms/step"
LAYER = "probe kernel"
MOVES = "tune_steps_per_s"
SOURCE = "device_trace"
SCOPES = ("pool_tick", "probe_route")


def read(ctx):
    s = program_trace.of(ctx).scoped_s(ctx.trace, *SCOPES)
    steps = ctx.counters["steps"]
    if s is None or steps <= 0:
        return None
    return 1e3 * s / steps
