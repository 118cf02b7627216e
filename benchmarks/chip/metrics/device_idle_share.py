"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window, in percent,
averaged over the chips the cell uses.  The host's part of a tick
(admission's uploads, the narrow-field fetch, collect, the O2 annex's
host work) shows here."""
UNIT = "%"
LAYER = "device"
MOVES = "tune_steps_per_s"
SOURCE = "device_trace"


def read(ctx):
    t = ctx.trace
    if t.window_s <= 0 or not t.devices:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
