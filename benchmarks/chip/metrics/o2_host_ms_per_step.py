"""Host milliseconds of the O2 annex per useful tuning step: the change
over the window of `stats()["o2"]["phase_ms"]` (capture, fine-tune and
assess, the service's own host clock around each phase) over the steps
the window completed."""
UNIT = "ms/step"
LAYER = "O2 annex"
MOVES = "tune_steps_per_s"
SOURCE = "program_span"


def read(ctx):
    ms = ctx.counters.get("o2_host_ms")
    steps = ctx.counters["steps"]
    if ms is None or steps <= 0:
        return None
    return ms / steps
