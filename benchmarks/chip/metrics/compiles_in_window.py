"""Programs compiled, or loaded from the persistent compilation cache,
inside the measured window (JAX's monitoring events
`/jax/core/compile/backend_compile_duration` and
`/jax/compilation_cache/cache_hits`).  Warm-up should leave none: the
program cache of `launch/serving/programs.py` holds every shape the
window uses."""
UNIT = "count"
LAYER = "program cache"
MOVES = "tune_steps_per_s"
SOURCE = "program_counter"


def read(ctx):
    return float(ctx.counters["compiles"])
