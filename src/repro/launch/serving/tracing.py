"""Host spans of the serving stack, on the profiler's clock.

`span(name, **ids)` is a `jax.profiler.TraceAnnotation` on the thread
that runs it, named `litune.*`.  Keyword ids become stats of the span's
trace event (`litune.tick` with `k`, `slots`, `active`); they hold
scalars.  With `acc` given, the span's host milliseconds are also added
to `acc[key]`: that is how the O2 runtime's `phase_ms` accumulates.

The device side is marked with `jax.named_scope` where the code is
traced: the tick and reset programs' names as their outermost scope
(`pool_tick`, `env_reset` in `programs.py`) and the environment's phases
(`agent`, `env_build`, `env_reads`, `probe`, `env_inserts`,
`env_observe`).  The name goes into each operation's `op_name` metadata,
which a TPU trace carries as the operation's `tf_op`; the compiled
program is otherwise the same (`tests/test_tracing.py`).
"""
from __future__ import annotations

import time
from contextlib import contextmanager

import jax


@contextmanager
def span(name: str, acc: dict | None = None, key: str | None = None,
         **ids):
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name, **ids):
        yield
    if acc is not None:
        acc[key] += 1e3 * (time.perf_counter() - t0)
