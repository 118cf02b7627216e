"""Process-wide Pallas execution-mode dispatch: the one resolution seam.

Every in-tree kernel op (`index_probe.batched_lookup`,
`flash_attention.mha`, `mamba_scan.scan`, `fused_tick.fused_capture`)
takes a single static ``mode`` argument instead of per-callsite
``use_pallas``/``interpret`` flag stacks, and ``mode=None`` defers to
this module's per-process resolution:

  * ``compiled``  — lower the Pallas kernel for real (GPU/TPU only);
  * ``interpret`` — run the Pallas kernel body through the interpreter
                    (any backend; the CPU correctness path for the
                    kernel *logic*, far too slow to serve from);
  * ``ref``       — the pure-jnp reference implementation (bitwise
                    oracle; what CPU serving actually runs);
  * ``auto``      — ``compiled`` when the default jax backend is an
                    accelerator, ``ref`` otherwise.

Resolution order for ``auto``/``None``: the ``REPRO_KERNEL_MODE``
environment variable (when set to a concrete mode) wins, then the
backend rule above.  The result is cached for the life of the process —
kernel mode is a deployment property, not a per-call one — so every
jitted program in the process agrees on it and the serving program
cache never splits on kernel flags.  `KernelConfig` is the frozen,
hashable carrier that threads an explicit override through
`EnvConfig`/`ServeConfig` (it participates in jit static args and the
serving program-cache keys, so two services with different kernel
postures never share an executable by accident).

Importing this module never initializes jax's backend: the backend
probe happens lazily inside `resolve()`, at program-build time, after
the operator's XLA_FLAGS are set (same contract as
`launch/serving/programs.py`).
"""
from __future__ import annotations

import dataclasses
import os
from functools import lru_cache

MODES = ("auto", "compiled", "interpret", "ref")
_ACCELERATOR_BACKENDS = ("gpu", "tpu", "cuda", "rocm")
_ENV_VAR = "REPRO_KERNEL_MODE"


def _validate(mode: str) -> str:
    if mode not in MODES:
        raise ValueError(f"kernel mode {mode!r} not in {MODES}")
    return mode


@lru_cache(maxsize=None)
def _auto_mode() -> str:
    """The process's resolved default mode (cached: kernel mode is a
    deployment property — one answer per process keeps every jitted
    program and cache key coherent)."""
    env = os.environ.get(_ENV_VAR, "").strip().lower()
    if env and env != "auto":
        return _validate(env)
    import jax  # lazy: never initialize the backend at import time
    backend = jax.default_backend()
    return "compiled" if backend in _ACCELERATOR_BACKENDS else "ref"


def resolve(mode: str | None = None) -> str:
    """Resolve a requested mode to a concrete one (never ``auto``)."""
    if mode is None or mode == "auto":
        return _auto_mode()
    return _validate(mode)


def interpret_flag(mode: str) -> bool:
    """The `pl.pallas_call(interpret=...)` flag for a resolved Pallas
    mode (callers branch to the jnp ref before consulting this)."""
    assert mode in ("compiled", "interpret"), mode
    return mode == "interpret"


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Frozen kernel posture threaded through `EnvConfig`/`ServeConfig`.

    ``mode`` picks the Pallas execution mode for every routed kernel
    (``auto`` defers to `resolve()`); ``probe_reads`` gates routing the
    learned-index read probes (`index/alex.py` / `carmi.py`
    ``run_reads``) through `index_probe.batched_lookup` when the
    resolved mode is a Pallas one; ``fused_tick`` gates fusing the
    K-ladder tick's transition-capture tail into the serving step
    program (`launch/serving/programs._step_program(capture=True)`);
    ``probe_tile`` overrides the probe kernel's key-tile size (0 =
    `index_probe.ops.auto_tile`: 512, or the smallest power of two
    covering a smaller n; the last tile is padded with +inf keys).
    """

    mode: str = "auto"
    probe_reads: bool = True
    fused_tick: bool = True
    probe_tile: int = 0

    def __post_init__(self):
        _validate(self.mode)
        if self.probe_tile < 0 or (
                self.probe_tile and self.probe_tile & (self.probe_tile - 1)):
            raise ValueError(f"probe_tile={self.probe_tile} must be 0 "
                             f"(auto) or a power of two")

    def resolved(self) -> str:
        """This config's concrete mode for the current process."""
        return resolve(self.mode)
