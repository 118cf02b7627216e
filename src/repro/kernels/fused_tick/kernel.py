"""Pallas kernel: fused K-ladder tick capture append.

The K-step serving tick (`core/etmdp.batched_episode_scan`) ends in a
memory-bound tail: re-key the scan's stacked outputs into the
transition view, pack six wide fields into one feature axis, and append
`[K, wide]` rows into each slot's `[H, wide]` capture block at a
per-slot dynamic offset.  Dispatched separately (the historical
`_capture_write` program) that tail materializes the whole `[K, B,
wide]` intermediate across a program boundary every tick; fused into
the step program (`launch/serving/programs._step_program(capture=True)`)
this kernel consumes the scan's outputs in place.

Grid: (B,) — one program instance per slot lane, mirroring the
`index_probe` one-block-per-step idiom.  Blocks: each wide field arrives
whole (`[K, B, d_f]` — a lane slice `[K, 1, d_f]` is not a block Mosaic
accepts, its second-minor dim being neither 8-aligned nor full), the
capture block as the lane's `[1, H, wide]` rows, and the `[B]` offsets
in SMEM.  Rows are appended one at a time: Mosaic stores a single row at
a dynamic sublane offset, but not a `[K, wide]` slab whose offset it
cannot prove 8-aligned.  The offset is clamped to `[0, H - K]` exactly
as `lax.dynamic_update_slice` clamps it, and the capture buffer aliases
the output.  The body is pure data movement, so the kernel is bitwise
against the jnp reference (`ref.fused_capture_ref`) in every mode — the
serving path's capture parity does not depend on which backend ran it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_tick.ref import FIELD_ORDER


def _capture_kernel(obs_ref, nobs_ref, ha_ref, ca_ref, hq_ref, cq_ref,
                    off_ref, cap_ref, out_ref):
    fields = (obs_ref, nobs_ref, ha_ref, ca_ref, hq_ref, cq_ref)
    K = obs_ref.shape[0]
    H = cap_ref.shape[1]
    b = pl.program_id(0)
    out_ref[...] = cap_ref[...]
    off = jnp.clip(off_ref[b], 0, H - K)

    def append_row(k, carry):
        row = jnp.concatenate(
            [f[pl.ds(k, 1), pl.ds(b, 1), :][0] for f in fields],
            axis=-1)                                        # [1, wide]
        out_ref[0, pl.ds(off + k, 1), :] = row
        return carry

    jax.lax.fori_loop(0, K, append_row, 0)


def fused_capture_pallas(cap, new, offsets, interpret: bool = True):
    """cap [B, H, wide]; new: dict of [K, B, d_f] wide fields (the tick's
    transition view); offsets [B] int32 -> updated cap."""
    B, H, wide = cap.shape
    K = new[FIELD_ORDER[0]].shape[0]
    field_specs = [
        pl.BlockSpec(new[f].shape, lambda i: (0, 0, 0))
        for f in FIELD_ORDER]
    return pl.pallas_call(
        _capture_kernel,
        grid=(B,),
        in_specs=field_specs + [
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, H, wide), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, wide), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, wide), cap.dtype),
        input_output_aliases={len(FIELD_ORDER) + 1: 0},
        interpret=interpret,
        name="fused_capture",
    )(*(new[f] for f in FIELD_ORDER), offsets.astype(jnp.int32), cap)
