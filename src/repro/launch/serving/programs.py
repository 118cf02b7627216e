"""Process-wide compiled-program cache for the serving stack.

Every jitted program the serving layers dispatch lives here, keyed on
(`topology.DeviceSlice`, frozen configs, shapes) so every
`TuningService` instance — and every pool within one — shares the same
jitted callables and their compiled executables.  A per-service dict on
top of this would recompile per instance, which is exactly the
recompile-on-mixed-streams failure this engine exists to avoid.

Slices hash by their device ids (display names excluded), so two
topologies whose slices cover the same devices — a flat host layout and
a carved pod mesh, say — alternate between the *same* resident
executables (tests/test_topology.py asserts zero re-traces across
equal-shape topologies).

The same cache is what makes **pool resizing** cheap: a pool growing
from B to B' slots re-enters the *same* `_step_program` callable with a
wider carry — jax traces the new shape once, and shrinking back to a
previously-served width re-uses its resident executable, so a
grow→shrink cycle after warmup binds zero new programs
(tests/test_serving_layers.py asserts this).

Buffer donation (the slot carry, capture buffers, learner state — the
largest live trees, all rebound every tick) is gated off the CPU
backend via `repro.core.replay.donate_argnums`: the CPU PJRT donation
hand-off synchronizes with pending readers (~6-70 ms per dispatch,
measured on jax 0.4.37) for no memory win.  The helper probes the
backend lazily at program-build time, so importing this module never
initializes jax before the operator's XLA_FLAGS are set.
tests/test_o2_service.py asserts the donating programs stay
re-trace-free either way.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import networks as nets
from repro.core.etmdp import batched_episode_scan, transition_view
from repro.core.parallel import mapped_reset
from repro.core.replay import donate_argnums
from repro.kernels.fused_tick.ops import fused_capture_core
from repro.kernels.fused_tick.ref import fused_capture_ref
from repro.launch.serving.topology import DeviceSlice, _slice_mesh


def _pow2_ladder(n: int) -> list[int]:
    out, k = [], 1
    while k <= n:
        out.append(k)
        k *= 2
    return out


def _admit_key_chain(window_key):
    """O2System.tune_window's PRNG discipline for one window key: the
    episode runs on the second split (k_on) and a diverged window's
    assessment on the second split of the remainder (k_off)."""
    remainder, k_on = jax.random.split(window_key)
    k_off = jax.random.split(remainder)[1]
    return k_on, k_off


# one dispatch derives a whole admission wave's episode + assessment keys
# (vmap over the integer threefry core is bitwise the per-key splits)
_batched_admit_keys = jax.jit(jax.vmap(_admit_key_chain))


def _mesh_for(device_ids: tuple):
    """Back-compat shim for raw-id callers (the tune_serve re-export):
    the topology layer's slice mesh is the one source of truth now."""
    return _slice_mesh(tuple(device_ids), "slots")


@lru_cache(maxsize=None)
def _step_program(slice_: DeviceSlice, net_cfg, env_cfg, et_cfg, k: int,
                  per_lane: bool = False, capture: bool = False):
    """K-step slot program: scan over K ticks of the bitwise-stable
    one-tick map body, lanes sharded over the slice.  The carry is
    donated — every caller rebinds it to the program's output, and the
    donation lets XLA write the new carry into the old one's buffers
    instead of allocating a fresh slot-state tree per tick.

    `per_lane=True` is the canary-pool variant: params carry a leading
    slot axis and shard with the lanes, so a pool may serve candidate
    params on a lane fraction while control lanes keep the incumbent —
    a *pure buffer update* relative to this resident program.  The lane
    math is the same mapped body either way (`batched_episode_scan_lanes`
    maps params instead of closing over them), so control lanes stay
    bitwise-equal to the shared-params program.  All variants live in
    this one lru cache: `programs_resident` counts them together, which
    is what lets tests assert a whole canary→promote/rollback cycle
    binds zero new programs after warmup.

    `capture=True` is the fused-tick variant: the program takes the
    pool's `[B, H, wide]` capture buffer and `[B]` pre-tick offsets as
    extra operands and appends the tick's transition view in place
    (`kernels/fused_tick`), so one dispatch covers scan + capture — no
    `[K, B, wide]` intermediate crosses a program boundary per tick.
    The append is pure data movement (bitwise the historical
    `_capture_write` program in every kernel mode), so serving results
    and ring contents are unchanged; the capture-tail kernel mode
    follows `env_cfg.kernel` like the read probes inside the scan."""
    mesh = slice_.mesh()
    ax = slice_.axis

    if per_lane:
        from repro.core.etmdp import batched_episode_scan_lanes

        def scan_core(p, c, n):
            return batched_episode_scan_lanes(p, c, n, k, net_cfg,
                                              env_cfg, et_cfg, False)
        p_spec = P(ax)
    else:
        def scan_core(p, c, n):
            return batched_episode_scan(p, c, n, k, net_cfg, env_cfg,
                                        et_cfg, False)
        p_spec = P()

    if capture:
        kmode = env_cfg.kernel.resolved()

        def core(p, c, n, cap, off):
            c2, out = scan_core(p, c, n)
            cap2 = fused_capture_core(cap, transition_view(out), off,
                                      kmode)
            return c2, out, cap2

        return jax.jit(jax.shard_map(
            core, mesh=mesh, in_specs=(p_spec, P(ax), P(ax), P(ax), P(ax)),
            out_specs=(P(ax), P(None, ax), P(ax)), check_vma=False),
            donate_argnums=donate_argnums(1, 3))

    return jax.jit(jax.shard_map(
        scan_core, mesh=mesh, in_specs=(p_spec, P(ax), P(ax)),
        out_specs=(P(ax), P(None, ax)), check_vma=False),
        donate_argnums=donate_argnums(1))


@lru_cache(maxsize=None)
def _reset_program(slice_: DeviceSlice, env_cfg):
    """Batched admission: reset a wave of episodes in one (sharded over
    the slice when the wave divides it) program."""
    mesh = slice_.mesh()
    ax = slice_.axis

    def core(d, r, i, wr):
        return mapped_reset(env_cfg, d, {"reads": r, "inserts": i}, wr)

    return jax.jit(jax.shard_map(
        core, mesh=mesh,
        in_specs=(P(ax), P(ax), P(ax), P(ax)),
        out_specs=P(ax), check_vma=False))


@lru_cache(maxsize=None)
def _admit_scatter_program(slice_: DeviceSlice, net_cfg, slots: int):
    """Scatter freshly-reset episodes into their slots (padded entries
    target slot index B and are dropped)."""
    sharded = slice_.sharded()

    def scatter(carry, idx, keys, env_states, obs):
        def upd(buf, x):
            return buf.at[idx].set(x, mode="drop")
        zero_h = nets.zero_hidden(net_cfg, (idx.shape[0],))
        return {
            "key": upd(carry["key"], keys),
            "env": jax.tree.map(upd, carry["env"], env_states),
            "obs": upd(carry["obs"], obs),
            "h_a": tuple(upd(c, z) for c, z in zip(carry["h_a"], zero_h)),
            "h_q": tuple(upd(c, z) for c, z in zip(carry["h_q"], zero_h)),
            "b_t": upd(carry["b_t"],
                       jnp.zeros((idx.shape[0],), jnp.float32)),
        }

    # the carry is rebound to the output on every admission — donate it
    return jax.jit(scatter, out_shardings=sharded,
                   donate_argnums=donate_argnums(0))


@lru_cache(maxsize=None)
def _build_carry_program(slice_: DeviceSlice, net_cfg, slots: int):
    """Initial-wave fast path: construct the whole B-slot carry from a
    full batch of resets (no scatter)."""
    sharded = slice_.sharded()

    def build(keys, env_states, obs):
        return {
            "key": keys,
            "env": env_states,
            "obs": obs,
            "h_a": nets.zero_hidden(net_cfg, (slots,)),
            "h_q": nets.zero_hidden(net_cfg, (slots,)),
            "b_t": jnp.zeros((slots,), jnp.float32),
        }

    return jax.jit(build, out_shardings=sharded)


def _extract_episode_core(cap, slot, src_idx):
    """One retired slot's capture rows, compacted to the episode's padded
    length: the small packed `[Tp, wide]` array the ring ingests (pure
    gather — indices are inputs)."""
    return cap[slot][src_idx]


@lru_cache(maxsize=None)
def _extract_episode_program(slice_: DeviceSlice):
    """Replicated-output extract: every device of the pool's slice holds
    the episode rows, so when the ring home lives inside the slice (the
    flat host layout, and `from_mesh` row 0) its single-device `_place`
    resolves to a local copy instead of a cross-device reshard the next
    gather would wait on.  Pools pinned to rows that exclude the ring
    home still pay one cross-device hop per retired episode — a per-row
    ring home is a ROADMAP follow-up."""
    return jax.jit(_extract_episode_core, out_shardings=slice_.replicated())


# Append one tick's transition view into the `[B, H, wide]` packed
# capture buffer at each slot's episode offset.  The body now lives in
# `kernels/fused_tick/ref.py` (the fused step program's bitwise oracle);
# this standalone program is the unfused fallback when a pool runs with
# `KernelConfig(fused_tick=False)`.  Pure data movement (offsets are
# array inputs): compiles once per (K, shape) pair and never re-traces
# on admissions or swaps.
_capture_write_core = fused_capture_ref


@lru_cache(maxsize=None)
def _capture_write_program():
    # built lazily (donate_argnums probes the backend) so importing this
    # module keeps the no-jax-init contract of the docstring above
    return jax.jit(_capture_write_core, donate_argnums=donate_argnums(0))


def _capture_write(cap, new, offsets):
    return _capture_write_program()(cap, new, offsets)


@lru_cache(maxsize=None)
def _mixed_params_program(slice_: DeviceSlice, slots: int):
    """Build the per-lane params tree of a canary pool: lane b serves
    `cand` where `mask[b]`, the incumbent `base` otherwise.  Pure data
    movement (the mask is an array input), stacked over the lane axis
    and sharded with it — selecting which lanes canary never re-traces,
    and the output feeds `_step_program(per_lane=True)` directly."""
    sharded = slice_.sharded()

    def mix(base, cand, mask):
        def leaf(b, c):
            m = mask.reshape((slots,) + (1,) * b.ndim)
            return jnp.where(
                m, jnp.broadcast_to(c, (slots,) + c.shape),
                jnp.broadcast_to(b, (slots,) + b.shape))
        return jax.tree.map(leaf, base, cand)

    return jax.jit(mix, out_shardings=sharded)


@lru_cache(maxsize=None)
def _fleet_stack_program(k: int):
    """Pack k same-structure tenant trees onto a new leading tenant axis
    — the fleet round's stack step as one resident program per stack
    width (the eager per-leaf `jnp.stack` would dispatch leaves × k
    copy ops per round).  Pure data movement: lane i of the output is
    bitwise tree i, so the stacked fine-tune's serial parity is carried
    entirely by the per-lane math (`core.o2._fleet_finetune_program`).
    Keyed on k alone; XLA traces lazily per tree structure (learner
    states and batch stacks each get one executable per width)."""
    def stack(*trees):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

    return jax.jit(stack)


@lru_cache(maxsize=None)
def _resize_program(slice_: DeviceSlice):
    """Slot-count resize: gather a pool's device state (the episode carry
    or the capture buffers) through a new→old slot index map, sharded
    over the pool's slice at the new width.  Growth pads fresh slots with
    slot 0's rows (valid, ignored state — the admission scatter
    overwrites them); shrink compacts the active slots to the front.
    Pure gather: indices are array inputs, so resizing never re-traces on
    the request stream — only the first visit to a new width traces its
    shape."""
    sharded = slice_.sharded()

    def gather(tree, idx):
        return jax.tree.map(lambda x: x[idx], tree)

    return jax.jit(gather, out_shardings=sharded)
