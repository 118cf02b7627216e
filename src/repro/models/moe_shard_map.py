"""Expert-parallel MoE with explicit all_to_all (shard_map, fully manual).

Why: under pure GSPMD the capacity-dispatch gathers/scatters between the
token space (batch-sharded) and the expert space (model-sharded) lower to
batch-replicated all-reduces of [B, S*k, D] f32 -- measured 1.15e3 s
collective term on qwen3-235B train_4k (EXPERIMENTS.md §Perf cell A).
Hand-placing the communication makes it two all_to_alls of exactly the
routed slots per direction; shard_map transposes all_to_all to all_to_all,
so the backward is equally lean.

Layout inside the manual region (per device):
  x_loc [b_loc, S, D]; expert weights [e_loc, D, F] (e_loc = E / tp).
  1. local top-k routing over the full router table;
  2. slots sorted by target shard -> send buffer [tp, cap_send, D];
  3. all_to_all over `model` -> recv [tp, cap_send, D] (+ int32 metadata);
  4. second-level local grouping by local expert -> [e_loc, cap_loc, D];
  5. local expert matmuls; inverse gather; all_to_all back; weighted
     combine into [b_loc, S, D].
Capacity factors apply at both levels (token drops mirror the GSPMD path).
"""
from __future__ import annotations

import math
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import ArchConfig
from repro.runtime import mesh_utils


def _capacity(n: int, mult: float) -> int:
    cap = math.ceil(n * mult)
    return max(8, -(-cap // 8) * 8)


def _local_moe(x, router, wg, wu, wd, cfg: ArchConfig, tp: int,
               model_axis: str, data_axes: tuple = ("data",)):
    """Runs on ONE device inside shard_map. x [t_loc, D] (flattened)."""
    t_loc, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    e_loc = e // tp

    logits = (x.astype(jnp.float32) @ router)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)                 # [t_loc, k]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(eidx, e).sum(1), axis=0) / k
    aux = cfg.router_aux_coef * e * jnp.sum(me * ce)
    aux = jax.lax.pmean(aux, (model_axis,) + tuple(data_axes))

    # ---- level 1: group slots by target expert shard ----
    n = t_loc * k
    flat_e = eidx.reshape(n)
    shard_of = flat_e // e_loc
    order = jnp.argsort(shard_of)
    se = shard_of[order]
    cap_s = _capacity(n // tp, cfg.capacity_factor)
    starts = jnp.searchsorted(se, jnp.arange(tp))
    pos = jnp.arange(n) - starts[se]
    keep = pos < cap_s
    safe_pos = jnp.where(keep, pos, cap_s - 1)

    tok = order // k
    send_x = jnp.zeros((tp, cap_s, d), x.dtype)
    send_x = send_x.at[se, safe_pos].add(
        jnp.where(keep[:, None], x[tok], 0))
    send_eid = jnp.full((tp, cap_s), -1, jnp.int32)
    send_eid = send_eid.at[se, safe_pos].max(
        jnp.where(keep, flat_e[order] % e_loc, -1).astype(jnp.int32))

    # ---- all_to_all: slots travel to their expert shard ----
    recv_x = jax.lax.all_to_all(send_x, model_axis, split_axis=0,
                                concat_axis=0, tiled=False)
    recv_eid = jax.lax.all_to_all(send_eid, model_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
    # recv_* [tp, cap_s, ...]: slot (src_shard, c) from each source shard

    # ---- level 2: group received slots by local expert ----
    m = tp * cap_s
    r_eid = recv_eid.reshape(m)                      # -1 = empty slot
    r_x = recv_x.reshape(m, d)
    order2 = jnp.argsort(jnp.where(r_eid < 0, e_loc, r_eid))
    ge = jnp.where(r_eid < 0, e_loc, r_eid)[order2]
    cap_l = _capacity(m // max(e_loc, 1), cfg.capacity_factor)
    starts2 = jnp.searchsorted(ge, jnp.arange(e_loc))
    pos2 = jnp.arange(m) - starts2[jnp.minimum(ge, e_loc - 1)]
    keep2 = (pos2 < cap_l) & (ge < e_loc)
    safe_pos2 = jnp.where(keep2, pos2, cap_l - 1)

    buf = jnp.zeros((e_loc, cap_l, d), x.dtype)
    buf = buf.at[jnp.minimum(ge, e_loc - 1), safe_pos2].add(
        jnp.where(keep2[:, None], r_x[order2], 0))

    # ---- local expert matmuls ----
    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(buf.dtype) * u
    y = jnp.einsum("ecf,efd->ecd", h, wd)            # [e_loc, cap_l, D]

    # ---- inverse: back to recv slots, all_to_all home, combine ----
    y_slots = jnp.zeros((m, d), y.dtype)
    vals = jnp.where(keep2[:, None],
                     y[jnp.minimum(ge, e_loc - 1), safe_pos2], 0)
    y_slots = y_slots.at[order2].add(vals)
    y_back = jax.lax.all_to_all(y_slots.reshape(tp, cap_s, d), model_axis,
                                split_axis=0, concat_axis=0, tiled=False)
    # y_back [tp, cap_s, d] in the original send layout
    slot_y = jnp.where(keep[:, None], y_back[se, safe_pos], 0)
    gates_sorted = gate.reshape(n)[order]
    out = jnp.zeros((t_loc, d), y.dtype)
    out = out.at[tok].add(slot_y * gates_sorted[:, None].astype(slot_y.dtype))
    return out, aux


def apply_moe_shard_map(p: dict, x_normed: jax.Array, cfg: ArchConfig,
                        mesh) -> tuple[jax.Array, jax.Array]:
    """Drop-in replacement for apply_moe when a mesh with a `model` axis is
    ambient.  x_normed [B, S, D] batch-sharded over the data axes."""
    from repro.models.layers import apply_norm
    b, s, d = x_normed.shape
    x = apply_norm(p["norm"], x_normed, cfg)
    tp = mesh_utils.axis_size(mesh, mesh_utils.MODEL_AXIS)
    data_axes = tuple(a for a in mesh_utils.DATA_AXES if a in mesh.shape)

    def body(router, wg, wu, wd, x_loc):
        b_loc = x_loc.shape[0]
        flat = x_loc.reshape(b_loc * x_loc.shape[1], d)
        # x is replicated over the model axis: each model-axis peer routes a
        # DISTINCT 1/tp slice of the tokens (otherwise all tp peers duplicate
        # the routing work and a2a traffic -- measured 16x compute).  Decode
        # steps (t_loc < tp) keep the replicated path: the duplicate routing
        # of a handful of tokens is cheaper than padding to tp slices.
        t_loc = flat.shape[0]
        if t_loc % tp == 0 and t_loc >= tp:
            me = jax.lax.axis_index(mesh_utils.MODEL_AXIS)
            t_me = t_loc // tp
            flat_me = jax.lax.dynamic_slice_in_dim(flat, me * t_me, t_me,
                                                   axis=0)
            out_me, aux = _local_moe(flat_me, router, wg, wu, wd, cfg, tp,
                                     mesh_utils.MODEL_AXIS, data_axes)
            out = jax.lax.all_gather(out_me, mesh_utils.MODEL_AXIS, axis=0,
                                     tiled=True)
        else:
            out, aux = _local_moe(flat, router, wg, wu, wd, cfg, tp,
                                  mesh_utils.MODEL_AXIS, data_axes)
            out = jax.lax.pmean(out, mesh_utils.MODEL_AXIS)  # identical copies
        return out.reshape(x_loc.shape), aux

    batch_spec = P(data_axes if len(data_axes) > 1 else data_axes[0])
    expert_spec = P(mesh_utils.MODEL_AXIS)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), expert_spec, expert_spec, expert_spec, batch_spec),
        out_specs=(batch_spec, P()),
        axis_names={mesh_utils.MODEL_AXIS, *data_axes}, check_vma=False)
    return fn(p["router"], p["wg"], p["wu"], p["wd"], x)
