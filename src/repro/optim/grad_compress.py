"""int8 error-feedback gradient compression for the cross-pod all-reduce.

At 1000+ node scale the *cross-pod* data-parallel all-reduce rides the
slowest links, so that is where compression pays: gradients are computed
pod-locally (shard_map over the `pod` axis, `data`/`model` left to GSPMD
via auto axes), quantized to int8 with per-leaf max-abs scaling and an
error-feedback residual (Karimireddy et al., 2019 -- unbiased over time),
then summed with an explicit int16 psum (lossless for <=258 pods since
max |sum| = 127*n_pods): 2x wire bytes vs f32 master-grad reduction on the
collective roofline term, with int8 storage at rest.

Used by launch/train.py --grad-compress; tested in
tests/test_grad_compress.py (including the EF-accumulator property:
compressed-SGD trajectories track uncompressed ones).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def quantize(x: jax.Array):
    """f32/bf16 -> (int8, scale). Symmetric per-tensor max-abs scaling."""
    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compress_residual(grad: jax.Array, error: jax.Array):
    """Error-feedback step: compensate, quantize, return residual."""
    comp = grad.astype(jnp.float32) + error
    q, scale = quantize(comp)
    new_error = comp - dequantize(q, scale)
    return q, scale, new_error


def init_error_state(grads):
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def compressed_psum_leaf(g_shard, e_shard, axis_name: str, n: int):
    """INSIDE shard_map: int8 EF compression + psum over `axis_name`.

    A shared (pmax) scale makes the int8 sum lossless across shards."""
    q, scale, new_e = compress_residual(g_shard, e_shard)
    smax = jax.lax.pmax(scale, axis_name)
    qg = jnp.clip(jnp.round(dequantize(q, scale) / jnp.maximum(smax, 1e-12)),
                  -127, 127).astype(jnp.int8)
    # int16 accumulate: |sum| <= 127 * n_pods < 2^15 for n <= 258
    acc = jax.lax.psum(qg.astype(jnp.int16), axis_name)
    mean = acc.astype(jnp.float32) * smax / n
    return mean, new_e


def make_pod_grad_fn(loss_fn, mesh, params_tree, batch_tree,
                     axis_name: str = "pod"):
    """Returns grad_fn(params, err_state, batch) -> (loss, grads, err').

    Gradients are computed pod-locally under a partial-manual shard_map
    (`axis_names={pod}`; `data`/`model` stay under GSPMD) and combined with
    the compressed int8 all-reduce.  Falls back to plain value_and_grad on
    meshes without a `pod` axis.
    """
    if axis_name not in mesh.shape:
        def plain(params, err_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, **batch)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            return loss, grads, err_state
        return plain

    n_pods = mesh.shape[axis_name]
    # params / error state are replicated across pods -> P(); batch leaves
    # are sharded on dim 0 over the pod axis.
    p_specs = jax.tree.map(lambda _: P(), params_tree)
    e_specs = jax.tree.map(lambda _: P(), params_tree)
    b_specs = jax.tree.map(
        lambda leaf: P(*((axis_name,) + (None,) * (leaf.ndim - 1))),
        batch_tree)

    def body(params, err_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, **batch)
        flat_g, treedef = jax.tree.flatten(grads)
        flat_e = jax.tree.leaves(err_state)
        out = [compressed_psum_leaf(g, e, axis_name, n_pods)
               for g, e in zip(flat_g, flat_e)]
        grads = jax.tree.unflatten(treedef, [o[0] for o in out])
        new_err = jax.tree.unflatten(treedef, [o[1] for o in out])
        loss = jax.lax.pmean(loss, axis_name)
        return loss, grads, new_err

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(p_specs, e_specs, b_specs),
                         out_specs=(P(), p_specs, e_specs),
                         axis_names={axis_name}, check_vma=False)
