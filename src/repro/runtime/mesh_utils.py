"""Logical-axis sharding rules -> concrete NamedShardings.

Every parameter/activation in the model stack is annotated with a tuple of
*logical* axis names ("vocab", "embed", "q_heads", ...).  A rule table maps
logical axes to mesh axes; `logical_to_spec` applies the table with
divisibility fallbacks so a single model definition lowers on any mesh
(1-device CPU smoke tests, 16x16 single pod, 2x16x16 multi-pod).
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Mesh axis groups: "data-like" axes absorb the batch; "model" is tensor
# parallel.  Multi-pod meshes prepend a "pod" axis that joins the data group.
DATA_AXES = ("pod", "data")
MODEL_AXIS = "model"


# Default logical-axis -> mesh-axis rules (single source of truth).
# None means replicate.  Tuples mean "shard over the product of these axes".
DEFAULT_RULES: dict[str, object] = {
    "batch": DATA_AXES,          # global batch over pod x data
    "seq": None,                 # baseline: sequence replicated in train
    "act_embed": None,
    "vocab": MODEL_AXIS,
    "embed": None,
    "q_heads": MODEL_AXIS,
    "kv_heads": MODEL_AXIS,      # falls back to replicated if not divisible
    "head_dim": None,
    "mlp": MODEL_AXIS,
    "expert": MODEL_AXIS,
    "expert_mlp": None,
    "expert_cap": None,
    "layers": None,              # stacked-layer leading dim, never sharded
    "cache_batch": DATA_AXES,
    "cache_seq": None,           # adaptive: "model" when kv_heads can't shard
    "d_inner": MODEL_AXIS,       # mamba inner channels
    "conv": None,
    "state": None,
    "dt_rank": None,
    "enc_seq": None,
    "generic": None,
}


def axis_size(mesh: Mesh, axis) -> int:
    """Product of mesh axis sizes for a (possibly tuple / missing) axis."""
    if axis is None:
        return 1
    if isinstance(axis, str):
        return mesh.shape[axis] if axis in mesh.shape else 1
    size = 1
    for a in axis:
        if a in mesh.shape:
            size *= mesh.shape[a]
    return size


def _present(mesh: Mesh, axis):
    """Filter a rule target down to the axes present in this mesh."""
    if axis is None:
        return None
    if isinstance(axis, str):
        return axis if axis in mesh.shape else None
    kept = tuple(a for a in axis if a in mesh.shape)
    if not kept:
        return None
    return kept if len(kept) > 1 else kept[0]


def logical_to_spec(
    logical_axes: Sequence[str | None],
    shape: Sequence[int],
    mesh: Mesh,
    rules: Mapping[str, object] | None = None,
) -> P:
    """Map logical axes to a PartitionSpec, dropping non-divisible shardings.

    A dropped sharding is safe (replication), just less parallel; the dry-run
    report surfaces them so they become roofline findings, not crashes.
    """
    rules = dict(DEFAULT_RULES, **(rules or {}))
    assert len(logical_axes) == len(shape), (logical_axes, shape)
    used: set[str] = set()
    out = []
    for name, dim in zip(logical_axes, shape):
        target = _present(mesh, rules.get(name)) if name else None
        if target is None:
            out.append(None)
            continue
        t_axes = (target,) if isinstance(target, str) else tuple(target)
        if any(a in used for a in t_axes):
            out.append(None)  # a mesh axis can appear only once per spec
            continue
        if dim % axis_size(mesh, target) != 0:
            out.append(None)  # divisibility fallback -> replicate
            continue
        used.update(t_axes)
        out.append(target)
    return P(*out)


def logical_to_sharding(logical_axes, shape, mesh, rules=None) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical_axes, shape, mesh, rules))


def tree_shardings(abstract_params, logical_tree, mesh, rules=None):
    """Shardings for a pytree of ShapeDtypeStructs given a parallel tree of
    logical-axis tuples."""
    return jax.tree.map(
        lambda sds, axes: logical_to_sharding(axes, sds.shape, mesh, rules),
        abstract_params,
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x),
    )


def constrain(x, logical_axes, mesh=None, rules=None):
    """with_sharding_constraint by logical axes (no-op outside a mesh)."""
    mesh = mesh or _current_mesh()
    if mesh is None or mesh.empty:
        return x
    spec = logical_to_spec(logical_axes, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _current_mesh():
    """The mesh of the enclosing `with mesh:` block, None outside one
    (`jax.sharding.get_mesh` sees only `jax.set_mesh`, not the context
    manager the launchers use)."""
    from jax._src import mesh as mesh_lib
    mesh = mesh_lib.thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """A mesh + rule overrides, carried through lowering."""
    mesh: Mesh
    rules: dict = dataclasses.field(default_factory=dict)

    def spec(self, logical_axes, shape) -> P:
        return logical_to_spec(logical_axes, shape, self.mesh, self.rules)

    def sharding(self, logical_axes, shape) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical_axes, shape))

    @property
    def dp(self) -> int:
        return axis_size(self.mesh, DATA_AXES)

    @property
    def tp(self) -> int:
        return axis_size(self.mesh, MODEL_AXIS)
