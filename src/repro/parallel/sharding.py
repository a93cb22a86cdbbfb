"""Logical-axis sharding rules (MaxText-style) with divisibility fallback.

Every parameter and activation in the model stack is annotated with *logical*
axis names; this module resolves them against the active mesh:

    batch   -> ("pod", "data")     activations' leading dim (pure DP outer
                                   axis crosses pods once per step)
    embed   -> "data"              FSDP weight sharding (ZeRO-3): parameters
                                   and optimizer state shard over the data
                                   axis and are all-gathered per layer
    heads   -> "model"             tensor parallelism over attention heads
    kv_heads-> "model"             (falls back to replicated when the arch
                                   has fewer kv heads than model shards)
    mlp     -> "model"             TP over the FFN hidden dim
    experts -> "model"             expert parallelism
    vocab   -> "model"             sharded logits/embedding gather
    seq     -> None                (sequence parallelism is opt-in via rules)

Resolution checks divisibility: a dim that does not divide the assigned mesh
axes is replicated instead of crashing — e.g. kv_heads=4 on a 16-way model
axis (minicpm3's 40 heads on 16 shards, etc.).  That single rule is what
lets all 10 architectures x 4 shapes compile on the same mesh unchanged.
"""

from __future__ import annotations

import contextlib
import math
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "DEFAULT_RULES",
    "LogicalRules",
    "resolve_axes",
    "sharding_for",
    "constrain",
    "tree_shardings",
    "data_parallel_mesh",
    "batch_sharding",
]

# logical name -> mesh axis (or tuple of axes, or None)
DEFAULT_RULES: dict[str, object] = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": "data",
    "embed_nofsdp": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "experts": "model",
    "expert_mlp": "data",   # FSDP over the expert FF dim (kimi: 2 TB of
                            # expert weights need 256-way, not 16-way, sharding)
    "vocab": "model",
    "state": None,
    "conv": None,
    "layers": None,
    "act_embed": None,
    "act_heads": "model",
    "act_mlp": "model",
    "act_seq": None,
    # flash-decode-style cache layout: shard the SEQ axis of KV caches over
    # the model axis (softmax max/sum partials combine via tiny collectives)
    # — kv_heads rarely divide a 16-wide TP axis, so head-sharding leaves
    # caches replicated (measured 256 GiB/dev on qwen3-32b decode_32k;
    # seq-sharding: 19 GiB/dev).  The dedup rule in resolve_axes drops the
    # later cache_heads claim on "model" automatically.
    "cache_seq": "model",
    "cache_heads": "model",
}


class _RulesState(threading.local):
    def __init__(self):
        self.rules = dict(DEFAULT_RULES)


_STATE = _RulesState()


@contextlib.contextmanager
def LogicalRules(overrides: dict[str, object]):
    """Temporarily override logical->mesh rules (used by the perf sweeps)."""
    old = dict(_STATE.rules)
    _STATE.rules.update(overrides)
    try:
        yield
    finally:
        _STATE.rules = old


def _mesh_axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        return math.prod(_mesh_axis_size(mesh, a) for a in axis)
    return mesh.shape[axis] if axis in mesh.shape else 1


def _present(mesh: Mesh, axis):
    """Filter an axis assignment down to axes that exist in this mesh."""
    if axis is None:
        return None
    if isinstance(axis, (tuple, list)):
        kept = tuple(a for a in axis if a in mesh.shape)
        return kept if kept else None
    return axis if axis in mesh.shape else None


def resolve_axes(logical_axes, shape, mesh: Mesh, rules=None) -> P:
    """logical axis names (one per dim, None = replicated) -> PartitionSpec.

    Dims that don't divide their assigned mesh axes fall back to replicated;
    a mesh axis claimed by an earlier dim is dropped from later dims (e.g.
    mLSTM's (mlp, heads) both map to "model" — the first wins).
    """
    rules = rules if rules is not None else _STATE.rules
    spec = []
    used: set = set()
    for dim, name in zip(shape, logical_axes):
        axis = _present(mesh, rules.get(name)) if name is not None else None
        if axis is not None:
            members = axis if isinstance(axis, tuple) else (axis,)
            members = tuple(a for a in members if a not in used)
            axis = members if len(members) > 1 else (members[0] if members else None)
        if axis is not None and dim % _mesh_axis_size(mesh, axis) != 0:
            axis = None
        if axis is not None:
            used.update(axis if isinstance(axis, tuple) else (axis,))
        spec.append(axis)
    return P(*spec)


def sharding_for(logical_axes, shape, mesh: Mesh, rules=None) -> NamedSharding:
    return NamedSharding(mesh, resolve_axes(logical_axes, shape, mesh, rules))


def constrain(x, logical_axes, mesh: Mesh | None = None, rules=None):
    """with_sharding_constraint via logical names; no-op without a mesh and
    no-op inside shard_map (manual axes are already placed)."""
    if _inside_manual_context():
        return x
    mesh = mesh if mesh is not None else _current_mesh()
    if mesh is None or mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(
        x, sharding_for(logical_axes, x.shape, mesh, rules))


def _inside_manual_context() -> bool:
    """True inside a shard_map body, whose ambient abstract mesh carries
    Manual axis types."""
    am = jax.sharding.get_abstract_mesh()
    return (not am.empty and any(
        t == jax.sharding.AxisType.Manual for t in am.axis_types))


def _current_mesh() -> Mesh | None:
    """The mesh set by ``jax.set_mesh(mesh)``, also inside ``jax.jit``
    (where the public ``jax.sharding.get_mesh`` refuses to answer)."""
    from jax._src import mesh as mesh_lib
    mesh = mesh_lib.get_concrete_mesh()
    return None if mesh is None or mesh.empty else mesh


def data_parallel_mesh(n_devices: int | None = None,
                       axis_name: str = "data") -> Mesh:
    """1-axis pure data-parallel mesh over the first ``n_devices`` devices
    (default: all) — what the RL training engine shards its batch axis
    over.  Kept as a function (never a module constant) so importing this
    module cannot touch jax device state."""
    import numpy as np
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if n > len(devs):
        raise ValueError(f"asked for {n} devices, have {len(devs)} "
                         "(set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N for host testing)")
    return Mesh(np.asarray(devs[:n]), (axis_name,))


def batch_sharding(mesh: Mesh, axis_name: str = "data") -> NamedSharding:
    """Sharding that splits a leading batch dim over ``axis_name`` — used to
    place host-packed batches before a sharded train step."""
    return NamedSharding(mesh, P(axis_name))


def tree_shardings(spec_tree, shape_tree, mesh: Mesh, rules=None):
    """Map a pytree of logical-axes tuples + matching shapes -> shardings."""
    return jax.tree.map(
        lambda axes, shp: sharding_for(axes, shp.shape, mesh, rules),
        spec_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x),
    )
