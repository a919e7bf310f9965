"""Sharding utilities: conditional constraints + pytree sharding builders.

Mesh axes are always ('pod', 'data', 'model') (multi-pod) or
('data', 'model') (single pod); specs written against the multi-pod
names degrade gracefully — axes absent from the active mesh are dropped
so the same model code runs on 1 CPU device, a single pod, or the full
production mesh.
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def _active_axes():
    """Axis names of the mesh set by ``jax.set_mesh``, or None."""
    m = jax.sharding.get_abstract_mesh()
    if m.empty:
        return None
    return set(m.axis_names)


def _filter_spec(spec: P, axes) -> P:
    """Drop mesh axes that don't exist in the active mesh."""
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in axes)
            out.append(kept if kept else None)
        else:
            out.append(entry if entry in axes else None)
    return P(*out)


def shard_hint(x, spec: P):
    """with_sharding_constraint that is a no-op without an active mesh."""
    axes = _active_axes()
    if axes is None:
        return x
    return jax.lax.with_sharding_constraint(x, _filter_spec(spec, axes))


def filter_specs(tree, mesh):
    """Adapt a PartitionSpec pytree to a concrete mesh's axis names."""
    axes = set(mesh.axis_names)
    return jax.tree.map(
        lambda s: _filter_spec(s, axes),
        tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def make_shardings(mesh, axes_tree):
    """PartitionSpec pytree -> NamedSharding pytree for a mesh."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        filter_specs(axes_tree, mesh),
        is_leaf=lambda s: isinstance(s, P),
    )


def batch_spec() -> P:
    return P(("pod", "data"), None)
