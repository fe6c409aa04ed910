"""Logical-axis sharding rules.

Every parameter / activation dimension in the framework is annotated with a
*logical* axis name ("vocab", "embed", "heads", ...).  A `LogicalRules` object
maps each logical name to an ordered list of *candidate* mesh-axis tuples; the
first candidate whose total size divides the dimension (and whose mesh axes are
all present in the mesh and not already used by another dimension of the same
tensor) is chosen.  Non-divisible dims fall back to replication — this is what
lets a fixed (data=16, model=16) production mesh host e.g. a 40-head model
(heads replicate, mlp/vocab still shard) without bespoke per-arch plumbing.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class LogicalRules:
    """Mapping: logical axis name -> ordered candidates (tuples of mesh axes)."""

    rules: Mapping[str, Sequence[tuple[str, ...]]]

    def candidates(self, name: str) -> Sequence[tuple[str, ...]]:
        return self.rules.get(name, ())


# The production rule set.  "pod" is used jointly with "data" for the batch
# when present (multi-pod data parallelism); "embed" is the FSDP axis.
_COMMON = {
    "batch": [("pod", "data"), ("data",)],
    "seq": [],                        # activations: sequence stays unsharded
    "seq_sp": [("model",)],           # Megatron-style sequence parallelism
    "kv_seq": [("model",)],           # long-context KV caches: shard sequence
    "vocab": [("model",)],
    "embed": [("data",)],             # FSDP: param d_model dim over data axis
    "embed_act": [],                  # activation d_model dim: replicated
    "heads": [("model",)],
    "kv_heads": [("model",)],
    "head_dim": [],
    "mlp": [("model",)],
    "experts": [("model",)],
    "expert_mlp": [],
    "rnn": [("model",)],              # RG-LRU / SSD inner width
    "state": [],                      # SSM state dim
    "conv": [],
    "layers": [],
    "stack": [],
    "norm": [],
    "classes": [],
    "groups": [("data",)],            # MoE dispatch groups
    "capacity": [],
    "window": [],
    "patch": [],
}

DEFAULT_RULES = LogicalRules(_COMMON)
MULTIPOD_RULES = DEFAULT_RULES  # same rules; "pod" candidates activate if present


def logical_to_spec(
    logical_axes: Sequence[str | None],
    shape: Sequence[int],
    mesh: Mesh,
    rules: LogicalRules = DEFAULT_RULES,
) -> P:
    """Resolve logical axis names for one tensor into a PartitionSpec."""
    if len(logical_axes) != len(shape):
        raise ValueError(
            f"logical axes {logical_axes} do not match shape {shape}"
        )
    mesh_sizes = dict(mesh.shape)  # works for Mesh and AbstractMesh
    used: set[str] = set()
    out: list = []
    for name, dim in zip(logical_axes, shape):
        if name is None:
            out.append(None)
            continue
        chosen = None
        for cand in rules.candidates(name):
            if not all(a in mesh_sizes for a in cand):
                continue
            if any(a in used for a in cand):
                continue
            total = int(np.prod([mesh_sizes[a] for a in cand]))
            if dim % total != 0:
                continue
            chosen = cand
            break
        if chosen is None:
            out.append(None)
        else:
            used.update(chosen)
            out.append(chosen if len(chosen) > 1 else chosen[0])
    return P(*out)


def named_sharding(
    logical_axes: Sequence[str | None],
    shape: Sequence[int],
    mesh: Mesh,
    rules: LogicalRules = DEFAULT_RULES,
) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical_axes, shape, mesh, rules))


def tree_shardings(specs_tree, shapes_tree, mesh, rules: LogicalRules = DEFAULT_RULES):
    """Map a tree of logical-axes tuples + a matching tree of shaped leaves
    (arrays or ShapeDtypeStructs) to a tree of NamedShardings."""
    return jax.tree.map(
        lambda axes, leaf: named_sharding(axes, leaf.shape, mesh, rules),
        specs_tree,
        shapes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x
        ),
    )


def constrain(x, logical_axes: Sequence[str | None], rules: LogicalRules = DEFAULT_RULES):
    """with_sharding_constraint by logical names (the constraint is a bare
    PartitionSpec).  A no-op outside a ``jax.set_mesh`` context, and inside
    a ``shard_map`` that made every axis Manual: per-shard code has nothing
    left to constrain."""
    mesh = get_abstract_mesh_or_none()
    if mesh is None or AxisType.Auto not in mesh.axis_types:
        return x
    spec = logical_to_spec(logical_axes, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, spec)


def get_abstract_mesh_or_none():
    """The mesh set by ``jax.set_mesh``, or None outside one."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh
