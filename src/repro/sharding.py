"""Logical-axis sharding rules (MaxText-style) with divisibility guards.

Every parameter/activation dimension carries a *logical* axis name; rules map
logical names to mesh axes. A dimension is sharded only when its size divides
the mesh-axis extent and the mesh axis is not already consumed by an earlier
dimension of the same tensor — otherwise it silently falls back to replication
(required for e.g. paligemma's kv_heads=1 on a 16-way model axis).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisVal = Union[None, str, Tuple[str, ...]]


# logical axis -> mesh axis (or tuple of mesh axes)
DEFAULT_RULES: Dict[str, AxisVal] = {
    "batch": ("pod", "data"),   # data parallel (pod axis extends DP across pods)
    "fsdp": "data",             # ZeRO/FSDP parameter+optimizer storage sharding
    "embed": "data",            # alias of fsdp for embedding-dim storage
    "vocab": "model",           # column-parallel embedding / logits
    "heads": "model",           # tensor-parallel attention heads
    "kv_heads": "model",
    "mlp": "model",             # tensor-parallel FFN width
    "experts": "model",         # expert parallelism
    "kv_seq": "model",          # context parallelism of decode KV caches
    "d_inner": "model",         # SSM inner width tensor parallelism
    "conv_dim": "model",
    # unsharded logical axes
    "layers": None,
    "seq": None,
    "head_dim": None,
    "ssm_state": None,
    "ssm_heads": None,
    "chunk": None,
    "width": None,
    "stack": None,
}


@dataclasses.dataclass(frozen=True)
class ShardingCtx:
    """Mesh context handed to models/runtime. mesh=None -> single-device paths."""

    mesh: Optional[Mesh] = None
    rules: Optional[Dict[str, AxisVal]] = None
    use_shard_map: bool = True  # manual paths (decode attention, MoE EP)

    @property
    def axis_sizes(self) -> Dict[str, int]:
        if self.mesh is None:
            return {}
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    def resolved_rules(self) -> Dict[str, AxisVal]:
        rules = dict(DEFAULT_RULES)
        if self.rules:
            rules.update(self.rules)
        # Drop mesh axes that do not exist on this mesh (e.g. "pod" single-pod).
        names = set(self.mesh.axis_names) if self.mesh is not None else set()

        def _filter(v: AxisVal) -> AxisVal:
            if v is None:
                return None
            if isinstance(v, str):
                return v if v in names else None
            kept = tuple(a for a in v if a in names)
            return kept if kept else None

        return {k: _filter(v) for k, v in rules.items()}

    def axis_size(self, mesh_axes: AxisVal) -> int:
        if mesh_axes is None or self.mesh is None:
            return 1
        sizes = self.axis_sizes
        if isinstance(mesh_axes, str):
            return sizes[mesh_axes]
        return math.prod(sizes[a] for a in mesh_axes)

    # ------------------------------------------------------------- spec build
    def spec(self, axes: Sequence[Optional[str]], shape: Optional[Sequence[int]] = None) -> P:
        """PartitionSpec for logical `axes` (guarded by `shape` divisibility)."""
        if self.mesh is None:
            return P()
        rules = self.resolved_rules()
        used: set = set()
        entries = []
        for i, name in enumerate(axes):
            mesh_axes = rules.get(name) if name else None
            if mesh_axes is None:
                entries.append(None)
                continue
            tup = (mesh_axes,) if isinstance(mesh_axes, str) else tuple(mesh_axes)
            tup = tuple(a for a in tup if a not in used)
            if not tup:
                entries.append(None)
                continue
            extent = math.prod(self.axis_sizes[a] for a in tup)
            if shape is not None and shape[i] % extent != 0:
                entries.append(None)  # replicate: not evenly divisible
                continue
            used.update(tup)
            entries.append(tup[0] if len(tup) == 1 else tup)
        while entries and entries[-1] is None:
            entries.pop()
        return P(*entries)

    def named(self, axes: Sequence[Optional[str]], shape: Optional[Sequence[int]] = None) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(axes, shape))

    def constrain(self, x, axes: Sequence[Optional[str]]):
        """with_sharding_constraint guarded for mesh-less runs."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.spec(axes, x.shape))
        )


NULL_CTX = ShardingCtx(mesh=None)


def tree_specs(ctx: ShardingCtx, spec_tree, shape_tree) -> "jax.tree_util.PyTreeDef":
    """Map a pytree of logical-axes tuples + matching shapes to PartitionSpecs."""
    return jax.tree.map(
        lambda axes, shp: ctx.spec(axes, shp),
        spec_tree,
        shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
    )


def tree_shardings(ctx: ShardingCtx, spec_tree, shape_tree):
    if ctx.mesh is None:
        return None
    return jax.tree.map(
        lambda s: NamedSharding(ctx.mesh, s),
        tree_specs(ctx, spec_tree, shape_tree),
        is_leaf=lambda x: isinstance(x, P),
    )
