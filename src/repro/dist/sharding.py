"""Logical-axis sharding rules -> PartitionSpecs (DESIGN §4).

Every tensor in the codebase is annotated with *logical* axis names
("batch", "heads", "fsdp", ...), never with mesh axes. One rule table per
execution mode (TRAIN_RULES / SERVE_RULES) maps each logical name to an
ordered preference of physical mesh axes, and `ShardingRules.resolve`
turns a logical tuple into a `PartitionSpec` for a concrete mesh:

* divisibility sanitizer — when the tensor shape is known, a mesh axis is
  only taken if the cumulative device count still divides the dimension
  (24 heads over model=16 -> replicated; 32 -> sharded). Every resolved
  spec is therefore valid as a jit in_sharding by construction.
* multi-axis rules with subset fallback — `batch: ("pod", "data")` shards
  over both axes when the dimension allows, degrading left-to-right
  (batch=2 on a pod=2 mesh -> ("pod",) only).
* no axis reuse — dims resolve left to right; an axis consumed by an
  earlier dim is skipped (`("fsdp", "batch")` on (data=4, model=2) ->
  P("data", None): batch cannot re-take "data").
* adaptive yield — later dims pick up axes earlier dims could not use:
  attention q is ("batch", "heads", "ctx", None), so the query sequence
  ("ctx") takes "model" (context parallelism) exactly when the head count
  does not divide it.
* size-1 mesh axes never appear in a spec, so the 1-device host mesh
  resolves everything to a no-op.

The mesh argument only needs `.axis_names` and `.devices.shape` — rule
resolution never touches device state, so tests resolve against abstract
stand-in meshes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional

from jax.sharding import NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Immutable logical-axis -> mesh-axis-preference table."""

    rules: dict   # {logical_name: tuple[mesh_axis, ...]}

    def resolve(self, logical_axes, mesh, shape: Optional[tuple] = None) -> P:
        """PartitionSpec for a tensor whose dims carry `logical_axes` names.

        logical_axes: tuple of logical names (None = never sharded).
        mesh: anything with .axis_names and .devices.shape.
        shape: optional concrete dims — enables the divisibility sanitizer.
        """
        logical_axes = tuple(logical_axes)
        if shape is not None and len(shape) != len(logical_axes):
            raise ValueError(
                f"shape {shape} has {len(shape)} dims but logical axes "
                f"{logical_axes} name {len(logical_axes)}")
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        used: set = set()
        entries = []
        for i, name in enumerate(logical_axes):
            if name is None:
                entries.append(None)
                continue
            if name not in self.rules:
                raise ValueError(
                    f"unknown logical axis {name!r}; known: "
                    f"{sorted(self.rules)}")
            taken = []
            degree = 1
            for ax in self.rules[name]:
                if ax not in sizes or ax in used or sizes[ax] == 1:
                    continue
                if shape is not None and shape[i] % (degree * sizes[ax]):
                    continue
                taken.append(ax)
                degree *= sizes[ax]
            used.update(taken)
            if not taken:
                entries.append(None)
            elif len(taken) == 1:
                entries.append(taken[0])
            else:
                entries.append(tuple(taken))
        return P(*entries)


# Mesh axes (repro/launch/mesh.py): pod -> data -> model, outermost first.
# `pod` is pure data parallelism across the slow inter-pod link; `data` is
# intra-pod data/FSDP parallelism; `model` is tensor parallelism.
TRAIN_RULES = ShardingRules(rules={
    # activations
    "batch": ("pod", "data"),
    "seq": (),                    # whole sequence resident per shard
    "ctx": ("model",),            # query seq: context parallelism, yields
                                  # to "heads" via no-reuse
    "heads": ("model",),
    "kv_heads": ("model",),
    "ffn": ("model",),
    "vocab": ("model",),
    "cache_seq": (),              # caches only shard while serving
    # parameters
    "fsdp": ("data",),            # pod keeps a full replica (grads cross
                                  # pods int8-compressed, not params)
    "tp": ("model",),
    "experts": ("model",),
    "expert_ffn": ("model",),     # only when "experts" could not take it
    "classes": (),                # WNN discriminators: the continuous
                                  # training ensemble is tiny — replicate
    "tenants": (),                # training is single-tenant; the stacked
                                  # serve fleet is where the axis shards
})

# Serving: decode works one token at a time, so the KV ring buffer is the
# long dimension — cache_seq takes `model` and kv_heads stay whole (the
# decode gather is local; attention reduces over the sharded seq).
# ULEEN Bloom tables shard over `model` by class ("classes"): per-class
# discriminators are fully independent until the final argmax (DESIGN §7),
# so the (M, N_f, E) tables partition on M with zero cross-device traffic
# until the (B, M) score gather.
# Multi-tenant fleets ("tenants", DESIGN §11) shard the stacked-artifact
# leading axis over `model` the same way: whole tenants are fully
# independent, so the only cross-device step is the single psum of the
# ownership-masked per-row scores. No-reuse means a cell sharding tenants
# leaves classes replicated (each tenant is KB-scale — that is the point).
SERVE_RULES = ShardingRules(rules={
    **TRAIN_RULES.rules,
    "kv_heads": (),
    "cache_seq": ("model",),
    "classes": ("model",),
    "tenants": ("model",),
})


def spec_degree(mesh, entry) -> int:
    """Shard count one PartitionSpec entry implies on `mesh` (None -> 1)."""
    if entry is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = entry if isinstance(entry, tuple) else (entry,)
    degree = 1
    for ax in axes:
        degree *= sizes[ax]
    return degree


def class_partition(mesh, num_classes: int,
                    rules: Optional[ShardingRules] = None):
    """Resolve the `classes` logical axis for an M-discriminator ensemble.

    Returns `(entry, degree)`: the PartitionSpec entry the class dimension
    takes on `mesh` and the resulting shard count. Falls back to
    replication — `(None, 1)` — whenever M does not divide the mesh axis
    (the divisibility sanitizer), so callers never have to special-case
    awkward class counts: the resolved spec is always a valid in_sharding.
    """
    rules = rules if rules is not None else SERVE_RULES
    entry = rules.resolve(("classes",), mesh, shape=(num_classes,))[0]
    return entry, spec_degree(mesh, entry)


def tenant_partition(mesh, num_tenants: int,
                     rules: Optional[ShardingRules] = None):
    """Resolve the `tenants` logical axis for a T-artifact stacked fleet.

    The multi-tenant twin of `class_partition`: returns `(entry, degree)`
    — the PartitionSpec entry the tenant dimension takes on `mesh` and the
    resulting shard count, falling back to replication `(None, 1)` when T
    does not divide the mesh axis (divisibility sanitizer), so the
    resolved spec is always a valid in_sharding.
    """
    rules = rules if rules is not None else SERVE_RULES
    entry = rules.resolve(("tenants",), mesh, shape=(num_tenants,))[0]
    return entry, spec_degree(mesh, entry)


def strip_axis(rules: ShardingRules, axis: str) -> ShardingRules:
    """Rules with one mesh axis removed from every preference tuple (used
    inside shard_map manual regions, where the manual axis must not appear
    in GSPMD constraints)."""
    return ShardingRules(rules={
        k: tuple(a for a in v if a != axis) for k, v in rules.rules.items()})


def shard_map(f, mesh, *, in_specs, out_specs, manual_axes=None):
    """`jax.shard_map` manual over `manual_axes` (default: every mesh
    axis), the entry of every manual-collective region in the repo
    (DESIGN §10). Replication checking is off: these regions return
    deliberately-replicated outputs the checker cannot verify."""
    import jax
    manual = frozenset(manual_axes if manual_axes is not None
                       else mesh.axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=manual,
                         check_vma=False)


# ---------------------------------------------------------------------------
# Mesh context
# ---------------------------------------------------------------------------

_STATE = threading.local()


@contextlib.contextmanager
def use_mesh(mesh, rules: ShardingRules):
    """Activate (mesh, rules) for `logical_constraint` on this thread."""
    prev = getattr(_STATE, "ctx", None)
    _STATE.ctx = (mesh, rules)
    try:
        yield mesh
    finally:
        _STATE.ctx = prev


def current_context():
    """(mesh, rules) of the innermost use_mesh, or None."""
    return getattr(_STATE, "ctx", None)


def named_sharding(mesh, rules: ShardingRules, logical_axes,
                   shape: Optional[tuple] = None) -> NamedSharding:
    return NamedSharding(mesh, rules.resolve(logical_axes, mesh, shape=shape))


def logical_constraint(x, logical_axes):
    """with_sharding_constraint by logical names; no-op outside use_mesh."""
    ctx = current_context()
    if ctx is None:
        return x
    import jax
    mesh, rules = ctx
    spec = rules.resolve(logical_axes, mesh, shape=tuple(x.shape))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def tree_shardings(mesh, rules: ShardingRules, logical_tree, shapes_tree):
    """NamedSharding tree from parallel (logical-axes, ShapeDtypeStruct)
    trees — logical leaves are tuples, so flatten with an explicit is_leaf."""
    import jax
    flat_l, treedef = jax.tree.flatten(
        logical_tree, is_leaf=lambda v: isinstance(v, tuple))
    flat_s = jax.tree.leaves(shapes_tree)
    if len(flat_l) != len(flat_s):
        raise ValueError(
            f"logical tree has {len(flat_l)} leaves, shapes tree "
            f"{len(flat_s)}")
    return jax.tree.unflatten(treedef, [
        named_sharding(mesh, rules, log, shape=s.shape)
        for log, s in zip(flat_l, flat_s)])
