"""Serve entry for the packed domain: PackedTables -> ensemble scores.

The packed analogue of `core/model.py::forward_binary_fused`: one
`kernels.ops.wnn_scores` dispatch per submodel on the raw thermometer
tuples, with the tables staying uint32 bitplanes end-to-end — the traced
program contains no int8 table and no unpack (the acceptance contract of
DESIGN §2 "Packed layout"). `core/export.py::artifact_scores` and the
serve engine's WNN batch path (`launch/scheduler.py::WnnBatcher`) both
route through here.

Under an active `dist.sharding.use_mesh` context each submodel's kernel
runs per shard (`ops.wnn_scores`) and the score matrix keeps the
("batch", "classes") logical sharding, so tables partitioned over `model`
by class (DESIGN §7) score their own class columns locally;
`packed_predict` gathers the (B, M) matrix and takes the final argmax —
the one cross-device step of the class-sharded dataflow. Outside a mesh
context every constraint is a no-op.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist import sharding as sh
from repro.packed.layout import PackedTables


def packed_scores(pt: PackedTables, bits: jnp.ndarray, *,
                  backend: str = "auto") -> jnp.ndarray:
    """bits: (B, total_bits) bool/int {0,1} -> scores (B, M) int32.

    backend="packed" runs the bitplane Pallas kernel per submodel
    (interpret mode off-TPU); "auto" keeps the packed domain but lets
    `ops.wnn_scores` pick the platform formulation (kernel on TPU, packed
    XLA gather oracle on CPU). "fused"/"gather" are rejected — they would
    need the 32× unpack this runtime exists to avoid; down-convert
    explicitly via `layout.unpack_words` if that is really wanted.

    The returned matrix keeps the ("batch", "classes") partial-score
    sharding inside a mesh context — callers that need the gathered
    matrix (or the prediction) go through `packed_predict`.
    """
    from repro.kernels import ops  # late import: layout stays pallas-free
    if backend not in ("packed", "auto"):
        raise ValueError(
            f"packed_scores serves the packed domain only (backend="
            f"'packed'|'auto', got {backend!r}); use core.model."
            "forward_binary_fused for the unpacked formulations")
    pt.validate()
    bits = jnp.asarray(bits)
    scores = jnp.zeros((bits.shape[0], pt.num_classes), jnp.int32)
    zero_bias = jnp.zeros((pt.num_classes,), jnp.int32)
    for i, (words, mask, perm, h3, entries) in enumerate(zip(
            pt.words, pt.masks, pt.perms, pt.h3s, pt.entries)):
        tuples = bits[:, perm].astype(jnp.int8)          # (B, N_f, n)
        # inside a mesh each partial is ("batch", "classes")-sharded
        # by wnn_scores' shard_map
        with jax.named_scope(f"submodel{i}"):
            scores = scores + ops.wnn_scores(tuples, h3, words, mask,
                                             zero_bias, backend=backend,
                                             entries=entries)
    # the bias add must ALSO be pinned: bias is class-sharded, and an
    # unconstrained `scores + bias` lets GSPMD hoist the gather above the
    # add — two all-gathers of the (B, M) matrix instead of the one this
    # dataflow promises (the collective-budget lint rule enforces it)
    return sh.logical_constraint(scores + pt.bias[None],
                                 ("batch", "classes"))


def stacked_scores(st, bits: jnp.ndarray, tids: jnp.ndarray, *,
                   backend: str = "auto", valid=None) -> jnp.ndarray:
    """Tenant-routed fleet scores (DESIGN §11): every row of `bits` is
    scored against tenant `tids[row]`'s tables in ONE fixed-shape program
    — `ops.wnn_scores_tenant` per submodel plus the row-gathered bias.

    st: `layout.StackedPackedTables`; bits: (B, total_bits) {0,1}; tids:
    (B,) int32 in [0, T). `valid` (optional (B,) bool) zeroes rows this
    caller does not own — the tenant-sharded path masks non-local rows
    before its single psum, so invalid/foreign rows contribute exactly 0.

    Packed-domain only, like `packed_scores`. No sharding constraints are
    applied here: the function must be callable inside a `shard_map`
    manual region, where GSPMD constraints are illegal — the GSPMD
    fallback constrains in `stacked_predict` instead.
    """
    from repro.kernels import ops  # late import: layout stays pallas-free
    if backend not in ("packed", "auto"):
        raise ValueError(
            f"stacked_scores serves the packed domain only (backend="
            f"'packed'|'auto', got {backend!r})")
    st.validate()
    bits = jnp.asarray(bits)
    tids = jnp.asarray(tids, jnp.int32)
    scores = jnp.zeros((bits.shape[0], st.num_classes), jnp.int32)
    for perm, h3, words, mask, entries in zip(
            st.perms, st.h3s, st.words, st.masks, st.entries):
        scores = scores + ops.wnn_scores_tenant(
            bits, tids, perm, h3, words, mask, backend=backend,
            entries=entries)
    scores = scores + st.bias[tids]
    if valid is not None:
        scores = jnp.where(valid[:, None], scores, 0)
    return scores


def stacked_predict(st, bits: jnp.ndarray, tids: jnp.ndarray, *,
                    backend: str = "auto"):
    """(scores (B, M) int32, per-row argmax (B,) int32) for a replicated
    fleet — the unsharded/fallback tail of the multi-tenant dataflow.
    Constrains the matrix to ("batch", None) so a mesh context shards the
    batch while the (KB-scale per tenant) stack stays replicated."""
    scores = sh.logical_constraint(
        stacked_scores(st, bits, tids, backend=backend), ("batch", None))
    return scores, jnp.argmax(scores, axis=-1).astype(jnp.int32)


def make_tenant_sharded_predict(st_spec, mesh, rules, global_batch: int, *,
                                backend: str = "auto"):
    """Build `predict(st, bits, tids) -> (scores, preds)` with the fleet
    partitioned over `mesh` by tenant (DESIGN §11).

    Each `model` shard holds T/degree whole tenants (`tenant_shard`), so
    inside the `shard_map` manual region a shard scores only the rows
    whose tenant it owns — local index `tid - lo`, ownership-masked — and
    the masked partials cross the mesh in ONE `psum` (int32 addition is
    associative: bit-exact vs the replicated path; rows whose tenant id
    is out of range everywhere score 0 and argmax to class 0). Batch rows
    shard over the batch axes; tenant tables never move.

    Falls back to `stacked_predict` (GSPMD, replicated stack) when the
    `tenants` axis resolves to replication — T not dividing the mesh axis
    or a trivial mesh — so callers never special-case awkward fleets.

    `st_spec`: a StackedPackedTables of arrays or ShapeDtypeStructs
    (geometry + shapes source only; the returned fn takes real arrays).
    """
    entry, degree = sh.tenant_partition(mesh, st_spec.num_tenants, rules)
    if degree == 1:
        return lambda st, bits, tids: stacked_predict(st, bits, tids,
                                                      backend=backend)
    t_axes = entry if isinstance(entry, tuple) else (entry,)
    t_loc = st_spec.num_tenants // degree
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    b_entry = rules.resolve(("batch",), mesh, shape=(global_batch,))[0]

    def local(st_loc, bits_l, tids_l):
        from repro.packed import layout
        # the manual region sees sliced leaves but the pytree aux still
        # carries the global T — rebuild the local view so validation
        # checks the shard's actual extent
        st_loc = layout.StackedPackedTables(
            words=st_loc.words, masks=st_loc.masks, perms=st_loc.perms,
            h3s=st_loc.h3s, bias=st_loc.bias, entries=st_loc.entries,
            num_classes=st_loc.num_classes, num_tenants=t_loc)
        # linear shard index over the tenant mesh axes == the slice order
        # device_put uses for the leading dim, so shard i holds tenants
        # [i*t_loc, (i+1)*t_loc)
        idx = jnp.int32(0)
        for ax in t_axes:
            idx = idx * sizes[ax] + jax.lax.axis_index(ax)
        lo = idx * t_loc
        own = (tids_l >= lo) & (tids_l < lo + t_loc)
        part = stacked_scores(st_loc, bits_l,
                              jnp.clip(tids_l - lo, 0, t_loc - 1),
                              backend=backend, valid=own)
        scores = jax.lax.psum(part, t_axes)   # the ONE collective
        return scores, jnp.argmax(scores, axis=-1).astype(jnp.int32)

    from jax.sharding import PartitionSpec as P
    return sh.shard_map(
        local, mesh,
        in_specs=(st_spec.tenant_pspecs(mesh, rules),
                  P(b_entry, None), P(b_entry)),
        out_specs=(P(b_entry, None), P(b_entry)))


def packed_predict(pt: PackedTables, bits: jnp.ndarray, *,
                   backend: str = "auto"):
    """(gathered scores (B, M) int32, argmax predictions (B,) int32).

    The class-sharded serve dataflow's tail (DESIGN §7): per-shard
    partial score columns -> one all-gather of the (B, M) matrix (the
    only cross-device traffic, B×M×4 bytes — the tables never move) ->
    argmax over the full class axis. int32 addition is associative, so
    the gathered scores are bit-identical to the replicated path's.
    """
    scores = packed_scores(pt, bits, backend=backend)
    from repro.kernels import ops
    return ops.ensemble_predict(scores)
