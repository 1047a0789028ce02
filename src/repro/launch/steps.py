"""Step functions the launcher jits and the dry-run AOT-compiles.

train_step: microbatched (lax.scan grad accumulation), bf16 compute with
fp32 master weights, MoE aux loss, optimizer update — one function of
(params, opt_state, batch), pure, shardable by in_shardings alone.

prefill_step / decode_step: the serving counterparts over ServeState.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.sharding import logical_constraint
from repro.models import kvcache, transformer
from repro.train import optimizer as opt_lib

AUX_COEF = 0.01


def cast_tree(tree, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree)


def cast_params_pinned(cfg, params, dtype):
    """fp32 master -> compute-dtype copy, with each cast pinned to the
    parameter's own sharding. Without the pin XLA hoists the convert past
    the FSDP all-gather and gathers fp32 — 2x the collective bytes and a
    full-size fp32 weight in HBM (§Perf it.3b: measured ~1 TB/step on
    mixtral train_4k)."""
    from repro.models import transformer
    logical = transformer.param_logical(cfg)
    flat_p, treedef = jax.tree.flatten(params)
    flat_l = jax.tree.leaves(logical,
                             is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for x, log in zip(flat_p, flat_l):
        if jnp.issubdtype(x.dtype, jnp.floating):
            x = logical_constraint(x.astype(dtype), log)
        out.append(x)
    return jax.tree.unflatten(treedef, out)


def lm_loss(cfg: ArchConfig, params, tokens, labels, *, frames=None,
            patches=None, remat: bool = True):
    """Mean next-token CE over real vocab entries (pad logits masked)."""
    logits, aux = transformer.forward_train(cfg, params, tokens,
                                            frames=frames, patches=patches,
                                            remat=remat)
    if cfg.patch_tokens:
        logits = logits[:, cfg.patch_tokens:]
    v = cfg.vocab_size
    if logits.shape[-1] > v:
        pad = jnp.full((logits.shape[-1] - v,), -1e30, logits.dtype)
        logits = logits.at[..., v:].set(pad)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = -jnp.mean(ll)
    return loss + AUX_COEF * aux, (loss, aux)


def make_train_step(cfg: ArchConfig, optimizer: opt_lib.Optimizer, *,
                    microbatches: int = 1,
                    compute_dtype=jnp.bfloat16,
                    remat: bool = True,
                    clip_norm: float = 1.0,
                    cross_pod_mesh=None) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    Gradients accumulate in fp32 sharded like the parameters; the optimizer
    runs once per global step. The microbatch loop is a lax.scan, so HLO
    size is independent of the accumulation depth.

    cross_pod_mesh: a mesh with a `pod` axis enables int8-compressed
    cross-pod gradient reduction (partial-manual shard_map over `pod`,
    GSPMD auto inside each pod; payload crosses the inter-pod link as
    int8 — §Perf it.7)."""

    def grads_of(params_c, mb):
        (total, (loss, aux)), grads = jax.value_and_grad(
            lambda p: lm_loss(cfg, p, mb["tokens"], mb["labels"],
                              frames=mb.get("frames"),
                              patches=mb.get("patches"), remat=remat),
            has_aux=True)(params_c)
        return grads, loss, aux

    def local_grads(params_c, batch):
        """Grad/loss/aux over this batch shard (microbatched)."""
        if microbatches > 1:
            def resplit(x):
                b = x.shape[0]
                return x.reshape(microbatches, b // microbatches,
                                 *x.shape[1:])
            mbs = {k: resplit(v) for k, v in batch.items()}

            def body(acc, mb):
                g_acc, l_acc, a_acc = acc
                g, l, a = grads_of(params_c, mb)
                g_acc = jax.tree.map(
                    lambda ga, gi: ga + gi.astype(jnp.float32), g_acc, g)
                return (g_acc, l_acc + l, a_acc + a), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params_c)
            (grads, loss, aux), _ = jax.lax.scan(
                body, (g0, jnp.zeros((), jnp.float32),
                       jnp.zeros((), jnp.float32)), mbs)
            inv = 1.0 / microbatches
            grads = jax.tree.map(lambda g: g * inv, grads)
            return grads, loss * inv, aux * inv
        grads, loss, aux = grads_of(params_c, batch)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        return grads, loss, aux

    use_compress = (cross_pod_mesh is not None
                    and "pod" in cross_pod_mesh.axis_names)

    def train_step(params, opt_state, batch):
        params_c = cast_params_pinned(cfg, params, compute_dtype) \
            if compute_dtype is not None else params

        if use_compress:
            from jax.sharding import PartitionSpec as P
            from repro.dist import sharding as shd
            from repro.train.compression import compressed_psum

            def per_pod(batch_pod):
                # constraints inside the manual-pod region must not
                # mention 'pod'
                ctx = getattr(shd._STATE, "ctx", None)
                if ctx is not None:
                    mgr = shd.use_mesh(ctx[0], shd.strip_axis(ctx[1], "pod"))
                else:
                    import contextlib
                    mgr = contextlib.nullcontext()
                with mgr:
                    grads, loss, aux = local_grads(params_c, batch_pod)
                grads, _ = compressed_psum(grads, "pod")
                return (grads, jax.lax.pmean(loss, "pod"),
                        jax.lax.pmean(aux, "pod"))

            grads, loss, aux = shd.shard_map(
                per_pod, cross_pod_mesh, in_specs=P("pod"),
                out_specs=P(), manual_axes=("pod",))(batch)
        else:
            grads, loss, aux = local_grads(params_c, batch)

        if clip_norm:
            grads, gnorm = opt_lib.clip_by_global_norm(grads, clip_norm)
        else:
            gnorm = opt_lib.global_norm(grads)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = opt_lib.apply_updates(params, updates)
        metrics = {"loss": loss, "aux": aux, "grad_norm": gnorm}
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: ArchConfig, *, max_len: int,
                      compute_dtype=None) -> Callable:
    def prefill_step(params, batch):
        if compute_dtype is not None:
            params = cast_tree(params, compute_dtype)
        logits, state = transformer.forward_prefill(
            cfg, params, batch["tokens"], max_len=max_len,
            frames=batch.get("frames"), patches=batch.get("patches"))
        return logits, state
    return prefill_step


def make_decode_step(cfg: ArchConfig, *, compute_dtype=None) -> Callable:
    def decode_step(params, token, state):
        if compute_dtype is not None:
            params = cast_tree(params, compute_dtype)
        return transformer.forward_decode(cfg, params, token, state)
    return decode_step


def write_state_slot(full, one, index):
    """Write a batch-1 ServeState into row `index` of a batch-wide state.

    Core primitive of prefill-into-slot (DESIGN §6): every leaf of the
    batch-1 tree is spliced into the batch-wide tree along its batch axis
    with a dynamic_update_slice, so the operation is fixed-shape and
    jit-compiles once regardless of which slot it targets. The batch axis
    is found per leaf by shape comparison — stacked cache leaves carry a
    leading layer axis, so batch is not always axis 0.
    """
    def upd(f, o):
        diff = [a for a, (fd, od) in enumerate(zip(f.shape, o.shape))
                if fd != od]
        if not diff:          # single-slot engine: the row is the whole state
            return o.astype(f.dtype)
        assert len(diff) == 1, (f.shape, o.shape)
        return jax.lax.dynamic_update_slice_in_dim(
            f, o.astype(f.dtype), index, axis=diff[0])
    return jax.tree.map(upd, full, one)


def make_slot_prefill_step(cfg: ArchConfig, *, max_len: int,
                           compute_dtype=None) -> Callable:
    """(params, batch, length, slot, state) -> (last logits, state').

    Prefills ONE request (batch-1 `batch["tokens"]`, optionally padded to a
    fixed bucket with `length` real tokens) against a fresh width-max_len
    cache and writes the result into row `slot` of the engine's batch-wide
    ServeState. Shapes are fixed per (prompt bucket), so a serving engine
    compiles one program per bucket at warmup and admits requests into
    freed slots mid-decode without recompiling (DESIGN §6).
    """
    def slot_prefill_step(params, batch, length, slot, state):
        if compute_dtype is not None:
            params = cast_tree(params, compute_dtype)
        logits, one = transformer.forward_prefill(
            cfg, params, batch["tokens"], max_len=max_len,
            frames=batch.get("frames"), patches=batch.get("patches"),
            length=length)
        return logits, write_state_slot(state, one, slot)
    return slot_prefill_step


def make_masked_decode_step(cfg: ArchConfig, *, compute_dtype=None) -> Callable:
    """(params, token, state, active) -> (logits, state').

    One decode step over every cache slot; `active` is a (B,) bool mask of
    slots holding live requests. Inactive slots still flow through the
    batch (shape-stable compilation — DESIGN §6) but their `pos` is frozen
    so an idle slot neither drifts through its ring buffer nor changes
    meaning between a request retiring and the next admission. Their cache
    rows may accumulate garbage; prefill-into-slot fully overwrites the
    visible prefix (pos ... kv_len) on admission, so no live slot can
    observe it.
    """
    def masked_decode_step(params, token, state, active):
        if compute_dtype is not None:
            params = cast_tree(params, compute_dtype)
        # active also rides into the forward as the MoE token mask:
        # expert capacity is shared across the batch, so a dead slot's
        # garbage token could otherwise evict a live token from an
        # expert queue — live rows must be a function of live rows only.
        logits, new = transformer.forward_decode(cfg, params, token, state,
                                                 token_mask=active)
        pos = jnp.where(active, new.pos, state.pos)
        return logits, new._replace(pos=pos)
    return masked_decode_step


# ---------------------------------------------------------------------------
# Paged serving steps (DESIGN §13)
# ---------------------------------------------------------------------------

# tree.map stops at these NamedTuples so paged pools (no batch axis) can be
# routed to scatters while everything else takes the per-slot splice.
_CACHE_LEAF_TYPES = (kvcache.AttnCache, kvcache.MLACache,
                     kvcache.PagedAttnCache, kvcache.PagedMLACache)


def write_paged_state_slot(full, one, slot, table_row):
    """`write_state_slot` for a paged engine: paged pool leaves scatter the
    batch-1 contiguous cache into the blocks of `table_row` ((MB,) int32);
    contiguous leaves (SSM/recurrent/windowed state, cross kv, pos) splice
    into row `slot` exactly as before. Fixed-shape either way."""
    def is_cache(x):
        return isinstance(x, _CACHE_LEAF_TYPES)

    def upd(f, o):
        if isinstance(f, kvcache.PagedAttnCache):
            return kvcache.paged_scatter_attn(f, o, table_row)
        if isinstance(f, kvcache.PagedMLACache):
            return kvcache.paged_scatter_mla(f, o, table_row)
        return write_state_slot(f, o, slot)

    return jax.tree.map(upd, full, one, is_leaf=is_cache)


def _state_row(cfg: ArchConfig, state, j: int):
    """Static batch-row j of a batch-A prefill state, keepdims — the
    batch axis sits behind the layer axis on scan-stacked segments."""
    segs = transformer.arch_segments(cfg)

    def take(tree, axis):
        return jax.tree.map(
            lambda l: jax.lax.slice_in_dim(l, j, j + 1, axis=axis), tree)

    caches = [take(c, 1 if seg.repeat > 1 else 0)
              for seg, c in zip(segs, state.caches)]
    cross = [None if x is None else take(x, 1 if seg.repeat > 1 else 0)
             for seg, x in zip(segs, state.cross)]
    pos = jax.lax.slice_in_dim(state.pos, j, j + 1, axis=0)
    return transformer.ServeState(caches=caches, cross=cross, pos=pos)


def make_paged_prefill_step(cfg: ArchConfig, *, max_len: int, admit: int,
                            compute_dtype=None) -> Callable:
    """(params, batch, lengths, slots, tables, state) -> (logits, state').

    Batched multi-slot prefill admission: `batch["tokens"]` is (admit, S)
    — up to `admit` same-bucket requests prefilled in ONE launch (short
    prompts amortised, DESIGN §13). lengths/slots are (admit,) int32,
    tables (admit, max_blocks). Partial groups pad with dummy rows that
    the engine orders FIRST and points at the first real request's slot
    (fully overwritten by the later real write) with an all-null table
    row, so dummies never touch live state. Paged cache leaves scatter
    into each row's blocks; contiguous leaves splice per slot."""
    def paged_prefill_step(params, batch, lengths, slots, tables, state):
        if compute_dtype is not None:
            params = cast_tree(params, compute_dtype)
        logits, one = transformer.forward_prefill(
            cfg, params, batch["tokens"], max_len=max_len,
            frames=batch.get("frames"), patches=batch.get("patches"),
            length=lengths)
        for j in range(admit):
            state = write_paged_state_slot(
                state, _state_row(cfg, one, j), slots[j], tables[j])
        return logits, state
    return paged_prefill_step


def make_paged_decode_step(cfg: ArchConfig, *, compute_dtype=None) -> Callable:
    """(params, token, state, active, block_tables) -> (logits, state').

    `make_masked_decode_step` plus the per-slot block tables (B, MB). An
    inactive slot's table row is all-null, so its (pos-frozen) write lands
    in the garbage-sink block 0 instead of a recycled live block."""
    def paged_decode_step(params, token, state, active, block_tables):
        if compute_dtype is not None:
            params = cast_tree(params, compute_dtype)
        logits, new = transformer.forward_decode(
            cfg, params, token, state, block_tables=block_tables,
            token_mask=active)
        pos = jnp.where(active, new.pos, state.pos)
        return logits, new._replace(pos=pos)
    return paged_decode_step


def paged_serve_state_zeros(cfg: ArchConfig, params, slots: int,
                            max_len: int, *, block_size: int,
                            num_blocks: int):
    """`serve_state_zeros` with full-width attn/MLA cache leaves replaced
    by shared block pools (no batch axis). SSM/recurrent/windowed-local
    leaves stay contiguous per slot: their state is O(1) (or O(window))
    per sequence already, so paging buys nothing (DESIGN §13)."""
    state = serve_state_zeros(cfg, params, slots, max_len)
    new_caches = []
    for seg, seg_cache in zip(transformer.arch_segments(cfg), state.caches):
        out = {}
        for name, c in seg_cache.items():
            ls = seg.layers[int(name[1:])]
            stack = seg.repeat if seg.repeat > 1 else None
            if ls.mixer == "attn":      # full-width GQA (sliding -> local)
                out[name] = kvcache.init_paged_attn_cache(
                    cfg.num_kv_heads, num_blocks, block_size,
                    cfg.resolved_head_dim, cfg.kv_cache_dtype, stack=stack)
            elif ls.mixer == "mla":
                out[name] = kvcache.init_paged_mla_cache(
                    num_blocks, block_size, cfg.kv_lora_rank,
                    cfg.qk_rope_dim, stack=stack)
            else:
                out[name] = c
        new_caches.append(out)
    return state._replace(caches=new_caches)


def serve_state_zeros(cfg: ArchConfig, params, slots: int, max_len: int):
    """All-zero batch-wide ServeState for an engine with `slots` cache
    rows: eval_shape over a 1-token prefill fixes the tree structure
    (incl. whisper cross-kv and stacked-layer caches), then every leaf is
    materialised as zeros. No prefill actually runs."""
    specs = {"tokens": jax.ShapeDtypeStruct((slots, 1), jnp.int32)}
    if cfg.encoder_layers:
        specs["frames"] = jax.ShapeDtypeStruct(
            (slots, cfg.encoder_frames, cfg.d_model), jnp.float32)
    if cfg.patch_tokens:
        specs["patches"] = jax.ShapeDtypeStruct(
            (slots, cfg.patch_tokens, cfg.d_model), jnp.float32)
    step = make_prefill_step(cfg, max_len=max_len)
    _, sspec = jax.eval_shape(step, params, specs)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), sspec)


def serve_state_spec(cfg: ArchConfig, batch: int, seq_len: int,
                     param_spec) -> Any:
    """Abstract ServeState after a seq_len prefill (for decode dry-runs):
    eval_shape over the prefill — no arrays are built."""
    specs = {"tokens": jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)}
    if cfg.encoder_layers:
        specs["frames"] = jax.ShapeDtypeStruct(
            (batch, cfg.encoder_frames, cfg.d_model), jnp.float32)
    if cfg.patch_tokens:
        specs["patches"] = jax.ShapeDtypeStruct(
            (batch, cfg.patch_tokens, cfg.d_model), jnp.float32)
    step = make_prefill_step(cfg, max_len=seq_len)
    _, state = jax.eval_shape(step, param_spec, specs)
    return state
