"""Multi-shot (gradient/STE) training for ULEEN (§III-B2).

Continuous Bloom tables in [-1, 1], unit-step binarisation on the forward
pass, straight-through gradients, softmax + cross-entropy over summed
ensemble responses, Adam(1e-3), dropout(0.5) on filter outputs. Hashes are
precomputed per batch (they carry no gradient).

The train step is a pure function of (params, opt_state, hashes, labels, rng)
so it pjit-shards over the production mesh: batch over data axes, tables
replicated or sharded over `model` by class (see repro/dist/sharding.py).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.model import (SubmodelStatic, UleenParams, UleenSpec,
                              compute_hashes, forward)
from repro.train import optimizer as opt_lib


@dataclasses.dataclass(frozen=True)
class MultiShotConfig:
    epochs: int = 10
    batch_size: int = 256
    learning_rate: float = 1e-3
    clip_table: float = 1.0          # keep entries in [-1, 1] (paper init range)
    label_smoothing: float = 0.0
    seed: int = 0
    verbose: bool = False


def cross_entropy(scores: jnp.ndarray, labels: jnp.ndarray,
                  smoothing: float = 0.0) -> jnp.ndarray:
    logp = jax.nn.log_softmax(scores, axis=-1)
    m = scores.shape[-1]
    onehot = jax.nn.one_hot(labels, m)
    if smoothing:
        onehot = onehot * (1.0 - smoothing) + smoothing / m
    return -jnp.mean(jnp.sum(onehot * logp, axis=-1))


def block_rng(rng: jax.Array, block: int | jnp.ndarray) -> jax.Array:
    """The dropout rng of batch block `block` within one step.

    Shared by the blocked single-device reference (`grad_blocks > 1`) and
    the distributed trainer (`launch/uleen_cell.make_uleen_dist_train_step`)
    — identical folding is what makes their dropout masks, and therefore
    their gradients, bit-identical (DESIGN §10).
    """
    return jax.random.fold_in(rng, block)


def make_loss_fn(spec: UleenSpec, smoothing: float = 0.0) -> Callable:
    """(params, hashes, labels, rng) -> (loss, acc) of one batch: the STE
    forward with dropout, cross-entropy and accuracy."""
    def loss_fn(params: UleenParams, hashes, labels, rng):
        scores = forward(spec, params, hashes, train=True, rng=rng)
        loss = cross_entropy(scores, labels, smoothing)
        acc = jnp.mean(jnp.argmax(scores, -1) == labels)
        return loss, acc
    return loss_fn


def block_grads(loss_fn, params, hashes, labels, rng, blocks: int,
                first_block: int | jnp.ndarray = 0):
    """Per-block (grads, loss, acc) stacks, each with leading axis
    `blocks`: the rows split into `blocks` equal blocks, block j of them
    global block `first_block + j`, each differentiated whole with its own
    dropout rng (`block_rng`). The one per-block computation of both
    trainers (DESIGN §10)."""
    b = labels.shape[0]
    if b % blocks:
        raise ValueError(f"batch {b} not divisible by {blocks} blocks")
    hs = tuple(h.reshape(blocks, b // blocks, *h.shape[1:]) for h in hashes)
    ys = labels.reshape(blocks, b // blocks)

    def block(j):
        (loss, acc), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, tuple(h[j] for h in hs), ys[j],
            block_rng(rng, first_block + j))
        return g, loss, acc

    return jax.lax.map(block, jnp.arange(blocks))


def fold_blocks(grads, losses, accs):
    """(grads, loss, acc) from per-block stacks with leading axis S: a left
    fold in block order (lax.scan), divided by S at the end.

    The one reduction both trainers run (DESIGN §10). The fold is its own
    loop over already-materialised block gradients: accumulating inside
    the loop that computes them lets XLA merge the running sum into each
    block's scatter-add, which changes the summation order and drifts the
    table gradients by an ulp.
    """
    s = losses.shape[0]

    def body(acc, xs):
        g_acc, l_acc, a_acc = acc
        gb, lb, ab = xs
        return (jax.tree.map(lambda x, y: x + y, g_acc, gb),
                l_acc + lb, a_acc + ab), None

    zeros = jax.tree.map(lambda g: jnp.zeros(g.shape[1:], jnp.float32),
                         grads)
    (g, loss, acc), _ = jax.lax.scan(
        body, (zeros, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (grads, losses, accs))
    inv = 1.0 / s
    return jax.tree.map(lambda x: x * inv, g), loss * inv, acc * inv


def blocked_grads(loss_fn, params, hashes, labels, rng, grad_blocks: int):
    """(grads, loss, acc) via the canonical blocked batch reduction.

    The batch splits into `grad_blocks` equal row blocks (`block_grads`),
    and the stacked block gradients combine by `fold_blocks`. The fold
    order is FIXED — independent of how the batch is later laid out over
    a mesh — so a distributed trainer that computes the same blocks on
    different devices and folds the gathered stack reproduces this
    function bit-for-bit (DESIGN §10: float addition is not associative;
    a plain `jnp.mean` over a sharded batch is reduced in mesh-dependent
    order and drifts ~1e-7/step).
    """
    return fold_blocks(*block_grads(loss_fn, params, hashes, labels, rng,
                                    grad_blocks))


def apply_step(optimizer: opt_lib.Optimizer, params, opt_state, grads,
               clip_table: float = 1.0):
    """The optimizer update both trainers apply: Adam, then the table
    clip to [-clip_table, clip_table]."""
    updates, opt_state = optimizer.update(grads, opt_state, params)
    params = opt_lib.apply_updates(params, updates)
    if clip_table:
        params = params._replace(tables=tuple(
            jnp.clip(t, -clip_table, clip_table) for t in params.tables))
    return params, opt_state


def make_train_step(spec: UleenSpec, optimizer: opt_lib.Optimizer,
                    clip_table: float = 1.0, smoothing: float = 0.0,
                    *, grad_blocks: int = 1) -> Callable:
    """The single-device multi-shot STE train step.

    grad_blocks=1 (default) is the plain formulation every example/test
    uses. grad_blocks=S>1 switches to the canonical blocked batch
    reduction (`blocked_grads`) — the parity reference the executed
    distributed trainer is asserted bit-identical against (DESIGN §10).
    """
    loss_fn = make_loss_fn(spec, smoothing)

    def train_step(params, opt_state, hashes, labels, rng):
        if grad_blocks > 1:
            grads, loss, acc = blocked_grads(loss_fn, params, hashes,
                                             labels, rng, grad_blocks)
        else:
            (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, hashes, labels, rng)
        params, opt_state = apply_step(optimizer, params, opt_state, grads,
                                       clip_table)
        return params, opt_state, loss, acc

    return train_step


def make_eval_fn(spec: UleenSpec) -> Callable:
    def eval_fn(params, hashes, labels):
        scores = forward(spec, params, hashes, train=False)
        return jnp.mean(jnp.argmax(scores, -1) == labels)
    return eval_fn


class TrainResult(NamedTuple):
    params: UleenParams      # best-validation-epoch snapshot
    history: list
    val_accuracy: float      # accuracy of the returned params


def train_multi_shot(spec: UleenSpec, statics: Sequence[SubmodelStatic],
                     params: UleenParams,
                     bits_train: jnp.ndarray, labels_train: jnp.ndarray,
                     bits_val: jnp.ndarray, labels_val: jnp.ndarray,
                     cfg: MultiShotConfig = MultiShotConfig()) -> TrainResult:
    """Single-host training driver (examples/tests). The distributed driver
    lives in repro/launch/train.py and reuses make_train_step under pjit.

    Returns the params of the best-validation epoch (early stopping by
    snapshot): STE + dropout(0.5) training never converges pointwise — the
    binarised model keeps hopping between nearby solutions — so the last
    epoch is an arbitrary draw from that plateau, not its best point.
    val_accuracy is the selected epoch's accuracy on the val split, i.e.
    the split also does model selection (upward-biased by the max over
    epochs). That mirrors the one-shot baseline, whose bleaching threshold
    is likewise searched on the val split — comparisons between the two
    select symmetrically. Report on a held-out test split for papers."""
    optimizer = opt_lib.adam(cfg.learning_rate)
    opt_state = optimizer.init(params)
    train_step = jax.jit(make_train_step(spec, optimizer, cfg.clip_table,
                                         cfg.label_smoothing))
    eval_fn = jax.jit(make_eval_fn(spec))

    # Hashes are static per sample: compute once for the whole epoch set.
    h_train = compute_hashes(spec, statics, bits_train)
    h_val = compute_hashes(spec, statics, bits_val)

    n = bits_train.shape[0]
    steps_per_epoch = max(1, n // cfg.batch_size)
    rng = jax.random.PRNGKey(cfg.seed)
    history = []
    rng_np = np.random.default_rng(cfg.seed)
    best_acc, best_params = -1.0, params

    for epoch in range(cfg.epochs):
        perm = rng_np.permutation(n)
        ep_loss = ep_acc = 0.0
        for s in range(steps_per_epoch):
            idx = perm[s * cfg.batch_size:(s + 1) * cfg.batch_size]
            hb = tuple(h[idx] for h in h_train)
            yb = labels_train[idx]
            rng, sub = jax.random.split(rng)
            params, opt_state, loss, acc = train_step(params, opt_state, hb, yb, sub)
            ep_loss += float(loss); ep_acc += float(acc)
        val_acc = float(eval_fn(params, h_val, labels_val))
        if val_acc > best_acc:
            best_acc, best_params = val_acc, params
        history.append(dict(epoch=epoch, loss=ep_loss / steps_per_epoch,
                            train_acc=ep_acc / steps_per_epoch, val_acc=val_acc,
                            time=time.time()))
        if cfg.verbose:
            print(f"[multi-shot] epoch {epoch}: loss={history[-1]['loss']:.4f} "
                  f"train_acc={history[-1]['train_acc']:.4f} val_acc={val_acc:.4f}")
    return TrainResult(params=best_params, history=history,
                       val_accuracy=best_acc if history else 0.0)


def evaluate(spec: UleenSpec, statics: Sequence[SubmodelStatic],
             params: UleenParams, bits: jnp.ndarray, labels: jnp.ndarray) -> float:
    hashes = compute_hashes(spec, statics, bits)
    return float(jax.jit(make_eval_fn(spec))(params, hashes, labels))
