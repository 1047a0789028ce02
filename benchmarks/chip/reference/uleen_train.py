"""Plain reference of ULEEN multi-shot training (arXiv:2304.10618,
Sec. III-B2), for the first steps of a run.

Continuous Bloom tables, initialised nearly empty (uniform in
[-s, 0.1 s]); a filter's response is the unit step of the smallest of its
k addressed entries, with a straight-through gradient (the step's
derivative taken as 1, so the gradient reaches the entry that was the
minimum); pruning masks, held as parameters that no gradient reaches;
dropout of p on filter outputs, one mask per (row, filter) shared by the
classes where the configuration says so;
class scores summed over filters in float32 plus a bias; softmax cross
entropy, averaged over the batch; Adam; tables clipped to [-1, 1].

The batch is cut into `grad_blocks` blocks; block j of step s draws its
dropout from fold_in(fold_in(key(seed), s), j), split once per submodel,
and step s reads the rows default_rng(seed * 1_000_003 + s) draws: the
same draws the configuration's trainer is run with, so the two can be
compared step by step. The gradient is that of the mean of the block
losses, taken whole in float32; the table gathers run in `gather_dtype`
(float32 unless a lower precision is asked for).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def batch_rows(seed: int, step: int, n: int, batch: int) -> np.ndarray:
    return np.random.default_rng(seed * 1_000_003 + step).integers(
        0, n, size=batch)


def init_params(config: dict, seed: int):
    tr = config["training"]
    s = float(tr["init_scale"])
    key = jax.random.PRNGKey(seed)
    m = config["num_classes"]
    tables = []
    for sm in config["submodels"]:
        key, sub = jax.random.split(key)
        n_f = math.ceil(config["total_bits"] / sm["inputs_per_filter"])
        tables.append(jax.random.uniform(
            sub, (m, n_f, 2 ** sm["log2_entries"]), jnp.float32, -s, 0.1 * s))
    return {"tables": tuple(tables), "bias": jnp.zeros((m,), jnp.float32),
            "masks": tuple(jnp.ones(t.shape[:2], jnp.float32)
                           for t in tables)}


def _hashes(bits, perm, h3):
    tuples = bits[:, perm]                                   # (B, N_f, n)
    chosen = jnp.where(tuples[:, :, None, :] != 0,
                       h3.astype(jnp.int32)[None, None], 0)  # (B,N_f,k,n)
    return jax.lax.reduce(chosen, jnp.int32(0), jax.lax.bitwise_xor, (3,))


def _block_loss(params, statics, bits, labels, key, *, dropout,
                shared, gather_dtype):
    rows = bits.shape[0]
    m = params["bias"].shape[0]
    scores = jnp.zeros((rows, m), jnp.float32)
    for table, mask, (perm, h3) in zip(params["tables"], params["masks"],
                                       statics):
        h = _hashes(bits, perm, h3)                          # (B, N_f, k)
        _, n_f, e = table.shape
        flat = (jnp.arange(n_f)[None, :, None] * e + h).reshape(rows, -1)
        t = table.astype(gather_dtype).reshape(m, n_f * e)
        vals = jnp.take(t, flat, axis=1)                     # (M,B,N_f*k)
        vals = vals.reshape(m, rows, n_f, -1).transpose(1, 0, 2, 3)
        low = jnp.min(vals, axis=-1)                         # (B, M, N_f)
        fires = low + jax.lax.stop_gradient(
            jnp.where(low >= 0, 1.0, 0.0).astype(low.dtype) - low)
        fires = fires * (mask != 0)[None].astype(fires.dtype)
        key, sub = jax.random.split(key)
        shape = (rows, 1, n_f) if shared else fires.shape
        keep = jax.random.bernoulli(sub, 1.0 - dropout, shape)
        fires = fires * keep.astype(fires.dtype) / (1.0 - dropout)
        scores = scores + jnp.sum(fires, axis=-1, dtype=jnp.float32)
    scores = scores + params["bias"][None]
    logp = jax.nn.log_softmax(scores, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))


@functools.partial(jax.jit, static_argnames=(
    "blocks", "dropout", "shared", "gather_dtype"))
def _grads(params, statics, bits, labels, step_key, *, blocks,
           dropout, shared, gather_dtype):
    rows = bits.shape[0] // blocks
    xs = (bits.reshape(blocks, rows, -1), labels.reshape(blocks, rows),
          jnp.arange(blocks))

    def loss(p):
        def one(x):
            b, y, j = x
            return _block_loss(p, statics, b, y,
                               jax.random.fold_in(step_key, j),
                               dropout=dropout, shared=shared,
                               gather_dtype=gather_dtype)
        return jnp.mean(jax.lax.map(one, xs))

    return jax.value_and_grad(loss)(params)


@jax.jit
def _adam(params, mu, nu, grads, count, lr):
    b1, b2, eps = 0.9, 0.999, 1e-8
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    new = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, mu, nu)
    new["tables"] = tuple(jnp.clip(t, -1.0, 1.0) for t in new["tables"])
    return new, mu, nu


def run(config: dict, statics, bits: np.ndarray, labels: np.ndarray, *,
        seed: int, steps: int, batch: int, grad_blocks: int,
        gather_dtype=jnp.float32, row_share: float = 1.0) -> dict:
    """The first `steps` steps: each step's loss, the first step's
    gradient, and the parameters before, after the first step and after
    the last. `row_share` < 1 keeps
    only the first rows of each block, the mean taken over them: a fault
    for the check to catch, never the configuration's training."""
    tr = config["training"]
    statics = tuple((jnp.asarray(p), jnp.asarray(h)) for p, h in statics)
    params = init_params(config, seed)
    start = jax.tree.map(np.asarray, params)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad, first = [], None, None
    base = jax.random.PRNGKey(seed)
    with jax.default_matmul_precision("highest"):
        for s in range(steps):
            idx = batch_rows(seed, s, bits.shape[0], batch)
            per = batch // grad_blocks
            idx = idx.reshape(grad_blocks, per)[:, :int(per * row_share)]
            idx = idx.reshape(-1)
            loss, grads = _grads(
                params, statics, jnp.asarray(bits[idx]),
                jnp.asarray(labels[idx]), jax.random.fold_in(base, s),
                blocks=grad_blocks, dropout=float(tr["dropout"]),
                shared=bool(tr["dropout_shared_classes"]),
                gather_dtype=gather_dtype)
            if first_grad is None:
                first_grad = jax.tree.map(np.asarray, grads)
            losses.append(float(loss))
            params, mu, nu = _adam(params, mu, nu, grads,
                                   jnp.float32(s + 1),
                                   jnp.float32(tr["learning_rate"]))
            if first is None:
                first = jax.tree.map(np.asarray, params)
    return {"losses": losses, "first_grad": first_grad, "start": start,
            "first": first, "end": jax.tree.map(np.asarray, params)}
