"""Standalone H3-hash Pallas kernel (training-path hash precompute hot spot).

The multi-shot trainer hashes the full training set once per run; for MNIST-
scale data that is B x N_f x k hashes over n-bit tuples. The kernel is the
unrolled XOR-select reduction of H3, tiled (batch x filters) so each
block's tuples live in VMEM while the (k, n) parameter matrix stays
resident (the paper's shared "Param RF").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _h3_hashes(bits_i32: jnp.ndarray, params_row) -> jnp.ndarray:
    """bits: (..., n) int32 in {0,1}; params_row: (n,) int32 -> (...,) int32.

    XOR-reduce of parameter words selected by set bits (unrolled; n <= ~40).
    """
    n = bits_i32.shape[-1]
    acc = jnp.zeros(bits_i32.shape[:-1], jnp.int32)
    for i in range(n):
        acc = acc ^ jnp.where(bits_i32[..., i] != 0, params_row[i], 0)
    return acc


def h3_hash_kernel(tuples_ref, params_ref, out_ref, *, num_hashes: int):
    bits = tuples_ref[...].astype(jnp.int32)          # (Bt, Ft, n)
    outs = []
    for j in range(num_hashes):
        outs.append(_h3_hashes(bits, params_ref[j, :]))
    out_ref[...] = jnp.stack(outs, axis=-1)           # (Bt, Ft, k)


def h3_hash_tiled(tuples: jnp.ndarray, params: jnp.ndarray, *,
                  block_b: int = 256, block_f: int = 512,
                  interpret: bool = False) -> jnp.ndarray:
    """tuples: (B, N_f, n) int8 {0,1}; params: (k, n) int32 -> (B, N_f, k)."""
    b, n_f, n = tuples.shape
    k = params.shape[0]
    block_b = min(block_b, max(8, b))
    block_f = min(block_f, max(8, n_f))
    pb, pf = (-b) % block_b, (-n_f) % block_f
    if pb or pf:
        tuples = jnp.pad(tuples, ((0, pb), (0, pf), (0, 0)))
    bp, fp = tuples.shape[0], tuples.shape[1]

    out = pl.pallas_call(
        functools.partial(h3_hash_kernel, num_hashes=k),
        grid=(bp // block_b, fp // block_f),
        in_specs=[
            pl.BlockSpec((block_b, block_f, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((k, n), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_f, k), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, fp, k), jnp.int32),
        interpret=interpret,
    )(tuples, params)
    return out[:b, :n_f]
