"""Plain reference of ULEEN inference (arXiv:2304.10618, Sec. III-A).

For each submodel: reorder the input bits by the submodel's input order
and cut them into N_f tuples of n bits; hash each tuple with each of the
k H3 functions (XOR of the parameter words whose input bit is set); read
the addressed entry of every class's Bloom filter; a filter responds when
all k entries are set; pruned filters never respond; a class's score is
the count of responding filters over all submodels, plus its bias.

Entry h of filter (m, f) is bit (h & 31) of table word h >> 5, the
artifact's stored layout. Integer arithmetic throughout, so the scores
are exact. Computed in blocks of rows, so that any number of rows fits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512


@functools.partial(jax.jit, static_argnames=("hashes_used",))
def _block_scores(subs, bias, bits, *, hashes_used):
    rows = bits.shape[0]
    total = jnp.zeros((rows, bias.shape[0]), jnp.int32)
    for words, mask, perm, h3 in subs:
        m, n_f, w = words.shape
        tuples = bits[:, perm]                                # (R, N_f, n)
        chosen = jnp.where(tuples[:, :, None, :] != 0,
                           h3.astype(jnp.int32)[None, None], 0)  # (R,N_f,k,n)
        hashes = jax.lax.reduce(chosen, jnp.int32(0), jax.lax.bitwise_xor,
                                (3,))                         # (R, N_f, k)
        hashes = hashes[:, :, :hashes_used]
        c = jnp.arange(m)[None, :, None, None]
        f = jnp.arange(n_f)[None, None, :, None]
        h = hashes[:, None, :, :]                             # (R,1,N_f,k)
        at = (c * n_f + f) * w + (h >> 5)                     # (R,M,N_f,k)
        word = jnp.take(words.reshape(-1), at)
        entry = (word >> (h & 31).astype(jnp.uint32)) & 1
        fires = jnp.all(entry == 1, axis=-1)                  # (R, M, N_f)
        fires = fires & mask[None]
        total = total + jnp.sum(fires, axis=-1, dtype=jnp.int32)
    return total + bias[None]


def scores(tables: dict, bits: np.ndarray, *,
           hashes_used: int | None = None) -> np.ndarray:
    """(rows, M) int32 scores of `bits` (rows, total_bits) {0,1} under
    `tables` (as `generators.tables` makes them). `hashes_used` < k reads
    only the first hashes: a model that breaks the configuration's AND
    across k."""
    bits = np.asarray(bits, np.uint8)
    rows = bits.shape[0]
    subs = tuple((jnp.asarray(s["words"]), jnp.asarray(s["mask"]),
                  jnp.asarray(s["perm"]), jnp.asarray(s["h3"]))
                 for s in tables["submodels"])
    k = min(s["num_hashes"] for s in tables["submodels"])
    used = k if hashes_used is None else hashes_used
    bias = jnp.asarray(tables["bias"])
    out = []
    for lo in range(0, rows, BLOCK):
        blk = bits[lo:lo + BLOCK]
        pad = BLOCK - blk.shape[0]
        if pad:
            blk = np.pad(blk, ((0, pad), (0, 0)))
        got = _block_scores(subs, bias, jnp.asarray(blk), hashes_used=used)
        out.append(np.asarray(got)[:BLOCK - pad])
    return np.concatenate(out) if out else np.zeros((0, bias.shape[0]),
                                                    np.int32)
