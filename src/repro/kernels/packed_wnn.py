"""Packed-domain ULEEN inference kernel: bitplane tables, never unpacked.

The fused kernel (`fused_wnn.py`) scans `(M, N_f, E)` int8 tables — 8 bits
per Bloom-filter entry where the accelerator stores 1. This kernel keeps
the tables in the artifact's native uint32 bitplane layout
(`core/export.py::pack_table`: 32 entries per word, little-endian bits):

    entry h of filter (m, f)  ==  bit (h & 31) of word[m, f, h >> 5]

It runs the fused kernel's body (`fused_wnn.wnn_lookup`) over the W = E/32
words instead of the E entries: the select scan picks word `h >> 5`, and
shift/AND on the VPU extracts the addressed bit. Hash, AND-across-k,
popcount and bias are shared, so the two kernels are exactly score-equal
by contract.

VMEM per class and filter tile is W·4 bytes of block and W·4 of
transposed scratch against E bytes and E·4 for int8 — an 8× density win
or better, and the scan is 32× shorter. At ULN-XL (E = 2^15) the
int8 block cannot hold the classes at all, while the packed plan splits
them into class blocks that fit (DESIGN §2 "Packed layout").

The uint32 words are bitcast to int32 outside the kernel (bit pattern
preserved); `(word >> b) & 1` extracts bit b correctly under arithmetic
shift.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fused_wnn import VMEM_LIMIT  # noqa: F401  (re-export)
from repro.kernels.fused_wnn import plan_blocks, wnn_lookup
# the single definition of the packed word-width rule (one whole padded
# word for E < 32) — validation (ops.py) and kernel blocking must agree
from repro.packed.layout import word_count


def word_plan(b: int, n: int, m: int, words: int, *,
              block_b: int = 128) -> dict:
    """The block geometry `packed_wnn` launches for B = b rows over W =
    `words` words per filter: classes split into blocks where the whole
    class set's words would not fit."""
    return plan_blocks(b, n, m, words, 4, block_b=block_b,
                       split_classes=True)


def vmem_plan(b: int, n: int, m: int, entries: int, *,
              block_b: int = 128) -> dict:
    """`word_plan` for E = `entries` (W = word_count(E)), and whether its
    analytical VMEM footprint fits the hard per-core limit
    (`VMEM_LIMIT`) — the packed twin of `fused_wnn.vmem_plan`, evaluated
    without tracing for the lint layer."""
    return word_plan(b, n, m, word_count(entries), block_b=block_b)


def packed_wnn(tuples: jnp.ndarray, params: jnp.ndarray,
               words: jnp.ndarray, mask: jnp.ndarray, bias: jnp.ndarray, *,
               block_b: int = 128, interpret: bool = False) -> jnp.ndarray:
    """tuples: (B, N_f, n) int8 {0,1}; params: (k, n) int32;
    words: (M, N_f, W) uint32 bitplanes; mask: (M, N_f) int8;
    bias: (M,) int32 -> scores (B, M) int32. Pads B and N_f internally;
    padded filters carry zero mask, so they never score.
    """
    b, _, n = tuples.shape
    m, _, w = words.shape
    plan = word_plan(b, n, m, w, block_b=block_b)
    words_i32 = jax.lax.bitcast_convert_type(words, jnp.int32)
    return wnn_lookup(tuples, params, words_i32, mask, bias, plan,
                      packed=True, interpret=interpret)
