"""Fused ULEEN inference kernel: hash -> lookup -> AND -> popcount -> bias.

The whole accelerator pipeline (paper Fig. 8/9) as ONE Pallas TPU kernel per
submodel, on int8 {0,1} tables. The packed kernel (`packed_wnn.py`) runs
the same body on uint32 bitplanes; both share `wnn_lookup` below.

TPU layout (DESIGN §2): filters ride the 128 lanes and batch rows the
sublanes, so every step of the pipeline is a 2-D elementwise op on
`(rows, Ft)` tiles —

    h[b, f]    = XOR_i  (bit_i[b, f] ? p[j, i] : 0)          (VPU, per hash)
    val[b, f]  = table[m, f, h[b, f]]                          (select scan)

The lookup is a select scan over the table's entry axis: each aligned
sublane tile of entries is loaded once and row r's value is kept where
`h == r`. The kernel reads the tables in their stored `(M, N_f, C)`
layout and, once per grid step, transposes each class's `(128, C)` block
in VMEM into a `(C, 128)` int32 scratch, so each filter's entry column
is a row there, broadcast along sublanes; no MXU operand, no gather, and
no per-call table copy in HBM. The last filter block may run past N_f:
its filters carry zero mask (padded per call), so they never score.

The k looked-up values AND via minimum ({0,1}), the class mask
multiplies, and the popcount is a lane sum that lands in the `(B, M)`
response column of its class.

Grid: (batch_tiles, filter_tiles, class_tiles); the filter and class axes
are sequential so each batch tile's output block stays resident and
accumulates (bias added at the first step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# hard per-core VMEM on the target TPU generation — a block plan past this
# is not a perf problem but a Mosaic compile failure (kernel_bench skips
# such geometries; the `vmem-budget` lint rule flags them before tracing)
VMEM_LIMIT = 16 * 1024 * 1024
# what `plan_blocks` aims under, leaving the rest for Mosaic's own scratch
VMEM_BUDGET = 12 * 1024 * 1024

LANES = 128          # filters per block: one lane tile
ROWS = 32            # batch rows per inner step: the int8 sublane tile


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def block_vmem_bytes(block_b: int, block_f: int, block_m: int, n: int,
                     m: int, cands: int, itemsize: int) -> int:
    """Analytical VMEM footprint of one grid step, double-buffered inputs
    and output included (every block rounded up to its (8·k, 128) tile),
    plus the transposed int32 table scratch."""
    return (2 * (n * block_b * block_f                 # tuples int8
                 + block_m * block_f * _round_up(cands, LANES) * itemsize
                 + block_m * 8 * block_f * 4           # mask rows int32
                 + block_b * _round_up(m, LANES) * 4)  # (Bt, M) scores
            + block_m * _round_up(cands, 8) * block_f * 4  # (C, Ft) scratch
            + 8 * LANES * 4)                           # bias row


def plan_blocks(b: int, n: int, m: int, cands: int, itemsize: int, *,
                block_b: int = 128, split_classes: bool) -> dict:
    """The block geometry a WNN kernel launches for one submodel.

    Every block's last two dims stay tile-aligned: filters in whole lane
    tiles (`LANES`), batch rows in whole int8 sublane tiles (`ROWS`), the
    entry axis whole. Over `VMEM_BUDGET` the plan first splits the classes
    into divisor blocks (when `split_classes`), then halves the batch
    tile; `fits` says whether the result is inside `VMEM_LIMIT`.
    """
    bb = min(_round_up(block_b, ROWS), _round_up(max(b, 1), ROWS))
    divisors = [d for d in range(m, 0, -1) if m % d == 0]
    if not split_classes:
        divisors = divisors[:1]
    while True:
        for bm in divisors:
            vmem = block_vmem_bytes(bb, LANES, bm, n, m, cands, itemsize)
            if vmem <= VMEM_BUDGET:
                break
        if vmem <= VMEM_BUDGET or bb == ROWS:
            break
        bb = max(ROWS, bb // 2)
    return {"block_b": bb, "block_f": LANES, "block_m": bm,
            "vmem_bytes": vmem, "fits": vmem <= VMEM_LIMIT}


def vmem_plan(b: int, n: int, m: int, entries: int, *,
              block_b: int = 128) -> dict:
    """The block geometry `fused_wnn` would launch for (b, n, m, entries)
    and whether its analytical VMEM footprint fits the hard per-core
    limit — evaluated without tracing, so the lint layer can flag an
    over-budget BlockSpec as a finding instead of a Mosaic failure. The
    int8 table block keeps every class (its E-wide entry axis is what
    outgrows VMEM; the packed kernel is the large-E path)."""
    return plan_blocks(b, n, m, entries, 1, block_b=block_b,
                       split_classes=False)


def _wnn_lookup_kernel(params_ref, tuples_ref, block_ref, mask_ref,
                       bias_ref, out_ref, table_ref, *, num_hashes: int,
                       packed: bool):
    f_idx, m_idx = pl.program_id(1), pl.program_id(2)
    n, bt, ft = tuples_ref.shape
    block_m, cands, _ = table_ref.shape
    chunk = min(cands, 8)           # entries per int32 sublane tile
    classes = jax.lax.broadcasted_iota(jnp.int32, (ROWS, out_ref.shape[1]), 1)

    @pl.when((f_idx == 0) & (m_idx == 0))
    def _init():
        out_ref[...] = jnp.broadcast_to(bias_ref[...], out_ref.shape)

    def transpose(c_m, carry):      # (Ft, C) stored block -> (C, Ft)
        table_ref[c_m] = block_ref[c_m].astype(jnp.int32).T
        return carry

    jax.lax.fori_loop(0, block_m, transpose, 0)

    def lookup(c_m, idx):
        """(ROWS, Ft) entry `idx` of class c_m, scanned tile by tile."""
        def scan(c, val):
            c0 = pl.multiple_of(c * chunk, chunk)
            tile = table_ref[c_m, pl.ds(c0, chunk), :]
            for r in range(chunk):
                val = jnp.where(idx == c0 + r, tile[r:r + 1, :], val)
            return val
        return jax.lax.fori_loop(0, cands // chunk, scan,
                                 jnp.zeros((ROWS, ft), jnp.int32))

    def rows_step(s, carry):
        r0 = pl.multiple_of(s * ROWS, ROWS)

        def hash_step(i, hs):
            bit = tuples_ref[i, pl.ds(r0, ROWS), :].astype(jnp.int32) != 0
            return tuple(h ^ jnp.where(bit, params_ref[j, i], 0)
                         for j, h in enumerate(hs))

        hashes = jax.lax.fori_loop(
            0, n, hash_step,
            (jnp.zeros((ROWS, ft), jnp.int32),) * num_hashes)

        def class_step(c_m, part):
            resp = None
            for h in hashes:
                if packed:   # bit (h & 31) of word h >> 5
                    val = (lookup(c_m, h >> 5) >> (h & 31)) & 1
                else:
                    val = lookup(c_m, h)
                resp = val if resp is None else jnp.minimum(resp, val)
            popc = jnp.sum(resp * mask_ref[c_m], axis=1, keepdims=True)
            return jnp.where(classes == m_idx * block_m + c_m, popc, part)

        out_ref[pl.ds(r0, ROWS), :] += jax.lax.fori_loop(
            0, block_m, class_step, jnp.zeros(classes.shape, jnp.int32))
        return carry

    jax.lax.fori_loop(0, bt // ROWS, rows_step, 0)


def wnn_lookup(tuples: jnp.ndarray, params: jnp.ndarray, table: jnp.ndarray,
               mask: jnp.ndarray, bias: jnp.ndarray, plan: dict, *,
               packed: bool, interpret: bool = False) -> jnp.ndarray:
    """Shared launcher of both WNN kernels.

    tuples: (B, N_f, n) int8 {0,1}; params: (k, n) int32; table:
    (M, N_f, C) — C int8 {0,1} entries, or (packed) C int32 words of 32
    entries each; mask: (M, N_f); bias: (M,) -> scores (B, M) int32.
    The table goes to the kernel as stored; only the per-batch tuples and
    the small mask are laid out filters-minor and padded to the plan's
    blocks. Padded filters carry zero mask, so they never score.
    """
    b, n_f, n = tuples.shape
    m, _, cands = table.shape
    k = params.shape[0]
    bb, bf, bm = plan["block_b"], plan["block_f"], plan["block_m"]
    pb, pf = (-b) % bb, (-n_f) % bf
    tuples_t = jnp.pad(jnp.transpose(tuples, (2, 0, 1)),
                       ((0, 0), (0, pb), (0, pf)))            # (n, B, N_f)
    mask_t = jnp.pad((mask != 0).astype(jnp.int32),
                     ((0, 0), (0, pf)))[:, None, :]          # (M, 1, N_f)
    bias_t = bias.astype(jnp.int32)[None, :]                 # (1, M)
    bp, fp = b + pb, n_f + pf

    kernel = functools.partial(_wnn_lookup_kernel, num_hashes=k,
                               packed=packed)
    out = pl.pallas_call(
        kernel,
        name="packed_wnn" if packed else "fused_wnn",
        grid=(bp // bb, fp // bf, m // bm),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((n, bb, bf), lambda i, j, c: (0, i, j)),
            pl.BlockSpec((bm, bf, cands), lambda i, j, c: (c, j, 0)),
            pl.BlockSpec((bm, 1, bf), lambda i, j, c: (c, 0, j)),
            pl.BlockSpec((1, m), lambda i, j, c: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, m), lambda i, j, c: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, m), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, cands, bf), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(params.astype(jnp.int32), tuples_t, table, mask_t, bias_t)
    return out[:b]


def fused_wnn(tuples: jnp.ndarray, params: jnp.ndarray, table: jnp.ndarray,
              mask: jnp.ndarray, bias: jnp.ndarray, *, block_b: int = 128,
              interpret: bool = False) -> jnp.ndarray:
    """tuples: (B, N_f, n) int8 {0,1}; params: (k, n) int32;
    table: (M, N_f, E) int8 {0,1}; mask: (M, N_f) int8; bias: (M,) int32
    -> scores (B, M) int32.
    """
    b, _, n = tuples.shape
    m, _, entries = table.shape
    plan = vmem_plan(b, n, m, entries, block_b=block_b)
    return wnn_lookup(tuples, params, table.astype(jnp.int8), mask, bias,
                      plan, packed=False, interpret=interpret)
