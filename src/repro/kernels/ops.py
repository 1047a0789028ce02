"""Jit'd public wrappers for the Pallas kernels.

On the CPU backend (this container) the wrappers run the kernels in
interpret mode (bit-exact Python execution of the kernel body) or fall back
to the jnp oracle where that is faster; on TPU they lower to Mosaic. The
model code calls only these entry points.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as sh
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_tiled
from repro.kernels.fused_wnn import fused_wnn
from repro.kernels.h3_hash import h3_hash_tiled
from repro.kernels.packed_wnn import packed_wnn
from repro.kernels.thermometer import thermometer_decompress, thermometer_encode
from repro.packed import layout as packed_layout


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# WNN inference backend dispatch (DESIGN §2 "Adoption" + "Packed layout")
# ---------------------------------------------------------------------------

WNN_BACKENDS = ("fused", "gather", "packed", "auto")

# The fused kernel unrolls the H3 XOR-select over n and the k hash lookups in
# the kernel body; these bound the unroll so a bad spec fails loudly at trace
# time instead of producing an enormous Mosaic program.
_MAX_TUPLE_BITS = 64
_MAX_HASHES = 8


def resolve_wnn_backend(backend: str = "auto", *,
                        packed_tables: bool = False) -> str:
    """'auto' -> 'packed' when the tables are already uint32 bitplanes
    (never pay the 32× expansion), else 'fused' on TPU (the MXU
    formulation) / 'gather' elsewhere (plain-XLA gathers beat an
    interpret-mode kernel on CPU)."""
    if backend not in WNN_BACKENDS:
        raise ValueError(
            f"backend must be one of {WNN_BACKENDS}, got {backend!r}")
    if backend == "auto":
        if packed_tables:
            return "packed"
        return "fused" if _on_tpu() else "gather"
    return backend


def validate_wnn_geometry(tuples, params, table, mask, bias, *,
                          entries: int | None = None) -> None:
    """Shape/tile validation shared by every backend.

    `table` is either an unpacked (M, N_f, E) int8 table or a packed
    (M, N_f, E/32) uint32 bitplane (distinguished by dtype; packed planes
    must declare `entries` since E is not recoverable from the word
    count). Raises ValueError at trace time for geometry the kernels
    cannot honour bit-exactly — most importantly non-power-of-two
    `entries`: H3 XOR-composes parameter words in [0, E), which stays
    in-range only when E is a power of two; out-of-range hashes would
    match no entry (read zero) in the fused kernel but clip in the gather's
    `take_along_axis` (and address the wrong word in the packed layout).
    """
    if tuples.ndim != 3:
        raise ValueError(f"tuples must be (B, N_f, n), got {tuples.shape}")
    if params.ndim != 2 or table.ndim != 3 or mask.ndim != 2 or bias.ndim != 1:
        raise ValueError(
            "expected params (k, n), table (M, N_f, E) or packed "
            f"(M, N_f, E/32), mask (M, N_f), bias (M,); got {params.shape}, "
            f"{table.shape}, {mask.shape}, {bias.shape}")
    _, n_f, n = tuples.shape
    k, n_p = params.shape
    m, n_f_t, last = table.shape
    if table.dtype == jnp.uint32:
        if entries is None:
            raise ValueError(
                "packed uint32 tables must declare entries= (the word "
                "count alone does not determine E)")
        packed_layout.validate_packed_geometry(table, entries)
    else:
        if entries is not None and entries != last:
            raise ValueError(
                f"entries={entries} != table E={last}")
        if last & (last - 1) or last == 0:
            raise ValueError(
                f"entries={last} must be a power of two (H3 range closure)")
    if n_p != n:
        raise ValueError(f"params n={n_p} != tuples n={n}")
    if n_f_t != n_f:
        raise ValueError(f"table N_f={n_f_t} != tuples N_f={n_f}")
    if mask.shape != (m, n_f):
        raise ValueError(f"mask {mask.shape} != (M, N_f)=({m}, {n_f})")
    if bias.shape != (m,):
        raise ValueError(f"bias {bias.shape} != (M,)=({m},)")
    if n > _MAX_TUPLE_BITS:
        raise ValueError(f"n={n} exceeds the kernel unroll bound "
                         f"{_MAX_TUPLE_BITS}")
    if not 1 <= k <= _MAX_HASHES:
        raise ValueError(f"k={k} outside [1, {_MAX_HASHES}]")


def wnn_scores(tuples, params, table, mask, bias, *, backend: str = "auto",
               entries: int | None = None):
    """One submodel's inference scores (B, M) int32, backend-dispatched.

    tuples: (B, N_f, n) int8 {0,1}; params: (k, n) int32; table:
    (M, N_f, E) int8 {0,1} or packed (M, N_f, E/32) uint32 bitplanes
    (dtype-dispatched; packed input requires the static `entries=`);
    mask: (M, N_f) int8; bias: (M,) int32.

    backend="fused"  — the select-scan Pallas kernel on int8 tables
                       (interpret mode off-TPU, so the exact TPU kernel
                       body runs bit-for-bit on CPU);
    backend="gather" — the jnp take_along_axis oracle (`ref.fused_wnn_ref`);
    backend="packed" — the bitplane Pallas kernel (`packed_wnn`): word
                       select scan over E/32 uint32 words +
                       shift/AND bit extract; interpret mode off-TPU.
                       int8 tables are packed at trace time (a tests/
                       bench convenience — serving packs once, see
                       `repro.packed`);
    backend="auto"   — packed when the tables arrive packed (off-TPU via
                       the packed-domain XLA oracle `ref.packed_wnn_ref`,
                       the fast CPU formulation that still never unpacks);
                       otherwise fused on TPU, gather elsewhere.

    All backends are exactly score-equal by contract
    (tests/test_fused_adoption.py + tests/test_packed.py enforce int32
    equality).

    Inside a `use_mesh` context the submodel runs per shard under
    `shard_map` — rows over the batch axes, classes over theirs — since
    GSPMD cannot partition a Mosaic kernel; each shard scores its own
    class columns (DESIGN §7) and there is no collective. This is the one
    way every caller reaches the kernels, so no caller can miss it. The
    mesh decision is made here, outside the jitted `_local_scores`, whose
    trace cache is keyed on avals only and so must never capture the
    thread-local `use_mesh` context.
    """
    score = functools.partial(_local_scores, backend=backend,
                              entries=entries)
    ctx = sh.current_context()
    if ctx is None:
        return score(tuples, params, table, mask, bias)
    mesh, rules = ctx
    rows, classes = rules.resolve(("batch", "classes"), mesh,
                                  shape=(tuples.shape[0], table.shape[0]))
    return sh.shard_map(
        score, mesh,
        in_specs=(P(rows, None, None), P(), P(classes, None, None),
                  P(classes, None), P(classes)),
        out_specs=P(rows, classes))(tuples, params, table, mask, bias)


@functools.partial(jax.jit, static_argnames=("backend", "entries"))
def _local_scores(tuples, params, table, mask, bias, *, backend: str,
                  entries: int | None):
    """`wnn_scores` on the arrays as given, with no mesh."""
    packed_in = table.dtype == jnp.uint32
    validate_wnn_geometry(tuples, params, table, mask, bias, entries=entries)
    resolved = resolve_wnn_backend(backend, packed_tables=packed_in)
    if resolved == "packed":
        words = table if packed_in else packed_layout.pack_words(
            table.astype(jnp.uint32))
        if _on_tpu():
            return packed_wnn(tuples, params, words, mask, bias)
        if backend == "packed":   # explicit: bit-for-bit kernel body
            return packed_wnn(tuples, params, words, mask, bias,
                              interpret=True)
        return ref.packed_wnn_ref(tuples, params, words, mask, bias)
    if packed_in:
        raise ValueError(
            f"backend={resolved!r} needs unpacked (M, N_f, E) int8 tables "
            "but got uint32 bitplanes — use backend='packed'/'auto', or "
            "down-convert explicitly via repro.packed.layout.unpack_words")
    if resolved == "fused":
        return fused_wnn(tuples, params, table, mask, bias,
                         interpret=not _on_tpu())
    return ref.fused_wnn_ref(tuples, params, table, mask, bias)


def validate_tenant_geometry(bits, tids, perms, params, words, mask, *,
                             entries: int) -> None:
    """Trace-time validation for the tenant-indexed packed entry: every
    per-tenant leaf must carry the same leading T, tenant 0's slice must
    be a legal packed geometry, and the batch/tid shapes must agree."""
    if bits.ndim != 2:
        raise ValueError(f"bits must be (B, total_bits), got {bits.shape}")
    if tids.ndim != 1 or tids.shape[0] != bits.shape[0]:
        raise ValueError(
            f"tids must be (B,)=({bits.shape[0]},), got {tids.shape}")
    if not jnp.issubdtype(tids.dtype, jnp.integer):
        raise ValueError(f"tids must be integer, got {tids.dtype}")
    if words.ndim != 4:
        raise ValueError(
            f"stacked words must be (T, M, N_f, W), got {words.shape}")
    t = words.shape[0]
    for name, leaf, nd in (("perms", perms, 3), ("params", params, 3),
                           ("mask", mask, 3)):
        if leaf.ndim != nd or leaf.shape[0] != t:
            raise ValueError(
                f"stacked {name} must have leading T={t} and {nd} dims, "
                f"got {leaf.shape}")
    # tenant 0's slice must be a legal single-tenant geometry; T-uniform
    # ndarray slices make one check cover every tenant
    n_f, n = perms.shape[1], perms.shape[2]
    sds = jax.ShapeDtypeStruct
    validate_wnn_geometry(
        sds((bits.shape[0], n_f, n), jnp.int8),
        sds(params.shape[1:], jnp.int32), sds(words.shape[1:], words.dtype),
        sds(mask.shape[1:], mask.dtype), sds((words.shape[1],), jnp.int32),
        entries=entries)


@functools.partial(jax.jit, static_argnames=("backend", "entries"))
def wnn_scores_tenant(bits, tids, perms, params, words, mask, *,
                      backend: str = "auto", entries: int = 0):
    """One submodel's tenant-indexed scores (B, M) int32 (DESIGN §11).

    bits: (B, total_bits) {0,1}; tids: (B,) int32 tenant index per row;
    perms: (T, N_f, n) int32; params: (T, k, n) int32; words:
    (T, M, N_f, W) uint32 bitplanes; mask: (T, M, N_f) int8. Returns the
    partial scores WITHOUT bias (the accumulator adds the per-tenant
    bias, mirroring how `packed.packed_scores` owns its constraints).

    Packed-domain only: backend must be "packed" or "auto" — the int8
    backends would need T copies of the 32× expansion this runtime
    exists to avoid. Both resolve to the row-gather XLA formulation
    (`ref.packed_wnn_tenant_ref`) on every platform: the gathers are
    already the memory-bound optimum and a dedicated Mosaic tenant
    kernel is future work (the vmem-budget rule still covers the
    per-tenant geometry each row exercises).

    Like `wnn_scores`, this is an inner `jax.jit` keyed on avals only —
    it must never capture the thread-local `use_mesh` context; sharding
    constraints and manual collectives live in the (uncached) callers.
    """
    if backend not in ("packed", "auto"):
        raise ValueError(
            f"wnn_scores_tenant serves the packed domain only (backend="
            f"'packed'|'auto', got {backend!r}); stacked fleets never "
            "materialize int8 tables")
    validate_tenant_geometry(bits, tids, perms, params, words, mask,
                             entries=entries)
    return ref.packed_wnn_tenant_ref(bits, tids, perms, params, words, mask)


def ensemble_predict(scores):
    """Gathered (B, M) score matrix + argmax predictions (B,) int32.

    The tail of the class-sharded dataflow (DESIGN §7): partial score
    columns live sharded as ("batch", "classes"); the argmax needs every
    class, so the matrix is first constrained to ("batch", None) — under
    GSPMD that lowers to ONE all-gather of B×M×4 bytes, the only
    cross-device traffic in the whole serve step (the tables never move).
    Outside a mesh context both steps are local no-ops.
    """
    scores = sh.logical_constraint(scores, ("batch", None))
    return scores, jnp.argmax(scores, axis=-1).astype(jnp.int32)


def wnn_infer(tuples, params, table, mask, bias, *, use_kernel: bool = False):
    """Fused WNN inference scores (B, M) int32 (one submodel).

    Legacy wrapper over `wnn_scores`: use_kernel=True forces the fused
    backend; otherwise the platform default ("auto") applies.
    """
    return wnn_scores(tuples, params, table, mask, bias,
                      backend="fused" if use_kernel else "auto")


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def h3_hash(tuples, params, *, use_kernel: bool = False):
    if use_kernel or _on_tpu():
        return h3_hash_tiled(tuples, params, interpret=not _on_tpu())
    return ref.h3_hash_ref(tuples, params)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def thermometer(x, thresholds, *, use_kernel: bool = False):
    if use_kernel or _on_tpu():
        return thermometer_encode(x, thresholds, interpret=not _on_tpu())
    return ref.thermometer_ref(x, thresholds)


@functools.partial(jax.jit, static_argnames=("bits", "use_kernel"))
def decompress(counts, bits: int, *, use_kernel: bool = False):
    if use_kernel or _on_tpu():
        return thermometer_decompress(counts, bits, interpret=not _on_tpu())
    return ref.decompress_ref(counts, bits)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "use_kernel"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    use_kernel: bool = False):
    """q: (B, H, Sq, D); k, v: (B, Hkv, Sk, D), GQA via head repetition."""
    b, h, sq, d = q.shape
    hkv = k.shape[1]
    if hkv != h:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, -1, d)
    vf = v.reshape(b * h, -1, d)
    if use_kernel or _on_tpu():
        out = flash_attention_tiled(qf, kf, vf, causal=causal, window=window,
                                    interpret=not _on_tpu())
    else:
        out = ref.attention_ref(qf, kf, vf, causal=causal, window=window)
    return out.reshape(b, h, sq, d)
