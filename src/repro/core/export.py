"""Export a trained ULEEN model to a deployable inference artifact.

Binary tables are bit-packed (32 entries per uint32 word), pruned filters are
dropped per-discriminator (ragged layout, stored with per-class filter index
lists exactly like the RTL generator consumes), and model size is accounted
the way the paper reports it (surviving filters x entries bits).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.model import SubmodelStatic, UleenParams, UleenSpec, binarize_params
from repro.obs import registry as obs_registry


@dataclasses.dataclass
class SubmodelArtifact:
    packed: np.ndarray          # (M, N_f, E//32) uint32 bit-packed table
    mask: np.ndarray            # (M, N_f) bool survival mask
    perm: np.ndarray            # (N_f, n) int32
    h3: np.ndarray              # (k, n) uint32
    entries: int
    inputs_per_filter: int
    num_hashes: int


@dataclasses.dataclass
class InferenceArtifact:
    submodels: list
    bias: np.ndarray            # (M,) int32
    num_classes: int
    total_bits: int
    bits_per_input: int

    @property
    def size_kib(self) -> float:
        bits = sum(int(sm.mask.sum()) * sm.entries for sm in self.submodels)
        return bits / 8.0 / 1024.0

    @property
    def packed_size_kib(self) -> float:
        """Surviving-table storage in the word-aligned packed layout —
        what the accelerator (and the packed serve path) actually holds:
        4 bytes per uint32 word, E < 32 rounded up to one word."""
        by = sum(int(sm.mask.sum()) * sm.packed.shape[-1] * 4
                 for sm in self.submodels)
        return by / 1024.0

    @property
    def hash_ops_per_inference(self) -> int:
        """Hash computations: one per filter per hash fn per submodel
        (shared across discriminators — the paper's central hash block)."""
        return sum(sm.perm.shape[0] * sm.num_hashes for sm in self.submodels)

    @property
    def lookups_per_inference(self) -> int:
        return sum(int(sm.mask.sum()) * sm.num_hashes for sm in self.submodels)


def pack_table(table_bin: np.ndarray) -> np.ndarray:
    """(M, N_f, E) bool -> (M, N_f, E//32) uint32."""
    m, n_f, e = table_bin.shape
    assert e % 32 == 0 or e < 32
    pad = (-e) % 32
    if pad:
        table_bin = np.concatenate(
            [table_bin, np.zeros((m, n_f, pad), bool)], axis=-1)
    words = table_bin.reshape(m, n_f, -1, 32).astype(np.uint32)
    weights = (np.uint32(1) << np.arange(32, dtype=np.uint32))
    return (words * weights).sum(axis=-1, dtype=np.uint64).astype(np.uint32)


def unpack_table(packed: np.ndarray, entries: int) -> np.ndarray:
    m, n_f, w = packed.shape
    bits = (packed[..., :, None] >> np.arange(32, dtype=np.uint32)) & 1
    return bits.reshape(m, n_f, w * 32)[..., :entries].astype(bool)


def export_model(spec: UleenSpec, statics: Sequence[SubmodelStatic],
                 params: UleenParams) -> InferenceArtifact:
    tables_bin, masks, bias = binarize_params(params)
    subs = []
    for sm, st, tb, mask in zip(spec.submodels, statics, tables_bin, masks):
        subs.append(SubmodelArtifact(
            packed=pack_table(np.asarray(tb)),
            mask=np.asarray(mask) > 0,
            perm=np.asarray(st.perm),
            h3=np.asarray(st.h3),
            entries=sm.entries,
            inputs_per_filter=sm.inputs_per_filter,
            num_hashes=sm.num_hashes,
        ))
    return InferenceArtifact(submodels=subs,
                             bias=np.asarray(jnp.round(bias), np.int32),
                             num_classes=spec.num_classes,
                             total_bits=spec.total_bits,
                             bits_per_input=spec.bits_per_input)


class UnpackedTables(NamedTuple):
    """Device-resident 32× expansion of an artifact for the int8 backends
    (fused/gather). Built once by `prepare_artifact`, never inside a
    traced function."""
    tables: tuple    # per submodel (M, N_f, E) int8
    masks: tuple     # (M, N_f) int8
    perms: tuple     # (N_f, n) int32
    h3s: tuple       # (k, n) int32
    bias: jnp.ndarray  # (M,) int32


def prep_shardings(prep, mesh, rules=None):
    """NamedSharding pytree partitioning prepared tables over `mesh` by
    class (DESIGN §7) — works for both serve representations.

    Per-class leaves (tables/words, masks, bias) carry the "classes"
    logical axis on M; the shared perm/H3 structures replicate. The
    resolver's divisibility sanitizer degrades the whole prep to
    replication together when M does not divide the mesh axis.
    """
    from repro.dist import sharding as sh
    rules = rules if rules is not None else sh.SERVE_RULES
    if not isinstance(prep, UnpackedTables):
        return prep.class_shardings(mesh, rules)   # PackedTables

    def ns(log, x):
        return sh.named_sharding(mesh, rules, log, shape=tuple(x.shape))

    return UnpackedTables(
        tables=tuple(ns(("classes", None, None), t) for t in prep.tables),
        masks=tuple(ns(("classes", None), m) for m in prep.masks),
        perms=tuple(ns((None, None), p) for p in prep.perms),
        h3s=tuple(ns((None, None), h) for h in prep.h3s),
        bias=ns(("classes",), prep.bias))


def prep_class_slice(prep, lo: int, hi: int):
    """The per-class shard [lo, hi) of a prepared-table object — what one
    device holds under the `classes` partition (manual-sharding oracle of
    the differential battery; `PackedTables.class_slice` for the packed
    representation)."""
    if not isinstance(prep, UnpackedTables):
        return prep.class_slice(lo, hi)
    if not 0 <= lo < hi <= prep.bias.shape[0]:
        raise ValueError(
            f"class range [{lo}, {hi}) outside [0, {prep.bias.shape[0]})")
    return UnpackedTables(
        tables=tuple(t[lo:hi] for t in prep.tables),
        masks=tuple(m[lo:hi] for m in prep.masks),
        perms=prep.perms, h3s=prep.h3s, bias=prep.bias[lo:hi])


# one prepared object per REPRESENTATION: PackedTables serves both
# packed-domain backends, one UnpackedTables serves both int8 ones
_SAME_REPRESENTATION = {"auto": "packed", "packed": "auto",
                        "fused": "gather", "gather": "fused"}


def _build_prep(artifact: InferenceArtifact, backend: str):
    """The (uncached) representation build behind `prepare_artifact`."""
    if backend in ("auto", "packed"):
        from repro import packed
        return packed.from_artifact(artifact)
    return UnpackedTables(
        tables=tuple(jnp.asarray(unpack_table(sm.packed, sm.entries),
                                 jnp.int8) for sm in artifact.submodels),
        masks=tuple(jnp.asarray(sm.mask).astype(jnp.int8)
                    for sm in artifact.submodels),
        perms=tuple(jnp.asarray(sm.perm, jnp.int32)
                    for sm in artifact.submodels),
        h3s=tuple(jnp.asarray(sm.h3).astype(jnp.int32)
                  for sm in artifact.submodels),
        bias=jnp.asarray(artifact.bias, jnp.int32))


def prepare_artifact(artifact: InferenceArtifact, *, backend: str = "auto",
                     mesh=None, rules=None):
    """Hoisted, cached table preparation for repeated serving.

    backend="packed"/"auto" lifts the artifact's uint32 word planes into a
    `repro.packed.PackedTables` verbatim (no expansion at all);
    "fused"/"gather" unpack to int8 device tables exactly ONCE. The result
    is memoized on the artifact instance per backend, so the traced serve
    path (`artifact_scores`, `launch.scheduler.WnnBatcher`) never redoes
    the 32× expansion — or any table work — per batch.

    With `mesh` the prepared tables are additionally device_put class-
    sharded over it (`prep_shardings`, DESIGN §7): every per-class leaf
    lands partitioned on M over the mesh's `model` axis, so each device
    holds M/degree discriminators' tables — the placement the sharded
    serve path (`WnnBatcher(mesh=...)`) runs against. Memoized per
    (representation, mesh, rules-content); a replicated prep already in
    the cache seeds the sharded placement, but a sharded-only server
    never pins a replicated device copy of its own.
    """
    from repro.kernels import ops  # late import: export is also numpy-only IO
    ops.resolve_wnn_backend(backend)     # reject unknown names eagerly
    rec = obs_registry.get_recorder()
    cache = getattr(artifact, "_prepared", None)
    if cache is None:
        cache = artifact._prepared = {}
    if mesh is not None:
        import jax
        from repro.dist import sharding as sh
        rules = rules if rules is not None else sh.SERVE_RULES
        # key on the REPRESENTATION (like the unsharded branch: one
        # sharded copy serves both same-representation backends) and on
        # the rules' content, not object identity
        rules_key = tuple(sorted(
            (k, tuple(v)) for k, v in rules.rules.items()))
        key = ("packed" if backend in ("auto", "packed") else "int8",
               mesh, rules_key)
        if key in cache:
            rec.counter("prep.cache_hit").inc()
            return cache[key]
        rec.counter("prep.cache_miss").inc()
        with rec.span("prep.build", backend=backend, sharded=True):
            base = cache.get(backend)
            if base is None:
                base = cache.get(_SAME_REPRESENTATION[backend])
            if base is None:
                base = _build_prep(artifact, backend)  # NOT cached: don't
                #                             pin a replicated copy too
            prep = jax.device_put(base, prep_shardings(base, mesh, rules))
        cache[key] = prep
        return prep
    if backend in cache:
        rec.counter("prep.cache_hit").inc()
        return cache[backend]
    prep = cache.get(_SAME_REPRESENTATION[backend])
    if prep is None:
        rec.counter("prep.cache_miss").inc()
        with rec.span("prep.build", backend=backend, sharded=False):
            prep = _build_prep(artifact, backend)
    else:
        # same-representation reuse: no build, but record the alias fill
        rec.counter("prep.cache_hit").inc()
    cache[backend] = prep
    return prep


def prepare_tenants(artifacts, *, backend: str = "auto",
                    mesh=None, rules=None):
    """Hoisted, cached multi-artifact prep: one `StackedPackedTables`
    fleet over N same-geometry artifacts (DESIGN §11).

    Packed-domain only (backend "packed"/"auto") — an int8 fleet would
    multiply the 32× expansion by T, exactly what the packed runtime
    exists to avoid. Each artifact's single-tenant prep goes through the
    `prepare_artifact` cache first (so a tenant already served solo costs
    nothing to re-prepare), then the slices stack with trace-time
    geometry validation (`packed.stack_tenants`).

    Memoization mirrors `prepare_artifact`'s per-(backend, mesh) scheme,
    keyed on the *first* artifact's `_prepared` dict with the identity
    tuple of the whole fleet (same artifact objects in the same order ->
    cache hit; the cached value holds a strong reference to the artifact
    tuple so the ids stay valid). With `mesh` the stacked leaves are
    device_put partitioned over it by tenant (`tenant_shardings` — every
    model shard holds T/degree whole tenants; replication fallback when
    T does not divide the axis).
    """
    from repro import packed
    from repro.kernels import ops
    ops.resolve_wnn_backend(backend)
    if backend not in ("auto", "packed"):
        raise ValueError(
            f"prepare_tenants serves the packed domain only (backend="
            f"'packed'|'auto', got {backend!r})")
    artifacts = tuple(artifacts)
    if not artifacts:
        raise ValueError("prepare_tenants needs at least one artifact")
    cache = getattr(artifacts[0], "_prepared", None)
    if cache is None:
        cache = artifacts[0]._prepared = {}
    ids = tuple(id(a) for a in artifacts)
    if mesh is not None:
        from repro.dist import sharding as sh
        rules = rules if rules is not None else sh.SERVE_RULES
        rules_key = tuple(sorted(
            (k, tuple(v)) for k, v in rules.rules.items()))
        key = ("tenants", ids, mesh, rules_key)
    else:
        key = ("tenants", ids)
    rec = obs_registry.get_recorder()
    hit = cache.get(key)
    if hit is not None:
        rec.counter("prep.cache_hit").inc()
        return hit[0]
    rec.counter("prep.cache_miss").inc()
    with rec.span("prep.stack_tenants", tenants=len(artifacts),
                  sharded=mesh is not None):
        stacked = packed.stack_tenants(
            prepare_artifact(a, backend=backend) for a in artifacts)
        if mesh is not None:
            import jax
            stacked = jax.device_put(
                stacked, stacked.tenant_shardings(mesh, rules))
    cache[key] = (stacked, artifacts)   # pin the ids the key ranges over
    return stacked


def scores_from_prep(prep, bits: jnp.ndarray, *,
                     backend: str = "auto") -> jnp.ndarray:
    """Backend-dispatched scores from prepared tables (jit-traceable).

    THE serve loop — `artifact_scores` and the serve engine's batch path
    (`launch.scheduler.WnnBatcher`) both route through here, so the
    per-submodel dispatch/mask/bias semantics cannot drift between them.
    """
    if not isinstance(prep, UnpackedTables):
        from repro.packed import runtime
        return runtime.packed_scores(prep, bits, backend=backend)
    from repro.dist import sharding as sh
    from repro.kernels import ops
    m = prep.bias.shape[0]
    scores = jnp.zeros((bits.shape[0], m), jnp.int32)
    zero_bias = jnp.zeros((m,), jnp.int32)
    for i, (table, mask, perm, h3) in enumerate(zip(
            prep.tables, prep.masks, prep.perms, prep.h3s)):
        tuples = bits[:, perm].astype(jnp.int8)
        # inside a mesh each partial is ("batch", "classes")-sharded
        # by wnn_scores' shard_map
        with jax.named_scope(f"submodel{i}"):
            scores = scores + ops.wnn_scores(tuples, h3, table, mask,
                                             zero_bias, backend=backend)
    # pin the bias add too: bias is class-sharded, and an unconstrained
    # `scores + bias` lets GSPMD hoist the gather above the add — two
    # all-gathers instead of the dataflow's promised one
    return sh.logical_constraint(scores + prep.bias[None],
                                 ("batch", "classes"))


def predict_from_prep(prep, bits: jnp.ndarray, *,
                      backend: str = "auto"):
    """(gathered scores (B, M), argmax predictions (B,)) from prepared
    tables — the class-sharded dataflow's tail for either representation:
    per-shard partial score columns, then ONE all-gather of the (B, M)
    matrix, then argmax over the full class axis (DESIGN §7)."""
    from repro.kernels import ops
    return ops.ensemble_predict(
        scores_from_prep(prep, bits, backend=backend))


def artifact_scores(artifact: InferenceArtifact, bits: jnp.ndarray, *,
                    backend: str = "auto") -> jnp.ndarray:
    """Serve encoded inputs straight from the deployable artifact.

    bits: (B, total_bits) bool/int {0,1} -> scores (B, M) int32, through
    the backend-dispatched WNN pipeline (`kernels.ops.wnn_scores`), one
    dispatch per submodel on tuples sliced via the stored permutation.

    backend="packed"/"auto" serves the artifact's native uint32 bitplanes
    (DESIGN §2 "Packed layout") — the traced path contains no int8 table
    and no unpack; "fused"/"gather" serve the int8 expansion, prepared
    once and cached by `prepare_artifact`, never re-unpacked per call.

    Bit-identical to `model.forward_binary` on the pre-export params —
    the golden fixtures in tests/test_fused_adoption.py and
    tests/test_packed.py pin every backend.
    """
    prep = prepare_artifact(artifact, backend=backend)
    return scores_from_prep(prep, jnp.asarray(bits), backend=backend)


def save(artifact: InferenceArtifact, path: str) -> None:
    arrs = {"bias": artifact.bias,
            "meta": np.array([artifact.num_classes, artifact.total_bits,
                              artifact.bits_per_input, len(artifact.submodels)])}
    for i, sm in enumerate(artifact.submodels):
        arrs[f"sm{i}_packed"] = sm.packed
        arrs[f"sm{i}_mask"] = sm.mask
        arrs[f"sm{i}_perm"] = sm.perm
        arrs[f"sm{i}_h3"] = sm.h3
        arrs[f"sm{i}_cfg"] = np.array([sm.entries, sm.inputs_per_filter,
                                       sm.num_hashes])
    np.savez_compressed(path, **arrs)


def load(path: str) -> InferenceArtifact:
    z = np.load(path)
    m, total_bits, bpi, n_sub = z["meta"]
    subs = []
    for i in range(int(n_sub)):
        e, n, k = z[f"sm{i}_cfg"]
        subs.append(SubmodelArtifact(
            packed=z[f"sm{i}_packed"], mask=z[f"sm{i}_mask"],
            perm=z[f"sm{i}_perm"], h3=z[f"sm{i}_h3"],
            entries=int(e), inputs_per_filter=int(n), num_hashes=int(k)))
    return InferenceArtifact(submodels=subs, bias=z["bias"],
                             num_classes=int(m), total_bits=int(total_bits),
                             bits_per_input=int(bpi))
