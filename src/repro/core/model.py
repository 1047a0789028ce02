"""The ULEEN model: an additive ensemble of Bloom-filter WiSARD submodels.

Specs are static (hashable) config; `SubmodelStatic` holds the frozen random
structures (input permutation + H3 parameters); `UleenParams` holds the
learnable state (continuous tables + per-class bias + pruning masks) and is a
pytree, so it flows through jit/pjit/grad untouched.

Shapes use the paper's names: M classes, L submodels, N_f filters per
discriminator, n inputs per filter, E entries per filter, k hash functions.
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import bloom
from repro.core.hashing import h3_hash, make_h3_params, murmur_double_hash


@dataclasses.dataclass(frozen=True)
class SubmodelSpec:
    inputs_per_filter: int          # n
    log2_entries: int               # E = 2**log2_entries
    num_hashes: int = 2             # k (paper: 2 everywhere)

    @property
    def entries(self) -> int:
        return 2 ** self.log2_entries


@dataclasses.dataclass(frozen=True)
class UleenSpec:
    num_classes: int                # M
    total_bits: int                 # encoded input width (F * T)
    submodels: tuple[SubmodelSpec, ...]
    bits_per_input: int = 1         # T (bookkeeping for size/IO accounting)
    dropout: float = 0.5
    # One dropout mask per (sample, filter-index), shared across the M
    # class discriminators, instead of per (sample, class, filter). The
    # paper's reading is per-class (default False = faithful); sharing
    # cuts the training step's RNG traffic ~M× — the dominant HBM term of
    # the fleet-scale cell (EXPERIMENTS §Perf it.5).
    dropout_shared_classes: bool = False
    # Gather/score in bf16 (f32 Adam masters untouched; scores accumulate
    # in f32). {0,1} responses and the [-1,1]-table sign test are exact in
    # bf16; halves the gather+response HBM traffic (§Perf it.5b).
    bf16_tables: bool = False

    def num_filters(self, sm: SubmodelSpec) -> int:
        return math.ceil(self.total_bits / sm.inputs_per_filter)

    def size_kib(self, masks: Optional[Sequence[jnp.ndarray]] = None) -> float:
        """Inference model size: surviving filters x entries, 1 bit each."""
        total_bits = 0.0
        for i, sm in enumerate(self.submodels):
            n_f = self.num_filters(sm)
            if masks is not None:
                surviving = float(jnp.sum(masks[i]))
            else:
                surviving = self.num_classes * n_f
            total_bits += surviving * sm.entries
        return total_bits / 8.0 / 1024.0


class SubmodelStatic(NamedTuple):
    perm: jnp.ndarray   # (N_f, n) int32 indices into [0, total_bits)
    h3: jnp.ndarray     # (k, n) uint32 hash parameters (shared across classes)


class UleenParams(NamedTuple):
    tables: tuple[jnp.ndarray, ...]  # each (M, N_f, E) float32 (continuous)
    bias: jnp.ndarray                # (M,) float32
    masks: tuple[jnp.ndarray, ...]   # each (M, N_f) float32 in {0,1}


def init_static(key: jax.Array, spec: UleenSpec) -> list[SubmodelStatic]:
    """Frozen random structures: input reordering + H3 parameters."""
    statics = []
    for sm in spec.submodels:
        key, k_perm, k_pad, k_h3 = jax.random.split(key, 4)
        n_f = spec.num_filters(sm)
        flat = n_f * sm.inputs_per_filter
        perm = jax.random.permutation(k_perm, spec.total_bits)
        if flat > spec.total_bits:  # pad by re-sampling (classic WiSARD wrap)
            extra = jax.random.randint(k_pad, (flat - spec.total_bits,), 0,
                                       spec.total_bits)
            perm = jnp.concatenate([perm, extra])
        perm = perm[:flat].reshape(n_f, sm.inputs_per_filter).astype(jnp.int32)
        h3 = make_h3_params(k_h3, sm.num_hashes, sm.inputs_per_filter,
                            sm.log2_entries)
        statics.append(SubmodelStatic(perm=perm, h3=h3))
    return statics


def init_params(key: jax.Array, spec: UleenSpec,
                init_scale: float = 1.0) -> UleenParams:
    """Tables start as *nearly empty* Bloom filters: U(-init_scale,
    0.1*init_scale), i.e. ~91% of entries negative. A symmetric U(-s, s)
    init leaves every entry the training data never touches with a random
    sign, so unseen (validation) patterns hash into untouched entries and
    fire filters spuriously with p=1/4 — a noise floor the one-shot
    counting tables (which start at 0 = "not seen") never pay. The small
    positive tail keeps a few initial responses alive so dropout/gradient
    signal exists from step one. init_scale only sets the range; STE
    dynamics are identical up to a time rescale (an entry flips after
    ~|init|/lr consistent gradient steps), so small-scale CPU runs use 0.1
    (DESIGN §9)."""
    tables = []
    masks = []
    for sm in spec.submodels:
        key, sub = jax.random.split(key)
        n_f = spec.num_filters(sm)
        tables.append(jax.random.uniform(
            sub, (spec.num_classes, n_f, sm.entries), jnp.float32,
            -init_scale, 0.1 * init_scale))
        masks.append(jnp.ones((spec.num_classes, n_f), jnp.float32))
    return UleenParams(tables=tuple(tables), bias=jnp.zeros(spec.num_classes),
                       masks=tuple(masks))


def compute_hashes(spec: UleenSpec, statics: Sequence[SubmodelStatic],
                   bits: jnp.ndarray, *, hash_family: str = "h3"
                   ) -> tuple[jnp.ndarray, ...]:
    """bits: (B, total_bits) bool -> per-submodel hashes (B, N_f, k) int32.

    Hashes depend only on the input, never on learnable state: compute once
    per batch, outside the gradient tape (paper: single-layer model, no
    gradient through indexing).
    """
    out = []
    for sm, st in zip(spec.submodels, statics):
        tuples = bits[:, st.perm]                 # (B, N_f, n)
        if hash_family == "h3":
            out.append(h3_hash(tuples, st.h3))
        elif hash_family == "murmur":             # Bloom WiSARD baseline
            out.append(murmur_double_hash(tuples, sm.num_hashes, sm.entries))
        elif hash_family == "identity":
            # true RAM node (classic WiSARD): the n-bit tuple IS the
            # address; requires entries == 2**n and k == 1.
            weights = (jnp.int32(1) << jnp.arange(sm.inputs_per_filter,
                                                  dtype=jnp.int32))
            addr = jnp.sum(tuples.astype(jnp.int32) * weights, axis=-1)
            out.append((addr % sm.entries)[..., None])
        else:
            raise ValueError(hash_family)
    return tuple(out)


def forward(spec: UleenSpec, params: UleenParams,
            hashes: Sequence[jnp.ndarray], *, train: bool = False,
            rng: Optional[jax.Array] = None) -> jnp.ndarray:
    """Ensemble scores (B, M): sum of discriminator responses + bias.

    Train mode binarises continuous tables with STE and applies dropout to
    filter outputs (p = spec.dropout), exactly the paper's recipe.
    """
    b = hashes[0].shape[0]
    scores = jnp.zeros((b, spec.num_classes), jnp.float32)
    for i, (table, mask) in enumerate(zip(params.tables, params.masks)):
        if spec.bf16_tables:
            table = table.astype(jnp.bfloat16)
        resp = bloom.continuous_filter_response(table, hashes[i])  # (B, M, N_f)
        # Masks are structural (pruning), never trained — apply_mask's
        # nonzero-keep test carries no gradient path to the mask.
        resp = bloom.apply_mask(resp, mask)
        if train and spec.dropout > 0.0:
            assert rng is not None, "train=True requires a dropout rng"
            rng, sub = jax.random.split(rng)
            mshape = (resp.shape[0], 1, resp.shape[2]) \
                if spec.dropout_shared_classes else resp.shape
            keep = jax.random.bernoulli(sub, 1.0 - spec.dropout, mshape)
            resp = resp * keep / (1.0 - spec.dropout)
        # accumulate in f32: a bf16 popcount over >256 filters would lose
        # integer precision (8-bit mantissa)
        scores = scores + jnp.sum(resp, axis=-1, dtype=jnp.float32)
    return scores + params.bias[None, :]


def forward_binary(spec: UleenSpec, tables_bin: Sequence[jnp.ndarray],
                   masks: Sequence[jnp.ndarray], bias: jnp.ndarray,
                   hashes: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Deployment inference: binary tables, AND-reduce, popcount, bias.

    The gather formulation — precomputed hashes indexing the tables via
    `take_along_axis`. This is the autodiff-shaped reference the fused
    Pallas path (`forward_binary_fused`) must stay bit-identical to.
    """
    b = hashes[0].shape[0]
    scores = jnp.zeros((b, len(bias)), jnp.int32)
    for i, table in enumerate(tables_bin):
        resp = bloom.binary_filter_response(table, hashes[i])
        resp = bloom.apply_mask(resp, masks[i])
        scores = scores + jnp.sum(resp, axis=-1, dtype=jnp.int32)
    return scores + jnp.round(bias).astype(jnp.int32)[None, :]


def forward_binary_fused(spec: UleenSpec, statics: Sequence[SubmodelStatic],
                         tables_bin: Sequence[jnp.ndarray],
                         masks: Sequence[jnp.ndarray], bias: jnp.ndarray,
                         bits: jnp.ndarray, *,
                         backend: str = "auto") -> jnp.ndarray:
    """Deployment inference straight from encoded input bits (B, total_bits).

    One `kernels.ops.wnn_scores` dispatch per submodel on the raw
    thermometer tuples — subsuming `compute_hashes` +
    `bloom.binary_filter_response` + mask/bias application. With
    `backend="fused"` each submodel is ONE Pallas kernel launch
    (hash → select-scan lookup → AND → popcount), the paper's whole
    accelerator pipeline; `"gather"` runs the jnp oracle on the same
    tuples and is bit-identical; `"packed"` runs the uint32 bitplane
    kernel (the int8 tables are packed at trace time — steady-state
    serving should pack once via `binarize_to_packed` /
    `repro.packed.packed_scores` instead); `"auto"` picks per platform
    (DESIGN §2 "Adoption" + "Packed layout").

    Only the H3 hash family is fused (the paper's central hash block).
    Models hashed with `murmur`/`identity` must go through
    `compute_hashes` + `forward_binary`.
    """
    from repro.kernels import ops  # late import: core must not import pallas
    b = bits.shape[0]
    scores = jnp.zeros((b, len(bias)), jnp.int32)
    for st, table, mask in zip(statics, tables_bin, masks):
        tuples = bits[:, st.perm].astype(jnp.int8)          # (B, N_f, n)
        scores = scores + ops.wnn_scores(
            tuples, st.h3.astype(jnp.int32), table.astype(jnp.int8),
            (mask != 0).astype(jnp.int8),
            jnp.zeros((len(bias),), jnp.int32), backend=backend)
    return scores + jnp.round(bias).astype(jnp.int32)[None, :]


def predict(scores: jnp.ndarray) -> jnp.ndarray:
    return jnp.argmax(scores, axis=-1)


def binarize_params(params: UleenParams) -> tuple[tuple[jnp.ndarray, ...],
                                                  tuple[jnp.ndarray, ...],
                                                  jnp.ndarray]:
    """Continuous training state -> deployable binary model."""
    tables_bin = tuple(bloom.binarize_continuous(t) for t in params.tables)
    return tables_bin, params.masks, params.bias


def binarize_to_packed(spec: UleenSpec, statics: Sequence[SubmodelStatic],
                       params: UleenParams):
    """Continuous training state -> `repro.packed.PackedTables`.

    The export-time pack (the one place int8/bool tables legitimately
    materialize); serve through `repro.packed.packed_scores`, which keeps
    the uint32 bitplanes native end-to-end (DESIGN §2 "Packed layout").
    """
    from repro import packed  # late import: core must not import pallas
    tables_bin, masks, bias = binarize_params(params)
    return packed.from_binary_model(
        statics, tables_bin, masks, bias,
        entries=[sm.entries for sm in spec.submodels],
        num_classes=spec.num_classes)
