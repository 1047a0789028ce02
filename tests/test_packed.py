"""Packed-domain runtime battery (DESIGN §2 "Packed layout").

The contract: `backend="packed"` — uint32 bitplane tables end-to-end,
artifact to Pallas kernel — is **exactly int32 score-equal** to both
int8 formulations (`"fused"`, `"gather"`) on every geometry, including
the awkward ones (masks > 1, all-zero masks, batches that don't divide
block_b, E < 32 single-word planes), and the traced packed serve path
never materializes an int8 `(M, N_f, E)` table.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:          # minimal containers: seeded deterministic shim
    from _hypothesis_compat import given, settings
    from _hypothesis_compat import strategies as st

from test_fused_adoption import _random_binary_model

from repro.core import export
from repro.core.model import (SubmodelSpec, UleenSpec, binarize_to_packed,
                              compute_hashes, forward_binary,
                              forward_binary_fused)
from repro.kernels import ops, ref
from repro.kernels.packed_wnn import packed_wnn
from repro.packed import (PackedTables, from_artifact, pack_words,
                          packed_scores, unpack_words,
                          validate_packed_geometry, word_count)

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")


# ---------------------------------------------------------------------------
# Layout: pack/unpack round-trip + geometry validation
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=20)
@given(st.integers(1, 5),             # classes M
       st.integers(1, 9),             # filters N_f
       st.integers(3, 10))            # log2 entries -> E in 8..1024
def test_pack_unpack_roundtrip_jax(m, n_f, log2e):
    """JAX-side pack is the exact inverse of unpack AND bit-identical to
    the numpy export-time packer."""
    e = 2 ** log2e
    rng = np.random.default_rng(m * 1000 + n_f * 10 + log2e)
    table = (rng.random((m, n_f, e)) < 0.4)
    words = pack_words(jnp.asarray(table, jnp.uint32))
    assert words.shape == (m, n_f, word_count(e))
    assert words.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(words),
                                  export.pack_table(table))
    np.testing.assert_array_equal(np.asarray(unpack_words(words, e)),
                                  table.astype(np.int8))


def test_packed_geometry_rejected_at_trace_time():
    """Non-power-of-two entries / word counts and representation
    mismatches all fail loudly before any kernel runs."""
    b, n_f, n, m, k = 4, 6, 8, 3, 2
    tuples = jnp.zeros((b, n_f, n), jnp.int8)
    params = jnp.zeros((k, n), jnp.int32)
    mask = jnp.ones((m, n_f), jnp.int8)
    bias = jnp.zeros((m,), jnp.int32)
    words_ok = jnp.zeros((m, n_f, 4), jnp.uint32)        # E=128
    ops.wnn_scores(tuples, params, words_ok, mask, bias,
                   backend="packed", entries=128)        # ok
    # packed tables must declare entries
    with pytest.raises(ValueError, match="entries"):
        ops.wnn_scores(tuples, params, words_ok, mask, bias,
                       backend="packed")
    # non-power-of-two entries (H3 range closure)
    with pytest.raises(ValueError, match="power of two"):
        validate_packed_geometry(jnp.zeros((m, n_f, 3), jnp.uint32), 96)
    # word count that no legal pack can produce
    with pytest.raises(ValueError, match="word count"):
        ops.wnn_scores(tuples, params,
                       jnp.zeros((m, n_f, 3), jnp.uint32), mask, bias,
                       backend="packed", entries=128)
    # declared E disagreeing with an unpacked table
    with pytest.raises(ValueError, match="entries"):
        ops.wnn_scores(tuples, params, jnp.zeros((m, n_f, 64), jnp.int8),
                       mask, bias, backend="gather", entries=128)
    # int8 backends refuse bitplanes instead of silently unpacking
    with pytest.raises(ValueError, match="bitplanes"):
        ops.wnn_scores(tuples, params, words_ok, mask, bias,
                       backend="fused", entries=128)
    # resolution: auto prefers the packed domain for packed tables
    assert ops.resolve_wnn_backend("auto", packed_tables=True) == "packed"
    assert ops.resolve_wnn_backend("packed") == "packed"


def test_packed_tables_validate():
    words = (jnp.zeros((3, 5, 2), jnp.uint32),)
    masks = (jnp.ones((3, 5), jnp.int8),)
    perms = (jnp.zeros((5, 4), jnp.int32),)
    h3s = (jnp.zeros((2, 4), jnp.int32),)
    bias = jnp.zeros((3,), jnp.int32)
    pt = PackedTables(words=words, masks=masks, perms=perms, h3s=h3s,
                      bias=bias, entries=(64,), num_classes=3)
    pt.validate()                                        # ok
    assert pt.table_bytes() == 3 * 5 * 2 * 4
    bad = PackedTables(words=words, masks=(jnp.ones((3, 4), jnp.int8),),
                       perms=perms, h3s=h3s, bias=bias, entries=(64,),
                       num_classes=3)
    with pytest.raises(ValueError, match="mask"):
        bad.validate()
    with pytest.raises(ValueError, match="disagree"):
        PackedTables(words=words, masks=masks, perms=perms, h3s=h3s,
                     bias=bias, entries=(64, 32), num_classes=3)


def test_packed_tables_is_a_pytree():
    pt = PackedTables(words=(jnp.zeros((2, 3, 1), jnp.uint32),),
                      masks=(jnp.ones((2, 3), jnp.int8),),
                      perms=(jnp.zeros((3, 4), jnp.int32),),
                      h3s=(jnp.zeros((2, 4), jnp.int32),),
                      bias=jnp.zeros((2,), jnp.int32),
                      entries=(16,), num_classes=2)
    leaves, treedef = jax.tree.flatten(pt)
    assert len(leaves) == 5
    back = jax.tree.unflatten(treedef, leaves)
    assert back.entries == (16,) and back.num_classes == 2


# ---------------------------------------------------------------------------
# Parity: packed vs fused vs gather, exact int32 equality
# ---------------------------------------------------------------------------

def _assert_three_way(spec, statics, tables, masks, bias, bits):
    expect = forward_binary(spec, tables, masks, bias,
                            compute_hashes(spec, statics, bits))
    for backend in ("packed", "fused", "gather"):
        got = forward_binary_fused(spec, statics, tables, masks, bias,
                                   bits, backend=backend)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))
    # the packed-native path (no int8 tables anywhere near the trace)
    pt = binarize_to_packed(spec, statics,
                            _params_like(spec, tables, masks, bias))
    for backend in ("packed", "auto"):
        got = packed_scores(pt, bits, backend=backend)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


def _params_like(spec, tables, masks, bias):
    """Continuous params whose binarization reproduces the given binary
    model (entry >= 0 <-> bit set)."""
    from repro.core.model import UleenParams
    return UleenParams(
        tables=tuple(jnp.where(t, 0.5, -0.5) for t in tables),
        bias=jnp.asarray(bias, jnp.float32),
        masks=tuple(jnp.asarray(m, jnp.float32) for m in masks))


@settings(deadline=None, max_examples=20)
@given(st.integers(1, 33),            # batch (incl. 1 and non-pow2)
       st.integers(4, 20),            # inputs per filter n
       st.integers(3, 9),             # log2 entries -> E in 8..512
       st.integers(1, 4),             # hash functions k
       st.integers(2, 11),            # classes M
       st.integers(5, 40),            # filters N_f (non-MXU-aligned)
       st.sampled_from(["ones", "random", "zeros"]))
def test_packed_matches_fused_and_gather_randomized(b, n, log2e, k, m, n_f,
                                                    mask_kind):
    """Hypothesis sweep: exact int32 three-way parity across geometries,
    including E < 32 (single padded word) and all-zero pruning masks."""
    seed = b * 99991 + n * 1013 + log2e * 103 + k * 13 + m + n_f
    key = jax.random.PRNGKey(seed)
    spec = UleenSpec(num_classes=m, total_bits=n * n_f,
                     submodels=(SubmodelSpec(n, log2e, num_hashes=k),))
    key, k_model, k_bits = jax.random.split(key, 3)
    statics, tables, masks, bias = _random_binary_model(k_model, spec,
                                                        mask_kind)
    bits = jax.random.bernoulli(k_bits, 0.5, (b, spec.total_bits))
    _assert_three_way(spec, statics, tables, masks, bias, bits)


def test_packed_batch_not_dividing_block_b():
    """b=131 > block_b=128 forces a padded partial batch tile in the
    packed kernel."""
    spec = UleenSpec(num_classes=4, total_bits=120,
                     submodels=(SubmodelSpec(8, 5, num_hashes=2),))
    key = jax.random.PRNGKey(11)
    statics, tables, masks, bias = _random_binary_model(key, spec, "random")
    bits = jax.random.bernoulli(jax.random.PRNGKey(12), 0.5,
                                (131, spec.total_bits))
    _assert_three_way(spec, statics, tables, masks, bias, bits)


def test_packed_mask_values_above_one_are_survival_flags():
    """core/bloom.py::apply_mask semantics hold in the bitplane kernel and
    the packed oracle: mask magnitude never scales the response."""
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 4)
    b, n_f, n, m, e, k = 9, 13, 8, 5, 64, 2
    tuples = jax.random.bernoulli(ks[0], 0.5, (b, n_f, n)).astype(jnp.int8)
    params = jax.random.randint(ks[1], (k, n), 0, e, dtype=jnp.int32)
    table = jax.random.bernoulli(ks[2], 0.4, (m, n_f, e)).astype(jnp.int8)
    bias = jnp.zeros((m,), jnp.int32)
    mask01 = jax.random.bernoulli(ks[3], 0.6, (m, n_f)).astype(jnp.int8)
    mask_big = mask01 * jax.random.randint(ks[3], (m, n_f), 2, 8,
                                           dtype=jnp.int8)
    words = pack_words(table.astype(jnp.uint32))
    base = ops.wnn_scores(tuples, params, table, mask01, bias,
                          backend="gather")
    for mask in (mask01, mask_big):
        got_k = packed_wnn(tuples, params, words, mask, bias,
                           interpret=True)
        got_r = ref.packed_wnn_ref(tuples, params, words, mask, bias)
        np.testing.assert_array_equal(np.asarray(got_k), np.asarray(base))
        np.testing.assert_array_equal(np.asarray(got_r), np.asarray(base))


def test_packed_all_zero_mask_scores_are_pure_bias():
    spec = UleenSpec(num_classes=6, total_bits=96,
                     submodels=(SubmodelSpec(12, 4),))
    key = jax.random.PRNGKey(9)
    statics, tables, masks, bias = _random_binary_model(key, spec, "zeros")
    bits = jax.random.bernoulli(jax.random.PRNGKey(10), 0.5,
                                (8, spec.total_bits))
    got = forward_binary_fused(spec, statics, tables, masks, bias, bits,
                               backend="packed")
    expect = jnp.broadcast_to(jnp.round(bias).astype(jnp.int32)[None],
                              got.shape)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


# ---------------------------------------------------------------------------
# The traced packed serve path holds no int8 table
# ---------------------------------------------------------------------------
# The jaxpr walking + shape check live in `repro.analysis` (DESIGN §8) —
# this test and the CI lint (`scripts/lint_programs.py`) share one
# implementation of both the walker and the rule.

def test_packed_trace_never_materializes_int8_tables(tiny_spec,
                                                     tiny_statics,
                                                     tiny_params, encoded):
    """No intermediate in the traced packed program has the unpacked
    (M, N_f, E) extent — the 32× expansion simply does not exist. Checked
    by the `no-unpacked-table` lint rule itself."""
    from repro.analysis import CellProgram, analyze_program, aval_shapes
    bits_tr, *_ = encoded
    pt = binarize_to_packed(tiny_spec, tiny_statics, tiny_params)
    bits = jnp.asarray(bits_tr[:16])
    jaxpr = jax.make_jaxpr(
        lambda p, b: packed_scores(p, b, backend="auto"))(pt, bits)
    unpacked_shapes = frozenset(
        (tiny_spec.num_classes, tiny_spec.num_filters(sm), sm.entries)
        for sm in tiny_spec.submodels)
    findings = analyze_program(
        CellProgram(name="tiny.packed", jaxpr=jaxpr, packed=True,
                    unpacked_table_shapes=unpacked_shapes),
        rules=["no-unpacked-table"])
    assert not findings, \
        f"traced packed path materialized an unpacked table: {findings}"
    # sanity: the same rule *does* trip on the unpacked gather path
    tables_bin, masks, bias = (
        tuple(jnp.where(t >= 0, 1, 0).astype(jnp.int8)
              for t in tiny_params.tables),
        tiny_params.masks, tiny_params.bias)
    jaxpr_g = jax.make_jaxpr(
        lambda bb: forward_binary_fused(tiny_spec, tiny_statics, tables_bin,
                                        masks, bias, bb,
                                        backend="gather"))(bits)
    findings_g = analyze_program(
        CellProgram(name="tiny.gather", jaxpr=jaxpr_g, packed=True,
                    unpacked_table_shapes=unpacked_shapes),
        rules=["no-unpacked-table"])
    assert findings_g, "the rule must flag the unpacked gather program"
    assert aval_shapes(jaxpr_g) & unpacked_shapes


# ---------------------------------------------------------------------------
# Golden artifact through the packed runtime + prepared serving
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def golden():
    art = export.load(os.path.join(GOLDEN_DIR, "uln_s_artifact.npz"))
    z = np.load(os.path.join(GOLDEN_DIR, "uln_s_golden.npz"))
    return art, jnp.asarray(z["bits"], jnp.uint8), z["scores"]


def test_golden_packed_runtime_scores(golden):
    """The frozen ULN-S artifact serves the exact golden scores through
    the packed-native runtime (words lifted verbatim, never unpacked)."""
    art, bits, scores = golden
    pt = from_artifact(art)
    for sm, words in zip(art.submodels, pt.words):
        np.testing.assert_array_equal(np.asarray(words), sm.packed)
    for backend in ("packed", "auto"):
        got = packed_scores(pt, bits, backend=backend)
        np.testing.assert_array_equal(np.asarray(got), scores)


def test_prepare_artifact_caches_per_backend(golden):
    art, bits, scores = golden
    p1 = export.prepare_artifact(art, backend="auto")
    p2 = export.prepare_artifact(art, backend="auto")
    assert p1 is p2, "repeated serving must reuse the prepared tables"
    assert isinstance(p1, PackedTables)
    pf = export.prepare_artifact(art, backend="fused")
    assert isinstance(pf, export.UnpackedTables)
    assert pf is export.prepare_artifact(art, backend="fused")
    with pytest.raises(ValueError, match="backend"):
        export.prepare_artifact(art, backend="mosaic")


def test_packed_scores_rejects_unpacked_backends(golden):
    art, bits, _ = golden
    with pytest.raises(ValueError, match="packed"):
        packed_scores(from_artifact(art), bits, backend="fused")


# ---------------------------------------------------------------------------
# Serve engine batch path (launch/scheduler.py::WnnBatcher)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["auto", "packed", "gather"])
def test_wnn_batcher_parity_and_single_compile(golden, backend):
    """The batch path serves the golden scores exactly, pads partial
    batches, and compiles its scores launch exactly once."""
    from repro.launch.scheduler import WnnBatcher
    art, bits, scores = golden
    eng = WnnBatcher(art, slots=12, backend=backend)
    for i in range(30):                      # 2 full batches + a partial
        eng.submit(np.asarray(bits[i]))
    results = eng.drain()
    got = np.stack([r.scores for r in results])
    np.testing.assert_array_equal(got, scores[:30])
    assert [r.pred for r in results] == list(np.argmax(scores[:30], -1))
    st = eng.stats()
    assert st["batches"] == 3 and st["requests"] == 30
    assert st["traces"] == 1, "steady state must not recompile"
    assert st["occupancy"] == pytest.approx(30 / 36)


def test_wnn_batcher_rejects_wrong_width(golden):
    from repro.launch.scheduler import WnnBatcher
    art, *_ = golden
    eng = WnnBatcher(art, slots=4)
    with pytest.raises(ValueError, match="bits"):
        eng.submit(np.zeros(7, np.uint8))
    assert eng.step() == 0                   # idle engine is a no-op


# ---------------------------------------------------------------------------
# Production-mesh packed infer cell + hardware-model accounting
# ---------------------------------------------------------------------------

def test_packed_infer_cell_lowers(tiny_spec):
    """The packed-domain inference cell lowers + compiles on the host mesh
    with both the kernel and auto backends threaded through."""
    from repro.launch import uleen_cell
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    for backend in ("packed", "auto"):
        compiled = uleen_cell.lower_uleen_packed_infer_cell(
            mesh, global_batch=32, spec=tiny_spec, backend=backend)
        assert compiled.memory_analysis().argument_size_in_bytes > 0


def test_uln_xl_exceeds_fused_vmem_but_fits_packed():
    """The geometry the packed subsystem exists for: ULN-XL's largest
    submodel cannot block inside 16 MiB VMEM as int8 tables, and
    comfortably can as uint32 bitplanes."""
    from repro.kernels import fused_wnn, packed_wnn as pk
    from repro.launch.uleen_cell import ULN_XL_SPEC
    vmem = 16 * 2 ** 20
    sm = max(ULN_XL_SPEC.submodels, key=lambda s: s.entries)
    b, m = 256, ULN_XL_SPEC.num_classes
    n_f = ULN_XL_SPEC.num_filters(sm)
    n = sm.inputs_per_filter
    fused_bytes = fused_wnn.vmem_plan(b, n, m, sm.entries)["vmem_bytes"]
    packed_bytes = pk.vmem_plan(b, n, m, sm.entries)["vmem_bytes"]
    assert fused_bytes > vmem, (fused_bytes, n_f)
    assert packed_bytes < vmem


def test_hwmodel_reads_packed_bytes(golden):
    art, *_ = golden
    from repro.core import hwmodel
    counts = hwmodel.counts_from_artifact(art)
    surviving_words = sum(int(sm.mask.sum()) * sm.packed.shape[-1]
                          for sm in art.submodels)
    assert counts.packed_table_bytes == surviving_words * 4
    assert counts.table_bytes == counts.packed_table_bytes
    assert counts.table_bits == surviving_words * 32
    # ULN-S entries are >= 32, so packed storage == ideal bit count
    assert art.packed_size_kib == pytest.approx(art.size_kib)
