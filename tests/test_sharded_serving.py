"""Class-sharded serving differential battery (DESIGN §7).

The contract: partitioning the Bloom tables over the mesh's `model` axis
by class — per-device partial score columns, one (B, M) gather, argmax —
is **exactly int32 score-equal** (and argmax-equal) to the replicated
serve path, for both the packed-domain and int8 gather representations,
on a real multi-device mesh. int32 addition is associative, so this holds
bit-for-bit, not approximately; any divergence is a sharding bug.

Runs on a forced 8-device host platform
(`XLA_FLAGS=--xla_force_host_platform_device_count=8`, set by
tests/conftest.py before jax initialises and by the CI fast job), meshed
as (data=2, model=4): M ∈ {8, 12} shard 4-way, M=10 exercises the
replication fallback.
"""
import collections

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
from repro.core.model import SubmodelSpec, UleenSpec, binarize_to_packed
from repro.dist import sharding as sh
from repro.launch.mesh import make_mesh
from repro.packed import packed_scores

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _mesh8():
    return make_mesh((2, 4), ("data", "model"))


def _spec(m, n=6, log2e=6, k=2, n_f_times=1, multi=False):
    if multi:
        subs = (SubmodelSpec(6, 5, num_hashes=2),
                SubmodelSpec(8, 6, num_hashes=3),
                SubmodelSpec(10, 4, num_hashes=1))
    else:
        subs = (SubmodelSpec(n, log2e, num_hashes=k),)
    total = max(sm.inputs_per_filter for sm in subs) * 8 * n_f_times
    return UleenSpec(num_classes=m, total_bits=total, submodels=subs)


def _binary_model(seed, spec, mask_kind="random"):
    statics, tables, masks, bias = _random_binary_model(
        jax.random.PRNGKey(seed), spec, mask_kind)
    bits = jax.random.bernoulli(jax.random.PRNGKey(seed + 1), 0.5,
                                (17, spec.total_bits))
    return statics, tables, masks, bias, bits


def _packed(spec, statics, tables, masks, bias):
    from repro.core.model import UleenParams
    params = UleenParams(
        tables=tuple(jnp.where(t, 0.5, -0.5) for t in tables),
        bias=jnp.asarray(bias, jnp.float32),
        masks=tuple(jnp.asarray(m, jnp.float32) for m in masks))
    return binarize_to_packed(spec, statics, params)


def _unpacked(spec, statics, tables, masks, bias):
    return export.UnpackedTables(
        tables=tuple(jnp.asarray(t, jnp.int8) for t in tables),
        masks=tuple((jnp.asarray(m) != 0).astype(jnp.int8) for m in masks),
        perms=tuple(jnp.asarray(st.perm, jnp.int32) for st in statics),
        h3s=tuple(jnp.asarray(st.h3).astype(jnp.int32) for st in statics),
        bias=jnp.asarray(jnp.round(bias), jnp.int32))


def _sharded_run(prep, bits, mesh, *, backend="auto"):
    """scores/preds through the class-sharded path: tables device_put
    partitioned by class, bits by batch, predict jitted with those
    in_shardings under the serve mesh."""
    pshard = export.prep_shardings(prep, mesh, sh.SERVE_RULES)
    bshard = sh.named_sharding(mesh, sh.SERVE_RULES, ("batch", None),
                               shape=tuple(bits.shape))
    prep_s = jax.device_put(prep, pshard)
    bits_s = jax.device_put(jnp.asarray(bits), bshard)
    with sh.use_mesh(mesh, sh.SERVE_RULES):
        fn = jax.jit(
            lambda p, b: export.predict_from_prep(p, b, backend=backend),
            in_shardings=(pshard, bshard))
        scores, preds = fn(prep_s, bits_s)
    return np.asarray(scores), np.asarray(preds), prep_s


# ---------------------------------------------------------------------------
# Packed-domain parity: divisible, fallback, multi-submodel ensembles
# ---------------------------------------------------------------------------

@needs8
@pytest.mark.parametrize("m,multi", [(8, False), (10, False), (12, False),
                                     (8, True), (12, True)])
def test_sharded_packed_parity(m, multi):
    """Sharded packed serve == replicated packed serve, exact int32, for
    the divisible (M=8, 12), fallback (M=10), and ensemble geometries."""
    mesh = _mesh8()
    spec = _spec(m, multi=multi)
    statics, tables, masks, bias, bits = _binary_model(m * 7 + multi, spec)
    pt = _packed(spec, statics, tables, masks, bias)
    expect = np.asarray(packed_scores(pt, bits))          # replicated, no mesh
    scores, preds, pt_s = _sharded_run(pt, bits, mesh)
    np.testing.assert_array_equal(scores, expect)
    np.testing.assert_array_equal(preds, expect.argmax(-1))
    # the tables really are partitioned (or really fell back)
    entry, degree = sh.class_partition(mesh, m)
    assert degree == (4 if m % 4 == 0 else 1)
    shard_m = pt_s.words[0].addressable_shards[0].data.shape[0]
    assert shard_m == m // degree


@needs8
@pytest.mark.parametrize("m", [8, 10, 12])
def test_sharded_gather_parity(m):
    """The int8 gather representation shards identically: scores_from_prep
    over a class-partitioned UnpackedTables is bit-equal to replicated."""
    mesh = _mesh8()
    spec = _spec(m, multi=(m == 12))
    statics, tables, masks, bias, bits = _binary_model(m * 13, spec)
    prep = _unpacked(spec, statics, tables, masks, bias)
    expect = np.asarray(export.scores_from_prep(prep, jnp.asarray(bits),
                                                backend="gather"))
    scores, preds, prep_s = _sharded_run(prep, bits, mesh, backend="gather")
    np.testing.assert_array_equal(scores, expect)
    np.testing.assert_array_equal(preds, expect.argmax(-1))
    shard_m = prep_s.tables[0].addressable_shards[0].data.shape[0]
    assert shard_m == m // (4 if m % 4 == 0 else 1)


@needs8
@settings(deadline=None, max_examples=10)
@given(st.sampled_from([8, 10, 12]),     # classes: divisible + fallback
       st.integers(4, 12),               # inputs per filter n
       st.integers(3, 8),                # log2 entries -> E in 8..256
       st.integers(1, 4),                # hash functions k
       st.integers(1, 23),               # batch (incl. odd, < and > data=2)
       st.sampled_from(["ones", "random", "zeros"]))
def test_sharded_parity_randomized(m, n, log2e, k, b, mask_kind):
    """Hypothesis sweep: random geometry, both representations, exact
    int32 sharded/replicated equality on the 8-device mesh."""
    mesh = _mesh8()
    spec = UleenSpec(num_classes=m, total_bits=n * 9,
                     submodels=(SubmodelSpec(n, log2e, num_hashes=k),))
    statics, tables, masks, bias = _random_binary_model(
        jax.random.PRNGKey(m * 7919 + n * 101 + log2e * 11 + k + b), spec,
        mask_kind)
    bits = jax.random.bernoulli(jax.random.PRNGKey(b), 0.5,
                                (b, spec.total_bits))
    pt = _packed(spec, statics, tables, masks, bias)
    expect = np.asarray(packed_scores(pt, bits))
    scores, preds, _ = _sharded_run(pt, bits, mesh)
    np.testing.assert_array_equal(scores, expect)
    prep = _unpacked(spec, statics, tables, masks, bias)
    scores_g, _, _ = _sharded_run(prep, bits, mesh, backend="gather")
    np.testing.assert_array_equal(scores_g, expect)


def test_class_slice_is_the_partial_score_oracle():
    """What one device computes: scoring the [lo, hi) class slice yields
    exactly those columns of the full matrix (per-class independence —
    the property that makes the `classes` axis partitionable at all)."""
    spec = _spec(12, multi=True)
    statics, tables, masks, bias, bits = _binary_model(3, spec)
    pt = _packed(spec, statics, tables, masks, bias)
    full = np.asarray(packed_scores(pt, bits))
    cols = []
    for lo in range(0, 12, 3):
        shard = pt.class_slice(lo, lo + 3)
        assert shard.num_classes == 3
        cols.append(np.asarray(packed_scores(shard, bits)))
    np.testing.assert_array_equal(np.concatenate(cols, axis=1), full)
    prep = _unpacked(spec, statics, tables, masks, bias)
    half = export.prep_class_slice(prep, 6, 12)
    np.testing.assert_array_equal(
        np.asarray(export.scores_from_prep(half, jnp.asarray(bits),
                                           backend="gather")),
        full[:, 6:])
    with pytest.raises(ValueError, match="class range"):
        pt.class_slice(4, 2)
    with pytest.raises(ValueError, match="class range"):
        export.prep_class_slice(prep, 0, 13)


# ---------------------------------------------------------------------------
# Mesh-aware WnnBatcher
# ---------------------------------------------------------------------------

def _artifact(spec, seed=0):
    """A small trained-model artifact via the real export path."""
    from repro.core.model import UleenParams
    statics, tables, masks, bias = _random_binary_model(
        jax.random.PRNGKey(seed), spec, "random")
    params = UleenParams(
        tables=tuple(jnp.where(t, 0.5, -0.5) for t in tables),
        bias=jnp.asarray(bias, jnp.float32),
        masks=tuple(jnp.asarray(m, jnp.float32) for m in masks))
    return export.export_model(spec, statics, params)


@needs8
@pytest.mark.parametrize("m,backend", [(8, "auto"), (10, "auto"),
                                       (8, "gather")])
def test_wnn_batcher_sharded_parity_single_compile(m, backend):
    """The mesh-aware batcher serves bit-identical scores/preds to the
    unsharded batcher, still compiling exactly once, with the tables
    genuinely class-partitioned (or cleanly fallen back for M=10)."""
    from repro.launch.scheduler import WnnBatcher
    mesh = _mesh8()
    spec = _spec(m)
    art = _artifact(spec, seed=m)
    rng = np.random.default_rng(m)
    rows = rng.integers(0, 2, (23, spec.total_bits)).astype(np.uint8)

    plain = WnnBatcher(art, slots=8, backend=backend)
    sharded = WnnBatcher(art, slots=8, backend=backend, mesh=mesh)
    for row in rows:
        plain.submit(row)
        sharded.submit(row)
    res_p, res_s = plain.drain(), sharded.drain()
    np.testing.assert_array_equal(np.stack([r.scores for r in res_s]),
                                  np.stack([r.scores for r in res_p]))
    assert [r.pred for r in res_s] == [r.pred for r in res_p]
    st_s = sharded.stats()
    assert st_s["traces"] == 1, "mesh placement must not add compiles"
    assert st_s["class_shards"] == (4 if m % 4 == 0 else 1)
    assert st_s["requests"] == st_s["submitted"] == 23
    # the prepared tables live sharded on the mesh, placed once at init
    leaf = (sharded._prep.words[0] if hasattr(sharded._prep, "words")
            else sharded._prep.tables[0])
    assert leaf.addressable_shards[0].data.shape[0] == \
        m // st_s["class_shards"]


@needs8
def test_prepare_artifact_memoizes_per_mesh():
    from repro.core import export as export_mod
    mesh = _mesh8()
    art = _artifact(_spec(8), seed=5)
    p1 = export_mod.prepare_artifact(art, backend="auto", mesh=mesh)
    assert p1 is export_mod.prepare_artifact(art, backend="auto", mesh=mesh)
    assert p1 is not export_mod.prepare_artifact(art, backend="auto")


# ---------------------------------------------------------------------------
# WnnBatcher stress: randomized submit/step/drain interleavings
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_wnn_batcher_interleaving_stress(seed):
    """Random interleavings of submit/step/drain never lose, duplicate,
    or mis-route a result: every rid maps to the scores of exactly the
    bits submitted under it, and stats totals reconcile."""
    from repro.launch.scheduler import WnnBatcher
    spec = _spec(10)
    art = _artifact(spec, seed=100 + seed)
    rng = np.random.default_rng(seed)
    eng = WnnBatcher(art, slots=4, backend="auto")

    submitted = {}                       # rid -> bits row
    for _ in range(200):
        op = rng.choice(["submit", "submit", "step", "drain"])
        if op == "submit":
            row = rng.integers(0, 2, (spec.total_bits,)).astype(np.uint8)
            rid = eng.submit(row)
            assert rid not in submitted, "rids must be unique"
            submitted[rid] = row
        elif op == "step":
            before = len(eng.queue)
            served = eng.step()
            assert served == min(4, before)
        else:
            eng.drain()
            assert not eng.queue
    results = eng.drain()

    # nothing lost, nothing duplicated, rid ordering stable
    assert [r.rid for r in results] == sorted(submitted)
    assert len(results) == len(submitted)
    # every result is the true scores of ITS OWN submitted row
    expect = np.asarray(export.artifact_scores(
        art, jnp.asarray(np.stack([submitted[r.rid] for r in results]))))
    np.testing.assert_array_equal(np.stack([r.scores for r in results]),
                                  expect)
    assert [r.pred for r in results] == list(expect.argmax(-1))
    assert all(r.t_done >= r.t_submit for r in results)
    # stats totals reconcile with submissions
    stats = eng.stats()
    assert stats["requests"] == stats["submitted"] == len(submitted)
    assert stats["served"] == len(submitted)
    assert stats["queued"] == 0
    assert stats["occupancy"] <= 1.0
    assert stats["traces"] == 1


@needs8
def test_wnn_batcher_interleaving_stress_sharded():
    """The same invariants hold with the batch sharded across the serve
    mesh — placement must not perturb scheduling or results."""
    from repro.launch.scheduler import WnnBatcher
    mesh = _mesh8()
    spec = _spec(8)
    art = _artifact(spec, seed=77)
    rng = np.random.default_rng(7)
    eng = WnnBatcher(art, slots=8, backend="auto", mesh=mesh)
    submitted = {}
    for _ in range(120):
        if rng.random() < 0.6:
            row = rng.integers(0, 2, (spec.total_bits,)).astype(np.uint8)
            submitted[eng.submit(row)] = row
        else:
            eng.step()
    results = eng.drain()
    assert [r.rid for r in results] == sorted(submitted)
    expect = np.asarray(export.artifact_scores(
        art, jnp.asarray(np.stack([submitted[r.rid] for r in results]))))
    np.testing.assert_array_equal(np.stack([r.scores for r in results]),
                                  expect)
    assert eng.stats()["traces"] == 1


# ---------------------------------------------------------------------------
# WnnBatcher: at most one launch in flight
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,sharded", [
    (0, False), (1, False), (2, False),
    pytest.param(3, True, marks=needs8)])
def test_wnn_batcher_keeps_one_launch_in_flight(seed, sharded):
    """A step that leaves rows queued returns its take and leaves its
    launch in flight: the launch's rows are answered by the next step,
    never later, and nothing else is unanswered but the queue. A step
    that empties the queue answers every row. The answers are the
    artifact's scores, bit for bit, with or without a mesh."""
    from repro.launch.scheduler import WnnBatcher
    spec = _spec(8 if sharded else 10)
    art = _artifact(spec, seed=200 + seed)
    rng = np.random.default_rng(seed)
    eng = WnnBatcher(art, slots=4, backend="auto",
                     mesh=_mesh8() if sharded else None)
    submitted = {}

    def submit(n):
        for _ in range(n):
            row = rng.integers(0, 2, (spec.total_bits,)).astype(np.uint8)
            submitted[eng.submit(row)] = row

    submit(11)
    for _ in range(40):
        if rng.random() < 0.4:
            submit(int(rng.integers(0, 7)))
        if not eng.queue:
            continue
        queued = [rid for rid, _ in eng.queue]
        take = eng.step()
        assert take == min(4, len(queued))
        pending = {r.rid for r in eng.results.values() if r.t_done is None}
        if eng.queue:
            assert pending == set(queued)      # launch k and the queue
        else:
            assert pending == set()
    eng.drain()

    results = [eng.results[rid] for rid in sorted(submitted)]
    expect = np.asarray(export.artifact_scores(
        art, jnp.asarray(np.stack([submitted[r.rid] for r in results]))))
    np.testing.assert_array_equal(np.stack([r.scores for r in results]),
                                  expect)
    assert [r.pred for r in results] == list(expect.argmax(-1))


def test_wnn_batcher_overlapped_counts_the_ready_waits():
    """`stats()["overlapped"]` counts the waits that found their launch
    finished, one wait per launch; while a launch is in flight its rows
    are served but not yet requests answered, and after the queue
    empties `requests`, `served` and `submitted` agree."""
    from repro.launch.scheduler import WnnBatcher
    from repro.obs import registry as oreg
    spec = _spec(10)
    eng = WnnBatcher(_artifact(spec, seed=5), slots=2, backend="auto")
    rng = np.random.default_rng(5)
    with oreg.recording() as rec:
        for _ in range(7):
            eng.submit(rng.integers(0, 2, (spec.total_bits,)).astype(np.uint8))
        assert eng.step() == 2
        st = eng.stats()
        assert (st["batches"], st["served"], st["requests"]) == (1, 2, 0)
        eng._in_flight.scores.block_until_ready()   # its wait finds it ready
        eng.drain()
    waits = [s for s in rec.spans if s.name == "wnn.wait"]
    st = eng.stats()
    assert [s.attrs["batch"] for s in waits] == list(range(st["batches"]))
    assert waits[0].attrs["ready"]
    assert st["overlapped"] == sum(s.attrs["ready"] for s in waits) >= 1
    assert st["batches"] == 4 and st["occupancy"] == 7 / 8
    assert st["requests"] == st["served"] == st["submitted"] == 7
    assert st["queued"] == 0


# ---------------------------------------------------------------------------
# The sharded production cell lowers with partitioned tables
# ---------------------------------------------------------------------------

@needs8
def test_sharded_infer_cell_lowers_with_partitioned_tables():
    """lower_uleen_sharded_infer_cell on the 8-device mesh: per-device
    table argument bytes shrink by the class-shard degree vs the
    replicated packed cell (the acceptance property of the
    infer_sharded_scale dry-run, CPU-sized)."""
    from repro.launch import uleen_cell
    mesh = _mesh8()
    spec = _spec(8, multi=True)
    sharded = uleen_cell.lower_uleen_sharded_infer_cell(
        mesh, global_batch=32, spec=spec)
    replicated = uleen_cell.lower_uleen_packed_infer_cell(
        mesh, global_batch=32, spec=spec)
    _, degree = sh.class_partition(mesh, spec.num_classes)
    assert degree == 4
    args_s = sharded.memory_analysis().argument_size_in_bytes
    args_r = replicated.memory_analysis().argument_size_in_bytes
    table_bytes = uleen_cell.packed_table_specs(spec).table_bytes()
    # sharded args shed ~ (1 - 1/degree) of the table bytes
    assert args_r - args_s >= (table_bytes - table_bytes // degree) * 0.9


# ---------------------------------------------------------------------------
# Serve-path stats regressions: zero-clock completions, even-length p50
# ---------------------------------------------------------------------------

class _Clock:
    """Injectable wall clock: tests set `t` between scheduler calls."""
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_quantiles_come_from_obs_histogram():
    """Serving quantiles migrated off raw-sample `_median` lists onto
    `repro.obs.metrics.Histogram` (DESIGN §12): nearest-rank quantiles at
    bucket resolution, clamped into the exact [min, max] envelope. Pin
    the contract the stats() surfaces now rely on."""
    from repro.obs.metrics import Histogram, exact_quantile
    # nearest-rank oracle the histogram approximates
    assert exact_quantile([5.0], 0.5) == 5.0
    assert exact_quantile([1.0, 3.0], 0.5) == 1.0
    assert exact_quantile([1.0, 2.0, 7.0], 0.5) == 2.0
    h = Histogram()
    for v in [1.0, 2.0, 3.0, 10.0]:
        h.observe(v)
    lo, hi = h.quantile_bounds(0.5)
    assert lo <= exact_quantile([1.0, 2.0, 3.0, 10.0], 0.5) <= hi
    assert h.quantile(0.5) == h.quantile(0.5)  # deterministic
    assert h.min == 1.0 and h.max == 10.0 and h.mean == 4.0
    # single-sample histograms are exact (clamped to the envelope)
    h1 = Histogram()
    h1.observe(5.0)
    assert h1.quantile(0.5) == 5.0 and h1.quantile(0.99) == 5.0


def test_wnn_batcher_zero_clock_and_latency_stats():
    """t_done == 0.0 is a COMPLETED request (the old `if r.t_done`
    truthiness filter dropped it), and the histogram-backed stats report
    an exact mean/max with bucket-resolution quantiles (DESIGN §12)."""
    from repro.launch.scheduler import WnnBatcher
    from repro.obs.metrics import RESOLUTION
    spec = _spec(8)
    art = _artifact(spec, seed=3)
    row = np.zeros((spec.total_bits,), np.uint8)

    zero = WnnBatcher(art, slots=2, backend="auto", clock=lambda: 0.0)
    zero.submit(row)
    results = zero.drain()
    assert results[0].t_done == 0.0
    st0 = zero.stats()
    assert st0["requests"] == 1
    assert st0["latency_p50_s"] == 0.0 and st0["latency_max_s"] == 0.0
    assert st0["latency_p99_s"] == 0.0

    clk = _Clock()
    eng = WnnBatcher(art, slots=4, backend="auto", clock=clk)
    eng.submit(row)                      # t_submit = 0.0
    clk.t = 1.0
    eng.submit(row)                      # t_submit = 1.0
    clk.t = 4.0
    eng.step()                           # both done at 4.0 -> lats [4, 3]
    st = eng.stats()
    assert st["requests"] == 2
    assert st["latency_mean_s"] == 3.5        # exact (tracked sum/count)
    assert st["latency_max_s"] == 4.0         # exact (tracked max)
    # p50 = rank-1 sample (3.0) at bucket resolution, clamped to >= min
    assert 3.0 <= st["latency_p50_s"] <= 3.0 * RESOLUTION
    # p99 = rank-2 sample (4.0); the clamp caps it at the exact max
    assert 4.0 / RESOLUTION <= st["latency_p99_s"] <= 4.0


# ---------------------------------------------------------------------------
# WnnTenantBatcher: tenant-routed fleet serving (DESIGN §11)
# ---------------------------------------------------------------------------

def _tenant_fleet(n, seed0=0):
    spec = _spec(10, multi=True)
    return spec, [_artifact(spec, seed=seed0 + i) for i in range(n)]


def test_tenant_batcher_parity_with_eviction_single_compile():
    """capacity 2 < 5 tenants forces admission/eviction churn, yet every
    request's scores are bit-identical to its tenant's solo WnnBatcher,
    with exactly ONE scores compile and ONE install compile."""
    from repro.launch.scheduler import WnnBatcher, WnnTenantBatcher
    spec, arts = _tenant_fleet(5, seed0=60)
    tb = WnnTenantBatcher(capacity=2, slots=4, backend="auto")
    tids = [tb.add_tenant(a) for a in arts]
    assert tids == list(range(5))
    solos = [WnnBatcher(a, slots=4, backend="auto") for a in arts]

    rng = np.random.default_rng(4)
    pairs = []
    for _ in range(40):
        tid = int(rng.integers(0, 5))
        row = rng.integers(0, 2, (spec.total_bits,)).astype(np.uint8)
        pairs.append((tb.submit(tid, row), tid, solos[tid].submit(row)))
    got = {r.rid: r for r in tb.drain()}
    ref = [{r.rid: r for r in s.drain()} for s in solos]
    for rid, tid, srid in pairs:
        assert got[rid].tid == tid
        np.testing.assert_array_equal(got[rid].scores,
                                      ref[tid][srid].scores)
        assert got[rid].pred == ref[tid][srid].pred
    st = tb.stats()
    assert st["traces"] == 1, "tenant churn must not add compiles"
    assert st["install_traces"] == 1, "slot installs share one program"
    assert st["evictions"] > 0, "capacity 2 over 5 tenants must evict"
    assert st["hits"] + st["misses"] == st["served"] == 40
    assert st["misses"] == st["admissions"]
    assert st["resident"] <= st["capacity"] == 2


@pytest.mark.parametrize("seed", [0, 1])
def test_tenant_batcher_interleaving_stress_per_tenant_stats(seed):
    """Random submit/step/drain interleavings over a 4-tenant fleet with
    a 3-slot cache: nothing lost, duplicated, or mis-routed; per-tenant
    stats reconcile with what was actually submitted per tenant."""
    from repro.launch.scheduler import WnnTenantBatcher
    spec, arts = _tenant_fleet(4, seed0=70 + 10 * seed)
    tb = WnnTenantBatcher(capacity=3, slots=4, backend="auto")
    for a in arts:
        tb.add_tenant(a)
    rng = np.random.default_rng(seed)
    submitted = {}                       # rid -> (tid, bits row)
    for _ in range(150):
        op = rng.choice(["submit", "submit", "step", "drain"])
        if op == "submit":
            tid = int(rng.integers(0, 4))
            row = rng.integers(0, 2, (spec.total_bits,)).astype(np.uint8)
            rid = tb.submit(tid, row)
            assert rid not in submitted
            submitted[rid] = (tid, row)
        elif op == "step":
            tb.step()
        else:
            tb.drain()
            assert not tb.queue
    results = tb.drain()
    assert [r.rid for r in results] == sorted(submitted)
    for r in results:
        tid, row = submitted[r.rid]
        assert r.tid == tid
        expect = np.asarray(export.artifact_scores(
            arts[tid], jnp.asarray(row[None])))[0]
        np.testing.assert_array_equal(r.scores, expect)
        assert r.t_done is not None and r.t_done >= r.t_submit
    st = tb.stats()
    assert st["requests"] == st["submitted"] == st["served"] == \
        len(submitted)
    assert st["queued"] == 0 and st["traces"] == 1
    per_tid = collections.Counter(tid for tid, _ in submitted.values())
    for tid in range(4):
        pt = st["per_tenant"][tid]
        assert pt["requests"] == per_tid[tid]
        if per_tid[tid]:
            assert pt["latency_p50_s"] is not None
            assert 0.0 < pt["occupancy"] <= 1.0
        else:
            assert pt["latency_p50_s"] is None
    assert abs(sum(st["per_tenant"][t]["occupancy"] for t in range(4))
               - st["occupancy"]) < 1e-9


@needs8
def test_tenant_batcher_mesh_parity_single_compile():
    """Batch-sharded tenant batcher on the 8-device mesh: bit-identical
    results to the unsharded batcher, still one compile."""
    from repro.launch.scheduler import WnnTenantBatcher
    mesh = _mesh8()
    spec, arts = _tenant_fleet(5, seed0=90)
    plain = WnnTenantBatcher(capacity=2, slots=8, backend="auto")
    sharded = WnnTenantBatcher(capacity=2, slots=8, backend="auto",
                               mesh=mesh)
    for a in arts:
        plain.add_tenant(a)
        sharded.add_tenant(a)
    rng = np.random.default_rng(5)
    for _ in range(30):
        tid = int(rng.integers(0, 5))
        row = rng.integers(0, 2, (spec.total_bits,)).astype(np.uint8)
        plain.submit(tid, row)
        sharded.submit(tid, row)
    res_p, res_s = plain.drain(), sharded.drain()
    np.testing.assert_array_equal(np.stack([r.scores for r in res_s]),
                                  np.stack([r.scores for r in res_p]))
    assert [r.pred for r in res_s] == [r.pred for r in res_p]
    assert sharded.stats()["traces"] == 1


def test_tenant_batcher_validation():
    from repro.launch.scheduler import WnnTenantBatcher
    spec, arts = _tenant_fleet(1, seed0=95)
    with pytest.raises(ValueError, match="capacity"):
        WnnTenantBatcher(capacity=0)
    with pytest.raises(ValueError, match="packed domain"):
        WnnTenantBatcher(backend="fused")
    tb = WnnTenantBatcher(capacity=2, slots=4)
    with pytest.raises(ValueError, match="unknown tenant"):
        tb.submit(0, np.zeros(8, np.uint8))
    tb.add_tenant(arts[0])
    with pytest.raises(ValueError, match="bits"):
        tb.submit(0, np.zeros(spec.total_bits + 1, np.uint8))
    with pytest.raises(ValueError, match="geometry"):
        tb.add_tenant(_artifact(_spec(8), seed=96))
    # empty stats: stable schema, latencies None
    st = tb.stats()
    assert st["requests"] == 0 and st["latency_p50_s"] is None
    assert st["per_tenant"][0]["latency_p50_s"] is None
