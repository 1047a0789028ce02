"""Observability battery (DESIGN §12): spans, histograms, the recorder,
and the obsmetrics/v1 METRICS.json contract.

Four layers, matching the package split:

* `trace`: injected-clock span nesting and JSONL round-trip;
* `metrics`: bucket boundary semantics and the quantile-vs-nearest-rank
  oracle (property-style over seeded samples);
* `registry`: snapshot schema validation (accept + targeted rejects),
  write/load round-trip, and the no-op-overhead pin — with the default
  NullRecorder installed, an instrumented serve run emits ZERO events;
* integration: a WnnTenantBatcher stress run under `recording()` whose
  snapshot counters reconcile exactly with `stats()`, with scores still
  bit-identical to the uninstrumented oracle (spans never touch traced
  values), and a short train_uleen run exporting step-time histograms,
  checkpoint spans, and the straggler EWMA gauge;
* the span trees of `WnnBatcher.step` and of the `train_uleen` loop, and
  a span's profiler annotation on a `jax.profiler` trace.
"""
import contextlib
import copy
import glob
import math
import time

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:          # minimal containers: seeded deterministic shim
    from _hypothesis_compat import given, settings
    from _hypothesis_compat import strategies as st

from repro.obs import metrics as om
from repro.obs import registry as oreg
from repro.obs import trace as otr


class _Clock:
    """Injectable wall clock (same pattern as the scheduler tests)."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture
def annotations():
    """Names of the profiler annotations that spans open, in the order
    they open: the annotation factory that `jaxhooks` installs is swapped
    for one that notes each name."""
    import repro.obs.jaxhooks  # noqa: F401  (installs the real factory)
    opened = []

    def note(name):
        opened.append(name)
        return contextlib.nullcontext()

    prev = oreg.set_annotation(note)
    yield opened
    oreg.set_annotation(prev)


def _children(rec, sp):
    """`sp`'s direct children in `rec`, in the order they opened."""
    return sorted((s for s in rec.spans if s.parent == sp.index),
                  key=lambda s: s.index)


def _inside(parent, children):
    """Each child lies within its parent, and each ends before the next
    one starts."""
    for c in children:
        assert parent.t0 <= c.t0 <= c.t1 <= parent.t1
    for a, b in zip(children, children[1:]):
        assert a.t1 <= b.t0


# ---------------------------------------------------------------------------
# trace: spans + JSONL
# ---------------------------------------------------------------------------

def test_span_nesting_and_jsonl_round_trip(tmp_path):
    clk = _Clock()
    path = tmp_path / "events.jsonl"
    rec = oreg.Recorder(clock=clk, jsonl_path=path)
    with rec.span("outer", cell="a.b") as outer:
        clk.t = 1.0
        with rec.span("inner") as inner:
            clk.t = 3.0
        clk.t = 5.0
    rec.event("straggler", step=7, ratio=2.5)
    rec.close()

    assert outer.dur_s == 5.0 and inner.dur_s == 2.0
    assert outer.depth == 0 and inner.depth == 1
    assert inner.parent == outer.index and outer.parent is None

    evs = otr.read_jsonl(path)
    assert [e["ev"] for e in evs] == ["span", "span", "straggler"]
    # inner closes (and therefore emits) first; indices preserve nesting
    assert evs[0]["name"] == "inner" and evs[1]["name"] == "outer"
    assert evs[0]["dur_s"] == 2.0 and evs[1]["attrs"] == {"cell": "a.b"}
    assert evs[2]["step"] == 7 and evs[2]["t"] == 5.0

    doc = rec.snapshot()
    assert [s["name"] for s in doc["spans"]] == ["inner", "outer"]
    assert doc["events_emitted"] == 3 and doc["spans_dropped"] == 0


def test_span_cap_bounds_snapshot_not_sink(tmp_path):
    """Past max_spans the snapshot stops growing (spans_dropped counts)
    but the JSONL sink still receives every span — bounded host memory
    without losing telemetry."""
    path = tmp_path / "ev.jsonl"
    rec = oreg.Recorder(clock=lambda: 0.0, jsonl_path=path, max_spans=3)
    for i in range(5):
        with rec.span(f"s{i}"):
            pass
    rec.close()
    assert len(rec.spans) == 3 and rec.spans_dropped == 2
    assert len(otr.read_jsonl(path)) == 5
    oreg.validate_snapshot(rec.snapshot())


def test_span_records_on_exception():
    clk = _Clock()
    rec = oreg.Recorder(clock=clk)
    with pytest.raises(RuntimeError):
        with rec.span("boom"):
            clk.t = 2.0
            raise RuntimeError("x")
    assert len(rec.spans) == 1 and rec.spans[0].dur_s == 2.0


# ---------------------------------------------------------------------------
# metrics: histogram semantics
# ---------------------------------------------------------------------------

def test_histogram_bucket_boundaries():
    h = om.Histogram()
    n = len(h.buckets)
    # closed below: an exact edge lands IN its bucket
    for i in (0, 1, 17, n - 1):
        assert h.bucket_index(h.edges[i]) == i
    # just below an edge -> the previous bucket
    assert h.bucket_index(math.nextafter(h.edges[5], 0.0)) == 4
    # outside [lo, hi): dedicated under/overflow
    assert h.bucket_index(h.edges[0] * 0.5) == -1
    assert h.bucket_index(0.0) == -1
    assert h.bucket_index(h.edges[-1]) == n
    assert h.bucket_index(float("inf")) == n


def test_histogram_all_zero_reports_exact_zero():
    """The serve zero-clock pins depend on this: identical samples (all
    0.0, below the lowest edge) report their exact value at every
    quantile via the [min, max] clamp."""
    h = om.Histogram()
    for _ in range(5):
        h.observe(0.0)
    assert h.underflow == 5 and h.count == 5
    assert h.quantile(0.5) == 0.0 and h.quantile(0.99) == 0.0
    assert h.mean == 0.0 and h.max == 0.0
    om.validate_histogram_json("zero", h.to_json())


def test_histogram_overflow_clamps_to_exact_max():
    h = om.Histogram()
    h.observe(5e3)                       # >= hi -> overflow bucket
    assert h.overflow == 1
    assert h.quantile(0.5) == 5e3 and h.quantile(0.99) == 5e3
    j = h.to_json()
    om.validate_histogram_json("over", j)
    assert j["count"] == 1 and j["p99"] == 5e3


def test_histogram_rejects_bad_geometry_and_quantiles():
    with pytest.raises(ValueError):
        om.Histogram(lo=1.0, hi=1.0)
    with pytest.raises(ValueError):
        om.Histogram(lo=0.0, hi=1.0)
    h = om.Histogram()
    h.observe(1.0)
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        om.exact_quantile([1.0], -0.1)


@settings(deadline=None, max_examples=30)
@given(st.integers(min_value=1, max_value=200),
       st.integers(min_value=0, max_value=10**6),
       st.floats(min_value=0.0, max_value=1.0))
def test_histogram_quantile_vs_sorted_sample_oracle(n, seed, q):
    """For in-range samples, `quantile_bounds(q)` brackets the
    nearest-rank order statistic and `quantile(q)` lands within one
    bucket RESOLUTION above it (never above the true max)."""
    rng = np.random.default_rng(seed)
    vals = np.exp(rng.uniform(np.log(1e-5), np.log(1e2), n))
    h = om.Histogram()
    for v in vals:
        h.observe(float(v))
    exact = om.exact_quantile(sorted(float(v) for v in vals), q)
    lo, hi = h.quantile_bounds(q)
    assert lo <= exact < hi
    qv = h.quantile(q)
    assert h.min <= qv <= h.max
    assert exact <= qv <= exact * om.RESOLUTION * (1 + 1e-9)


def test_counter_and_gauge_contracts():
    c = om.Counter("c")
    c.inc()
    c.inc(3)
    assert c.value == 4 and c.to_json() == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    g = om.Gauge("g")
    assert g.to_json() is None
    g.set(2)
    assert g.value == 2.0


def test_fmt_seconds_none_safe():
    assert om.fmt_seconds(None) == "n/a"
    assert om.fmt_seconds(1.25) == "1.250"
    assert om.fmt_seconds(1.25, ".1f") == "1.2"


# ---------------------------------------------------------------------------
# registry: snapshot schema, round-trip, no-op overhead
# ---------------------------------------------------------------------------

def test_snapshot_schema_and_default_counters(tmp_path):
    rec = oreg.Recorder(clock=lambda: 0.0)
    doc = rec.snapshot()
    assert doc["schema"] == oreg.SCHEMA == "obsmetrics/v1"
    # stable key set: every default counter present at 0 on a fresh
    # recorder (a dryrun METRICS.json still carries the tenant counters)
    for name in oreg.DEFAULT_COUNTERS:
        assert doc["counters"][name] == 0
    path = tmp_path / "METRICS.json"
    written = rec.write(path)
    assert oreg.load_metrics(path) == written


def test_validate_snapshot_rejects_malformed():
    good = oreg.Recorder(clock=lambda: 0.0).snapshot()

    bad = copy.deepcopy(good)
    bad["schema"] = "obsmetrics/v2"
    with pytest.raises(ValueError, match="schema"):
        oreg.validate_snapshot(bad)

    bad = copy.deepcopy(good)
    bad["counters"]["x"] = -1
    with pytest.raises(ValueError, match="counter"):
        oreg.validate_snapshot(bad)

    bad = copy.deepcopy(good)
    bad["spans"] = [{"name": "x"}]       # missing timing keys
    with pytest.raises(ValueError, match="span"):
        oreg.validate_snapshot(bad)

    bad = copy.deepcopy(good)
    bad["spans"] = [{"name": "x", "t0": 1.0, "t1": 0.0, "dur_s": -1.0,
                     "depth": 0, "index": 0, "parent": None, "attrs": {}}]
    with pytest.raises(ValueError, match="negative"):
        oreg.validate_snapshot(bad)

    h = om.Histogram()
    h.observe(1.0)
    hj = h.to_json()
    hj["count"] = 2                      # buckets no longer partition
    bad = copy.deepcopy(good)
    bad["histograms"]["h"] = hj
    with pytest.raises(ValueError, match="partition"):
        oreg.validate_snapshot(bad)


def test_recording_scopes_and_restores():
    base = oreg.get_recorder()
    assert isinstance(base, oreg.NullRecorder)
    with oreg.recording() as rec:
        assert oreg.get_recorder() is rec and rec.enabled
        with oreg.recording() as inner:
            assert oreg.get_recorder() is inner
        assert oreg.get_recorder() is rec
    assert oreg.get_recorder() is base


def test_disabled_recorder_emits_nothing(annotations):
    """No-op overhead pin: with observability off (the default), an
    instrumented serve path emits zero events and opens no profiler
    annotation — the NullRecorder's instruments are shared no-op
    singletons."""
    from repro.launch.scheduler import WnnBatcher
    from test_sharded_serving import _artifact, _spec

    rec = oreg.get_recorder()
    assert isinstance(rec, oreg.NullRecorder) and not rec.enabled

    spec = _spec(8)
    art = _artifact(spec, seed=11)
    eng = WnnBatcher(art, slots=2, backend="auto")
    rng = np.random.default_rng(0)
    for _ in range(5):
        eng.submit(rng.integers(0, 2, (spec.total_bits,)).astype(np.uint8))
    eng.drain()
    assert eng.stats()["requests"] == 5

    assert oreg.get_recorder() is rec
    assert rec.events_emitted == 0 and rec.spans_dropped == 0
    assert annotations == []
    assert rec.counter("anything").value == 0
    assert rec.histogram("anything").count == 0

    # null spans still time (dryrun reads dur_s) but emit nothing
    with rec.span("x") as sp:
        pass
    assert sp.dur_s is not None and sp.dur_s >= 0.0
    assert rec.events_emitted == 0


def test_span_appears_in_profiler_trace(tmp_path):
    """A span of a real Recorder is also a host event of the same name on
    a `jax.profiler` trace, as long as the span."""
    import jax
    from jax.profiler import ProfileData

    import repro.obs.jaxhooks  # noqa: F401  (installs the annotations)

    jax.profiler.start_trace(str(tmp_path))
    try:
        with oreg.recording() as rec:
            with rec.span("obs.probe") as sp:
                time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [ev for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name == "obs.probe"]
    assert len(events) == 1
    assert abs(events[0].duration_ns * 1e-9 - sp.dur_s) < 1e-3


# ---------------------------------------------------------------------------
# integration: instrumented serve + train runs
# ---------------------------------------------------------------------------

def test_wnn_batcher_step_span_tree(annotations):
    """Every non-empty `WnnBatcher.step` records one `wnn.step` span over
    assemble, batch and one deliver per launch it completes; the batch
    over transfer and dispatch of its own launch, then one wait per
    launch completed. With one launch in flight, step k completes launch
    k-1, and the step that empties the queue completes its own launch
    too. Each child lies inside its parent; each span opens one profiler
    annotation of its name."""
    from repro.launch.scheduler import WnnBatcher
    from test_sharded_serving import _artifact, _spec

    spec = _spec(8)
    eng = WnnBatcher(_artifact(spec, seed=11), slots=2, backend="auto")
    rng = np.random.default_rng(0)
    with oreg.recording() as rec:
        for _ in range(5):
            eng.submit(rng.integers(0, 2, (spec.total_bits,)).astype(np.uint8))
        eng.drain()
        assert eng.step() == 0          # an empty step records nothing

    steps = [s for s in rec.spans if s.name == "wnn.step"]
    assert [(s.attrs["batch"], s.attrs["take"]) for s in steps] == [
        (0, 2), (1, 2), (2, 1)]
    completes = [[], [0], [1, 2]]       # launches each step waits for
    assert len(rec.spans) == sum(5 + 2 * len(c) for c in completes)
    for st, done in zip(steps, completes):
        assert st.parent is None
        parts = _children(rec, st)
        assert [c.name for c in parts] == [
            "wnn.assemble", "wnn.batch"] + ["wnn.deliver"] * len(done)
        launch = _children(rec, parts[1])
        assert [c.name for c in launch] == [
            "wnn.transfer", "wnn.dispatch"] + ["wnn.wait"] * len(done)
        assert [c.attrs["batch"] for c in launch[2:]] == done
        assert [c.attrs["batch"] for c in parts[2:]] == done
        assert all(isinstance(c.attrs["ready"], bool) for c in launch[2:])
        _inside(st, parts)
        _inside(parts[1], launch)
    waits = [s for s in rec.spans if s.name == "wnn.wait"]
    assert eng.stats()["overlapped"] == sum(s.attrs["ready"] for s in waits)
    assert annotations == [s.name for s in
                           sorted(rec.spans, key=lambda s: s.index)]


def test_train_uleen_spans_from_a_recorder_installed_mid_run(annotations):
    """`train_uleen` fetches the recorder per step: one installed from
    `on_step` records `train.step` over `train.feed` and `train.sync` for
    the steps after it; the steps before, under the NullRecorder, emit
    nothing and open no annotation."""
    from repro.launch import train as train_mod

    spec, statics, bits, labels = train_mod.uleen_smoke_problem(
        0, n_train=512)
    base = oreg.get_recorder()
    rec = oreg.Recorder()

    def on_step(step, params):
        if step == 1:
            oreg.set_recorder(rec)

    try:
        train_mod.train_uleen(
            spec, statics, bits, labels, steps_total=4, global_batch=64,
            lr=1e-3, grad_blocks=2, seed=0, on_step=on_step, verbose=False)
    finally:
        oreg.set_recorder(base)

    steps = [s for s in rec.spans if s.name == "train.step"]
    assert [s.attrs["step"] for s in steps] == [2, 3]
    assert len(rec.spans) == 3 * len(steps)
    for st in steps:
        parts = _children(rec, st)
        assert [c.name for c in parts] == ["train.feed", "train.sync"]
        _inside(st, parts)
    assert rec.counter("train.steps").value == 2
    assert base.events_emitted == 0
    assert annotations == [s.name for s in
                           sorted(rec.spans, key=lambda s: s.index)]


def test_tenant_batcher_stress_snapshot_reconciles(tmp_path):
    """Acceptance cell: a WnnTenantBatcher stress run under `recording()`
    writes a schema-valid METRICS.json whose tenant-cache counters equal
    the batcher's own stats, with latency histograms populated — and the
    scores stay bit-identical to the uninstrumented oracle."""
    import jax.numpy as jnp

    from repro.core import export
    from repro.launch.scheduler import WnnTenantBatcher
    from test_sharded_serving import _tenant_fleet

    spec, arts = _tenant_fleet(5, seed0=40)
    rng = np.random.default_rng(7)
    with oreg.recording(jsonl_path=tmp_path / "events.jsonl") as rec:
        tb = WnnTenantBatcher(capacity=2, slots=4, backend="auto")
        for a in arts:
            tb.add_tenant(a)
        submitted = {}
        for _ in range(30):
            tid = int(rng.integers(0, 5))
            row = rng.integers(0, 2, (spec.total_bits,)).astype(np.uint8)
            submitted[tb.submit(tid, row)] = (tid, row)
        results = tb.drain()
        st = tb.stats()
        doc = rec.write(tmp_path / "METRICS.json")

    # parity: instrumentation never touches traced values
    for r in results:
        tid, row = submitted[r.rid]
        expect = np.asarray(export.artifact_scores(
            arts[tid], jnp.asarray(row[None])))[0]
        np.testing.assert_array_equal(r.scores, expect)

    c = doc["counters"]
    assert c["serve.tenant.cache_hit"] == st["hits"]
    assert c["serve.tenant.cache_miss"] == st["misses"]
    assert c["serve.tenant.eviction"] == st["evictions"] > 0
    assert c["serve.tenant.admission"] == st["admissions"]
    assert c["jax.trace.batch_scores"] == st["traces"] == 1
    assert c["jax.trace.install"] == st["install_traces"] == 1

    hist = doc["histograms"]["serve.tenant.latency_s"]
    assert hist["count"] == st["requests"] == 30
    names = {s["name"] for s in doc["spans"]}
    assert "wnn.tenant_batch" in names and "tenant.install" in names

    loaded = oreg.load_metrics(tmp_path / "METRICS.json")
    assert loaded == doc
    assert otr.read_jsonl(tmp_path / "events.jsonl")


def test_train_uleen_exports_step_metrics(tmp_path):
    """A short train_uleen run under `recording()` exports the step-time
    histogram, train.steps counter, checkpoint-save spans, and the
    straggler EWMA gauge."""
    from repro.launch import train as train_mod

    spec, statics, bits, labels = train_mod.uleen_smoke_problem(
        0, n_train=512)
    with oreg.recording() as rec:
        out = train_mod.train_uleen(
            spec, statics, bits, labels, steps_total=4, global_batch=64,
            lr=1e-3, grad_blocks=2, seed=0,
            ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=2, verbose=False)
        doc = rec.snapshot()

    assert len(out["history"]) == 4
    assert doc["counters"]["train.steps"] == 4
    assert doc["histograms"]["train.step_s"]["count"] == 4
    assert doc["gauges"]["train.straggler_ewma_s"] is not None
    names = [s["name"] for s in doc["spans"]]
    assert "ckpt.save" in names and "ckpt.restore" in names
