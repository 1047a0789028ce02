"""The readers of the program's own spans, on synthetic inputs: known
spans give known means, device idle inside a span is cut at the span's
edges and at the window's, and missing spans give None."""
import numpy as np
import pytest

import drive
import run
import trace_reduce
from repro.obs.trace import Span

SERVE = ("wnn_assemble_us.rate", "wnn_transfer_us.rate",
         "wnn_dispatch_us.rate", "wnn_wait_us.rate", "wnn_deliver_us.rate")
TRAIN = ("train_feed_ms", "train_host_ms")
IDLE = ("idle_in_step_us.rate", "train_idle_in_feed_ms")
DEVICE = "/device:TPU:0"
MS = 1e-3


class Spans(list):
    """Spans as a Recorder keeps them, made from host times in ms after
    the window opens at host time 100 s."""

    def add(self, name, t0_ms, t1_ms, parent=None):
        sp = Span(name=name, t0=100.0 + t0_ms * MS, t1=100.0 + t1_ms * MS,
                  index=len(self),
                  parent=None if parent is None else parent.index)
        self.append(sp)
        return sp


def inputs(spans, busy_ms=None):
    """LayerInputs of a 100 ms window at host time 100 s, traced from 5 ms
    on the trace's clock, the first device busy over `busy_ms` (trace
    ms) or, with None, no trace at all."""
    win = drive.Window(t0=100.0, t1=100.1, rids=np.arange(0),
                       submitted=np.arange(0))
    trace = None
    if busy_ms is not None:
        trace = trace_reduce.Reduction(
            window_ns=(5e6, 105e6), devices=[DEVICE], ops={DEVICE: []},
            host=[], busy_ns={DEVICE: [[a * 1e6, b * 1e6]
                                       for a, b in busy_ms]})
    return run.LayerInputs(cell=None, window=win, spans=list(spans),
                           trace=trace, peaks={})


def read(name, inp):
    return run.load_module("layer_metrics", name).read(inp)


def launch(spans, t0, assemble, transfer, dispatch, wait, deliver):
    """One `WnnBatcher.step` span tree, its parts' lengths in ms."""
    end = t0 + assemble + transfer + dispatch + wait + deliver
    step = spans.add("wnn.step", t0, end)
    t = t0 + assemble
    spans.add("wnn.assemble", t0, t, step)
    batch = spans.add("wnn.batch", t, t + transfer + dispatch + wait, step)
    for name, ms in (("wnn.transfer", transfer), ("wnn.dispatch", dispatch),
                     ("wnn.wait", wait)):
        spans.add(name, t, t + ms, batch)
        t += ms
    spans.add("wnn.deliver", t, end, step)
    return step


def serve_spans():
    spans = Spans()
    launch(spans, 10, 3.0, 1.0, 0.5, 5.5, 10.0)
    launch(spans, 50, 5.0, 3.0, 1.5, 2.5, 6.0)
    spans.add("wnn.assemble", -10, 990)      # opens before the window
    spans.add("wnn.assemble", 200, 1200)     # opens after it
    return spans


def train_spans():
    spans = Spans()
    for t0, feed, sync0, sync1, t1 in ((10, 12, 20, 29, 30),
                                       (40, 44, 55, 65, 70)):
        step = spans.add("train.step", t0, t1)
        spans.add("train.feed", t0, feed, step)
        spans.add("train.sync", sync0, sync1, step)
    return spans


def test_known_spans_give_known_means():
    got = {n: read(n, inputs(serve_spans())) for n in SERVE}
    want = dict(zip(SERVE, (4000.0, 2000.0, 1000.0, 4000.0, 8000.0)))
    assert got == pytest.approx(want, rel=1e-6)
    got = {n: read(n, inputs(train_spans())) for n in TRAIN}
    # the step less its sync: 20 - 9 and 30 - 10 ms
    assert got == pytest.approx({"train_feed_ms": 3.0,
                                 "train_host_ms": 15.5}, rel=1e-6)


def test_idle_is_cut_at_the_span_and_window_edges():
    # On the trace the steps lie at 15-35 ms and 55-73 ms. Busy 10-20 ms
    # and 30-40 ms straddle the first step's edges (5 ms of each inside),
    # 60-62 ms lies inside the second: idle 10 ms + 16 ms.
    busy = [(10, 20), (30, 40), (60, 62), (80, 90)]
    assert read("idle_in_step_us.rate", inputs(serve_spans(), busy)) == (
        pytest.approx(13000.0, rel=1e-6))
    # A third step over 90-190 ms on the host, 95-195 ms on the trace,
    # counts only up to the window's end at 105 ms: 10 ms idle.
    spans = serve_spans()
    launch(spans, 90, 10.0, 10.0, 10.0, 10.0, 60.0)
    assert read("idle_in_step_us.rate", inputs(spans, busy)) == (
        pytest.approx((10 + 16 + 10) / 3 * 1e3, rel=1e-6))
    # feeds at 15-17 and 45-49 ms: busy 16-40 ms covers 1 ms of the first
    assert read("train_idle_in_feed_ms",
                inputs(train_spans(), [(16, 40)])) == (
        pytest.approx((1 + 4) / 2, rel=1e-6))


def test_missing_spans_give_none():
    for name in SERVE + TRAIN + IDLE:
        assert read(name, inputs([], [(10, 20)])) is None
    # the parent's spans: one `wnn.batch` per launch, nothing inside it
    only_batch = Spans()
    only_batch.add("wnn.batch", 10, 20)
    for name in SERVE + IDLE:
        assert read(name, inputs(only_batch, [(10, 20)])) is None
    # spans but no device trace: the span readers read, the idle ones not
    assert read("wnn_wait_us.rate", inputs(serve_spans())) is not None
    assert read("idle_in_step_us.rate", inputs(serve_spans())) is None
    assert read("train_idle_in_feed_ms", inputs(train_spans())) is None


@pytest.mark.parametrize("workload,names", [("uln-l.serve-max", SERVE),
                                            ("uln-l.train", TRAIN)])
def test_traced_cpu_run_reports_the_span_metrics(bench, workload, names):
    """A traced run of the cell, on the CPU at a test's size, reads every
    span metric of the cell from the program; the CPU trace has no device
    plane, so the idle readers report nothing."""
    from test_correct import CPU, small_cell, tiny_train_cell
    seed = 2 ** 31 + 5
    cell = (small_cell(bench, workload, seed) if workload == "uln-l.serve-max"
            else tiny_train_cell(bench, seed))
    res = run.run_cell(bench, cell, trace=True, device=CPU, t_start=0.0)
    assert res["correct"], res["checks"]
    for name in names:
        assert res["metrics"][name]["value"] > 0
    assert not set(IDLE) & set(res["metrics"])
