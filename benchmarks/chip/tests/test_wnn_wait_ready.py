"""The reader of `wnn_wait_ready_pct.rate`: the share of the window's
`wnn.wait` spans whose launch was ready when the wait began, on synthetic
spans and on a traced CPU run of the bulk-serving cell."""
import pytest

import run
from repro.obs.trace import Span
from test_program_spans import MS, inputs, read, serve_spans

NAME = "wnn_wait_ready_pct.rate"


def wait(index, t0_ms, **attrs):
    """One `wnn.wait` span of 1 ms, `t0_ms` after the window opens."""
    return Span(name="wnn.wait", t0=100.0 + t0_ms * MS,
                t1=100.0 + (t0_ms + 1) * MS, attrs=attrs, index=index)


def test_wait_ready_share_counts_the_ready_waits():
    spans = [wait(i, t0, batch=i, ready=ready)
             for i, (t0, ready) in enumerate(((10, True), (20, False),
                                              (30, True), (40, True)))]
    spans.append(wait(4, 200, batch=4, ready=False))   # opens after the window
    assert read(NAME, inputs(spans)) == pytest.approx(75.0)


@pytest.mark.parametrize("spans", [[], serve_spans(), [wait(0, 10, batch=0)]],
                         ids=["no_spans", "no_ready_attr", "batch_only"])
def test_wait_ready_share_is_none_without_the_attribute(spans):
    """A program that predates the `ready` attribute gives no reading."""
    assert read(NAME, inputs(spans)) is None


def test_traced_cpu_run_reports_the_ready_share(bench):
    """A traced run of the bulk-serving cell on the CPU at a test's size
    reads the share from the program's own spans."""
    from test_correct import CPU, small_cell
    cell = small_cell(bench, "uln-l.serve-max", 2 ** 31 + 5)
    res = run.run_cell(bench, cell, trace=True, device=CPU, t_start=0.0)
    assert res["correct"], res["checks"]
    assert 0 <= res["metrics"][NAME]["value"] <= 100
