"""Readings of the program's own `repro.obs` spans, for per-layer metrics.

A span belongs to the window when its `t0` falls inside it. Each reading
is None where the window holds none of the spans it reads: a program
without them (one that predates them, or a path that does not record
them) reports nothing rather than a zero.

`idle_seconds` places spans on the trace's timeline. The harness opens
its `bench.window` annotation within microseconds of `window.t0` on the
host clock, so a span at host time t sits at
`t * 1e9 + trace.window_ns[0] - window.t0 * 1e9` on the trace. The device
is idle inside the span wherever the first device's busy intervals
(`trace.busy_ns`) do not cover it; the span is clipped to the window,
outside which the trace keeps no busy time.
"""
from __future__ import annotations

import bisect

import numpy as np


def in_window(inputs, name: str) -> list:
    """The spans named `name` that open inside the window, in order."""
    win = inputs.window
    return sorted((s for s in inputs.spans
                   if s.name == name and win.t0 <= s.t0 <= win.t1),
                  key=lambda s: s.t0)


def mean_seconds(inputs, name: str):
    """Mean duration of the window's spans named `name`, or None."""
    spans = in_window(inputs, name)
    return float(np.mean([s.dur_s for s in spans])) if spans else None


def child(inputs, parent, name: str):
    """`parent`'s direct child named `name`, or None."""
    return next((s for s in inputs.spans
                 if s.parent == parent.index and s.name == name), None)


def idle_seconds(inputs, spans):
    """Seconds in which the first device ran no op while one of `spans`
    (which must not overlap) was open, or None without a device trace."""
    trace = inputs.trace
    if trace is None or not trace.devices:
        return None
    lo, hi = trace.window_ns
    shift = lo - inputs.window.t0 * 1e9
    busy = trace.busy_ns[trace.devices[0]]       # merged and sorted
    starts = [s for s, _ in busy]
    idle = 0.0
    for sp in spans:
        a = max(sp.t0 * 1e9 + shift, lo)
        b = min(sp.t1 * 1e9 + shift, hi)
        if b <= a:
            continue
        covered = 0.0
        j = max(0, bisect.bisect_right(starts, a) - 1)
        while j < len(busy) and busy[j][0] < b:
            covered += max(0.0, min(busy[j][1], b) - max(busy[j][0], a))
            j += 1
        idle += (b - a) - covered
    return idle * 1e-9
