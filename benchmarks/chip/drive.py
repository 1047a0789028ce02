"""The measured loop that drives a request server, and what a window did.

A server here is anything with `submit(request index) -> rid`,
`step() -> rows served`, `queued() -> int` and `done_at(rid) -> float`
(the host clock at which the request's answer reached the host). One
process, one thread: the loop tops the queue up to the backlog, then runs
one step of the server.

In the traced run each phase sits in a `jax.profiler.TraceAnnotation`
(`bench.submit`, `bench.step`), so the trace reduction can say what the
host was doing in each idle gap of the device.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

clock = time.perf_counter


@dataclasses.dataclass
class Window:
    """What one measured window did, on the host clock."""
    t0: float
    t1: float
    rids: np.ndarray                 # rid of each request, in order
    submitted: np.ndarray            # absolute submit time
    done: np.ndarray = None          # absolute answer time, after drain
    steps: list = dataclasses.field(default_factory=list)  # (t0, t1, n)
    examples: int = 0                # training examples in the window
    queued_at_close: int = 0         # requests still queued at t1

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def completed_in_window(self) -> int:
        return int(np.sum(self.done <= self.t1))

    def latencies(self) -> np.ndarray:
        """Seconds from submit to answer, of every request of the window."""
        return self.done - self.submitted


def annotator(traced: bool):
    """`name -> context manager`: a profiler annotation when traced."""
    if not traced:
        null = contextlib.nullcontext()
        return lambda name: null
    import jax
    return jax.profiler.TraceAnnotation


def _finish(server, win: Window) -> Window:
    """Answer everything still queued after the window, then read each
    request's answer time."""
    while server.queued():
        server.step()
    win.done = np.array([server.done_at(r) for r in win.rids])
    return win


def backlog(server, depth: int, seconds: float, *, traced: bool = False,
            begin=None) -> Window:
    """Keep at least `depth` requests queued all through the window, so
    every step launches full. `begin` is called just before the window
    opens."""
    note = annotator(traced)
    if begin is not None:
        begin()
    rids, sub, steps = [], [], []
    t0 = clock()
    end = t0 + seconds
    i = 0
    with note("bench.window"):
        while True:
            now = clock()
            if now >= end:
                break
            if server.queued() < depth:
                with note("bench.submit"):
                    while server.queued() < depth:
                        rids.append(server.submit(i))
                        sub.append(now)
                        i += 1
            with note("bench.step"):
                a = clock()
                got = server.step()
                steps.append((a, clock(), got))
    t1 = clock()
    win = Window(t0=t0, t1=t1, rids=np.array(rids),
                 submitted=np.array(sub), steps=steps,
                 queued_at_close=server.queued())
    return _finish(server, win)
