"""Reduction of a `jax.profiler` trace (`.xplane.pb`) to device metrics.

Reads the trace with `jax.profiler.ProfileData` only, and gives:

- the traced window: the host event named `bench.window` that the
  harness opens around its measured loop;
- device busy time: the union of the op intervals on each chip's
  `XLA Ops` line, clipped to the window and averaged over the chips that
  ran any op;
- device time per op (events named by the op's HLO text), and the time
  of the ops that match a kernel;
- the idle gaps between device ops, each attributed to the innermost
  `bench.*` host annotation open at the gap's midpoint: what the host was
  doing while the device waited.

Times are in seconds unless the name says `_ns`.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str             # on a chip's op line: the op's whole HLO text
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


_OPCODE = re.compile(r"[\])}] ([a-z][a-z0-9_-]*)\(")


def op_label(name: str) -> str:
    """A short label of an op's HLO text: its instruction name and opcode,
    "%fusion.15 fusion" for "%fusion.15 = s8[202240]{...} fusion(...)"."""
    head, _, rest = name.partition(" = ")
    m = _OPCODE.search(rest)
    return f"{head} {m.group(1)}" if m else head[:80]


def _union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class Reduction:
    window_ns: tuple                  # (start, end) of bench.window
    devices: list                     # device plane names
    ops: dict                         # device -> [Event] on XLA Ops
    host: list                        # bench.* host annotations
    busy_ns: dict                     # device -> merged busy intervals

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Device busy seconds in the window, averaged over devices."""
        if not self.devices:
            return 0.0
        tot = sum(sum(e - s for s, e in self.busy_ns[d])
                  for d in self.devices)
        return tot * 1e-9 / len(self.devices)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_totals(self) -> collections.Counter:
        """Device seconds per op (`op_label`), summed over devices."""
        tot = collections.Counter()
        for d in self.devices:
            for ev in self.ops[d]:
                tot[op_label(ev.name)] += ev.dur_ns * 1e-9
        return tot

    def kernel_seconds(self, needle: str) -> float:
        """Device seconds of op events whose name (the op's HLO text)
        holds `needle`, averaged over devices; 0.0 where none matches."""
        if not self.devices:
            return 0.0
        tot = sum(ev.dur_ns for d in self.devices for ev in self.ops[d]
                  if needle in ev.name)
        return tot * 1e-9 / len(self.devices)

    def idle_gaps(self):
        """[(seconds, host annotation)] for every gap between busy
        intervals of the first device inside the window, the window's
        edges included."""
        if not self.devices:
            return []
        lo, hi = self.window_ns
        busy = self.busy_ns[self.devices[0]]
        edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
        host = sorted(self.host, key=lambda ev: ev.start_ns)
        starts = [ev.start_ns for ev in host]
        out = []
        for i in range(0, len(edges), 2):
            g0, g1 = edges[i], edges[i + 1]
            if g1 <= g0:
                continue
            mid = 0.5 * (g0 + g1)
            label, best = "unannotated", None
            j = bisect.bisect_right(starts, mid)
            for ev in host[max(0, j - 64):j]:
                if ev.end_ns >= mid and (best is None
                                         or ev.dur_ns < best.dur_ns):
                    best = ev
            if best is not None:
                label = best.name
            out.append(((g1 - g0) * 1e-9, label))
        return out

    def breakdown(self, top: int = 10) -> dict:
        """The `breakdown` of a traced run's result line: the device ops
        that took most time, and idle time per host annotation."""
        ops = self.op_totals().most_common(top)
        idle = collections.Counter()
        for sec, label in self.idle_gaps():
            idle[label] += sec
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle.most_common(top)]}


# A chip's plane; not the host's, and not "/device:CUSTOM:..." planes such
# as the Megascale trace, which hold no ops of a chip.
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def is_device_plane(name: str) -> bool:
    return DEVICE_PLANE.match(name) is not None


def reduce_profile(profile) -> Reduction:
    """`Reduction` of a `jax.profiler.ProfileData`."""
    window = None
    host, ops = [], {}
    for plane in profile.planes:
        dev = is_device_plane(plane.name)
        if dev:
            ops[plane.name] = []
        for line in plane.lines:
            for ev in line.events:
                if dev and line.name == OPS_LINE:
                    ops[plane.name].append(
                        Event(ev.name, ev.start_ns, ev.duration_ns))
                elif not dev and ev.name.startswith("bench."):
                    e = Event(ev.name, ev.start_ns, ev.duration_ns)
                    if ev.name == WINDOW:
                        window = (e.start_ns, e.end_ns)
                    host.append(e)
    if window is None:
        raise ValueError(f"the trace has no {WINDOW!r} host annotation")
    devices = sorted(d for d in ops if ops[d])    # the chips the run used
    lo, hi = window

    def inside(events):
        return [e for e in events if e.end_ns > lo and e.start_ns < hi]

    ops = {d: inside(v) for d, v in ops.items()}
    busy = {d: _clip(_union((e.start_ns, e.end_ns) for e in ops[d]), lo, hi)
            for d in devices}
    return Reduction(window_ns=window, devices=devices, ops=ops,
                     host=inside(host), busy_ns=busy)


def reduce_dir(log_dir: str) -> Reduction:
    """`Reduction` of the one `.xplane.pb` under a profiler log dir."""
    import jax
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"{len(files)} .xplane.pb files under {log_dir}")
    return reduce_profile(jax.profiler.ProfileData.from_file(files[0]))
