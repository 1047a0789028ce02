"""`WnnBatcher.step` host time per launch outside its `wnn.batch` span
(queue pops, the batch copy, per-result bookkeeping): the step's wall
time on the host clock minus the span's, mean over the window's launches
in us, the host's share of a full batch's cost. None where steps and
spans do not pair one to one."""
import numpy as np


def read(inputs):
    win = inputs.window
    steps = [(a, b) for a, b, n in win.steps if n]
    spans = [s for s in inputs.spans
             if s.name == "wnn.batch" and win.t0 <= s.t0 <= win.t1]
    if not steps or len(steps) != len(spans):
        return None
    host = [(b - a) - s.dur_s for (a, b), s in zip(steps, spans)]
    return float(np.mean(host)) * 1e6
