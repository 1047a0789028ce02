"""Arithmetic that several per-layer readers share."""
from __future__ import annotations

import work


def idle_pct(inputs):
    """Share of the traced window in which no op ran on the device."""
    if inputs.trace is None or not inputs.trace.devices:
        return None
    return 100.0 * inputs.trace.idle_share


def served_work(inputs) -> work.Work:
    """Work of every launch of the window, counted at the rows it served."""
    total = work.Work(0.0, 0.0)
    for _, _, n in inputs.window.steps:
        if n:
            total = total + work.serve_launch(inputs.cell.config, n)
    return total


def serve_least_seconds(inputs) -> float:
    p = inputs.peaks
    return served_work(inputs).least_seconds(p["int8_ops"],
                                             p["hbm_bytes"])[0]


# The packed WNN kernel's events, as the trace names them: the full HLO
# text of a Mosaic custom call. Until the kernel carries a name of its own,
# this matches every Mosaic kernel; the ULN-L batch program has no other.
PACKED_WNN_KERNEL = 'custom_call_target="tpu_custom_call"'


def kernel_seconds(inputs):
    """(device seconds of the packed kernel in the window, launches of
    the batch program in the window)."""
    if inputs.trace is None:
        return 0.0, 0
    launches = sum(1 for _, _, n in inputs.window.steps if n)
    return inputs.trace.kernel_seconds(PACKED_WNN_KERNEL), launches
