"""The whole train step's share of the chip's peak, in %: the least time
the window's steps (`work.train_step`, counted from the geometry) could
take at the chip's peak bf16 operations or HBM bytes, whichever bounds
it, over the traced window's length."""
import work


def read(inputs):
    steps = inputs.window.steps
    if inputs.trace is None or not inputs.trace.devices or not steps:
        return None
    p = inputs.peaks
    total = work.Work(0.0, 0.0)
    for _, _, rows in steps:
        total = total + work.train_step(inputs.cell.config, rows)
    least, _ = total.least_seconds(p["bf16_flops"], p["hbm_bytes"])
    return 100.0 * least / inputs.trace.window_s
