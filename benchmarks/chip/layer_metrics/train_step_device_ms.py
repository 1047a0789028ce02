"""Device busy time per optimizer step in the traced window, in ms: the
union of the device's op intervals over the steps completed in it."""


def read(inputs):
    steps = len(inputs.window.steps)
    if inputs.trace is None or not inputs.trace.devices or not steps:
        return None
    return inputs.trace.busy_s / steps * 1e3
