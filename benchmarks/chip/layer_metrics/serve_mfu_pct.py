"""The whole serve step's share of the chip's peak, in %: the least time
the window's classifications (`work.serve_launch` per launch, at the rows
served) could take at the chip's peak int8 operations or HBM bytes,
whichever bounds it, over the traced window's length."""
import layer_common


def read(inputs):
    if inputs.trace is None or not inputs.trace.devices:
        return None
    least = layer_common.serve_least_seconds(inputs)
    return 100.0 * least / inputs.trace.window_s if least else None
