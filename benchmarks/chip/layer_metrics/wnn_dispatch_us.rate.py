"""Host time of `WnnBatcher.step` in the jitted batch program until the call
returns (the `wnn.dispatch` span), mean per launch in the window, in us."""
import program_spans


def read(inputs):
    sec = program_spans.mean_seconds(inputs, "wnn.dispatch")
    return None if sec is None else sec * 1e6
