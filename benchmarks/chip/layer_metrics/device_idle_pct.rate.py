"""Share of the traced window in which no op ran on the device, in %."""
import layer_common


def read(inputs):
    return layer_common.idle_pct(inputs)
