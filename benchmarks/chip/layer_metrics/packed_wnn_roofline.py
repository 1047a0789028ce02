"""The packed WNN kernel's share of its roofline, in %: the least time
its launches' work (`work.serve_launch`, counted from the geometry at the
rows served) could take at the chip's peak int8 operations or HBM bytes,
whichever bounds it, over the kernel's measured device time."""
import layer_common


def read(inputs):
    sec, _ = layer_common.kernel_seconds(inputs)
    if not sec:
        return None
    return 100.0 * layer_common.serve_least_seconds(inputs) / sec
