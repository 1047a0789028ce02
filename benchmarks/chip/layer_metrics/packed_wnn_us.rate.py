"""Device time of the packed WNN Mosaic kernel (`kernels/packed_wnn.py`
-> `fused_wnn.wnn_lookup`) per launch of the batch program, in us:
summed over the kernel's events (one per submodel) in the window."""
import layer_common


def read(inputs):
    sec, launches = layer_common.kernel_seconds(inputs)
    return sec / launches * 1e6 if sec else None
