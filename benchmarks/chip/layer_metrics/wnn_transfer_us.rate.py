"""Host time of `WnnBatcher.step` handing the batch to the device (the
`wnn.transfer` span: `jnp.asarray`, or `device_put` on a mesh), mean per
launch in the window, in us."""
import program_spans


def read(inputs):
    sec = program_spans.mean_seconds(inputs, "wnn.transfer")
    return None if sec is None else sec * 1e6
