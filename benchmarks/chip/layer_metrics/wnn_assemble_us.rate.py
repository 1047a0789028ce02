"""Host time of `WnnBatcher.step` assembling its batch (the `wnn.assemble`
span: the batch buffer, queue pops, row copies), mean per launch in the
window, in us."""
import program_spans


def read(inputs):
    sec = program_spans.mean_seconds(inputs, "wnn.assemble")
    return None if sec is None else sec * 1e6
