"""Host time of `WnnBatcher.step` waiting for the scores (the `wnn.wait`
span: `np.asarray` of the batch program's result), mean per launch in the
window, in us."""
import program_spans


def read(inputs):
    sec = program_spans.mean_seconds(inputs, "wnn.wait")
    return None if sec is None else sec * 1e6
