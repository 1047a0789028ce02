"""Host time of `WnnBatcher.step` handing out the results (the `wnn.deliver`
span: per row the scores, argmax, answer time and latency histogram),
mean per launch in the window, in us."""
import program_spans


def read(inputs):
    sec = program_spans.mean_seconds(inputs, "wnn.deliver")
    return None if sec is None else sec * 1e6
