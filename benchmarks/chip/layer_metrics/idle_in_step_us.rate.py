"""Device idle time inside `WnnBatcher.step` (the `wnn.step` spans, placed
on the trace by `program_spans.idle_seconds`), per launch in the window,
in us: the part of the device's idle that the batcher's own step holds."""
import program_spans


def read(inputs):
    steps = program_spans.in_window(inputs, "wnn.step")
    idle = program_spans.idle_seconds(inputs, steps) if steps else None
    return None if idle is None else idle / len(steps) * 1e6
