"""Device idle time inside `train_uleen`'s feed (the `train.feed` spans,
placed on the trace by `program_spans.idle_seconds`), per step in the
window, in ms."""
import program_spans


def read(inputs):
    feeds = program_spans.in_window(inputs, "train.feed")
    idle = program_spans.idle_seconds(inputs, feeds) if feeds else None
    return None if idle is None else idle / len(feeds) * 1e3
