"""Host time of `train_uleen` feeding a step (the `train.feed` span: batch
rows, their `device_put`s, the step's rng), mean per step in the window,
in ms."""
import program_spans


def read(inputs):
    sec = program_spans.mean_seconds(inputs, "train.feed")
    return None if sec is None else sec * 1e3
