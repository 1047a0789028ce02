"""Host time of a `train_uleen` step outside its wait for the loss (each
`train.step` span less its `train.sync` child): the feed, the dispatch of
the step, the step hook; mean per step in the window, in ms."""
import numpy as np

import program_spans


def read(inputs):
    host = []
    for st in program_spans.in_window(inputs, "train.step"):
        sync = program_spans.child(inputs, st, "train.sync")
        if sync is not None:
            host.append(st.dur_s - sync.dur_s)
    return float(np.mean(host)) * 1e3 if host else None
