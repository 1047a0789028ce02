"""Share of the window's `wnn.wait` spans (`WnnBatcher` waiting for a
launch's scores) whose launch had finished on the device when the wait
began (attr `ready`), in %: how often the launch overlapped the host's
work. None where the spans carry no `ready`."""
import program_spans


def read(inputs):
    ready = [s.attrs["ready"]
             for s in program_spans.in_window(inputs, "wnn.wait")
             if "ready" in s.attrs]
    return 100.0 * sum(ready) / len(ready) if ready else None
