"""Training examples of the optimizer steps completed inside the window,
over the window's length."""


def read(win) -> float:
    return win.examples / win.seconds
