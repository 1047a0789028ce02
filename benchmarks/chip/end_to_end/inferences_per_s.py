"""Classifications whose scores reached the host inside the window, over
the window's length."""


def read(win) -> float:
    return win.completed_in_window() / win.seconds
