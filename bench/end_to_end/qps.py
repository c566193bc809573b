"""Requests answered inside the window, per second of the window."""
UNIT = "q/s"


def read(run):
    w = run.window
    return w.completed_in_window() / w.seconds
