"""Queries answered in the window over the window's length."""


def read(run):
    w = run.window
    return w.queries / (w.end_s - w.start_s)
