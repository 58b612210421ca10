"""Share of the traced window with no operation running on the device."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0 or not run.trace.ops:
        return None
    return 1.0 - run.trace.busy_s() / run.trace.window_s
