"""Start of the process to the start of the window: data, training,
build and warm-up."""


def read(run):
    return run.setup_s
