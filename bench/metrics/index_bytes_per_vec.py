"""Bytes the served index holds in memory per base vector, counted on its
objects by ``bench/footprint.py`` after the window."""


def read(run):
    return run.index_bytes / run.n
