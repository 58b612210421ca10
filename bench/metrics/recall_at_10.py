"""Intersection recall@10 of every query answered in the window, against
exact brute force over the whole base (computed by the benchmark)."""


def read(run):
    return run.recall_at(10)
