"""Id-list decodes (decoded-list cache misses) per query answered."""


def read(run):
    q = run.window.queries
    return sum(f.stats.decodes for f in run.window.flushes) / q if q else None
