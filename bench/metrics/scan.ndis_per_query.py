"""Distance evaluations per query (``SearchStats.ndis``): the rows the
probes make the scan score."""


def read(run):
    q = run.window.queries
    return sum(f.stats.ndis for f in run.window.flushes) / q if q else None
