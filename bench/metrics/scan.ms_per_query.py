"""Search wall time per query: sum of ``SearchStats.wall_s`` over the
window's flushes, in ms, over the queries answered."""


def read(run):
    q = run.window.queries
    return sum(f.stats.wall_s for f in run.window.flushes) * 1e3 / q if q else None
