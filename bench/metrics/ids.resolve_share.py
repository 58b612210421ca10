"""Share of search wall time spent in late id resolution
(``SearchStats.id_resolve_s`` over ``wall_s``)."""


def read(run):
    wall = sum(f.stats.wall_s for f in run.window.flushes)
    res = sum(f.stats.id_resolve_s for f in run.window.flushes)
    return res / wall if wall > 0 else None
