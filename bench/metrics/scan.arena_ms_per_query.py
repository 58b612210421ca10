"""Host time building the scan's arenas per query answered, in ms: the
sum of ``SearchStats.arena_s`` (span ``scan.arena``: probe dedup, the
candidate map, allocating and filling the arena and the query block or
LUTs, releasing them at the block's end) over the window's flushes.
Nothing where the program has no such field."""


def read(run):
    vals = [getattr(f.stats, "arena_s", None) for f in run.window.flushes]
    q = run.window.queries
    if not q or not vals or None in vals:
        return None
    return 1e3 * sum(vals) / q
