"""Time decoding id lists per query answered, in ms: the sum of
``SearchStats.decode_s`` (span ``ids.decode``, the decoded-list cache's
misses; part of ``id_resolve_s``) over the window's flushes.  Nothing
where the program has no such field."""


def read(run):
    vals = [getattr(f.stats, "decode_s", None) for f in run.window.flushes]
    q = run.window.queries
    if not q or not vals or None in vals:
        return None
    return 1e3 * sum(vals) / q
