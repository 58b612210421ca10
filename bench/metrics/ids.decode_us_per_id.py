"""Time decoding one id, in us: the sum of ``SearchStats.decode_s`` (span
``ids.decode``) over the sum of ``SearchStats.decode_ids`` (the ids those
decodes produced) across the window's flushes.  The speed of the decode
itself, whichever lists the window's cache misses were.  Nothing where the
program has no ``decode_ids`` field or decoded no id in the window."""


def read(run):
    secs, ids = [], []
    for f in run.window.flushes:
        secs.append(getattr(f.stats, "decode_s", None))
        ids.append(getattr(f.stats, "decode_ids", None))
    if not ids or None in secs or None in ids or not sum(ids):
        return None
    return 1e6 * sum(secs) / sum(ids)
