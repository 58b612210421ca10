"""Megabytes handed to the device per query answered: the sum of
``SearchStats.upload_bytes`` (arena, query block or LUTs, and the
device select's metadata on each run) over the window's flushes.
Nothing where the program has no such field."""


def read(run):
    vals = [getattr(f.stats, "upload_bytes", None) for f in run.window.flushes]
    q = run.window.queries
    if not q or not vals or None in vals:
        return None
    return sum(vals) / 1e6 / q
