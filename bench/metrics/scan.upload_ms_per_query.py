"""Host time handing the arena and the query block or LUTs to the device
per query answered, in ms: the sum of ``SearchStats.upload_s`` (span
``scan.upload``) over the window's flushes.  Nothing where the program
has no such field."""


def read(run):
    vals = [getattr(f.stats, "upload_s", None) for f in run.window.flushes]
    q = run.window.queries
    if not q or not vals or None in vals:
        return None
    return 1e3 * sum(vals) / q
