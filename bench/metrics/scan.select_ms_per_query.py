"""Host time of the top-k cut per query answered, in ms: the sum of
``SearchStats.select_s`` (span ``scan.select``: the select's dispatch,
the blocking copy of the shortlists, which waits for the device, the
per-query thresholds and K-doubling retries) over the window's flushes.
Nothing where the program has no such field."""


def read(run):
    vals = [getattr(f.stats, "select_s", None) for f in run.window.flushes]
    q = run.window.queries
    if not q or not vals or None in vals:
        return None
    return 1e3 * sum(vals) / q
