"""Host time of the exact re-score per query answered, in ms: the sum of
``SearchStats.rescore_s`` (span ``scan.rescore``) over the window's
flushes.  Nothing where the program has no such field."""


def read(run):
    vals = [getattr(f.stats, "rescore_s", None) for f in run.window.flushes]
    q = run.window.queries
    if not q or not vals or None in vals:
        return None
    return 1e3 * sum(vals) / q
