"""Scorer and select programs first dispatched inside the window: the
sum of ``SearchStats.new_shapes`` (span ``scan.compile``) over the
window's flushes; each traced and compiled, or loaded from the
persistent cache, in the window.  0 is a reading; nothing where the
program has no such field."""


def read(run):
    vals = [getattr(f.stats, "new_shapes", None) for f in run.window.flushes]
    if not vals or None in vals:
        return None
    return sum(vals)
