"""Device-select runs per query block selected on the device: the sum
of ``SearchStats.select_calls`` (K-doubling retries included) over the
sum of ``device_select``.  1.0 when no shortlist had to widen; nothing
where no block selected on the device or the program has no such
field."""


def read(run):
    calls = [getattr(f.stats, "select_calls", None)
             for f in run.window.flushes]
    if not calls or None in calls:
        return None
    blocks = sum(f.stats.device_select for f in run.window.flushes)
    return sum(calls) / blocks if blocks else None
