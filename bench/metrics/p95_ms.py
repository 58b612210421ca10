"""95th percentile of request latency, in ms, over every request completed
in the window, from when the request was due (its client had the
previous answer) to results ready, so the loop's own lag is counted."""

import numpy as np


def read(run):
    lat = [a.late_s + a.latency_s for a in run.window.answers]
    return float(np.quantile(lat, 0.95)) * 1e3 if lat else None
