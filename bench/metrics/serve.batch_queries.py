"""Mean queries per flush of ``AnnService``, from the tickets' batch size."""

import numpy as np


def read(run):
    sizes = {a.batch_id: a.batch_size for a in run.window.answers}
    return float(np.mean(list(sizes.values()))) if sizes else None
