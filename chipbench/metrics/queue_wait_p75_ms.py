"""75th percentile, over the requests due in the window that got a lane,
of due time to lane assignment (the scheduler's queue)."""

import numpy as np


def read(v):
    x = [r.admitted_at - r.arrived for r in v.due if r.admitted_at > 0]
    return float(np.percentile(x, 75) * 1e3) if x else None
