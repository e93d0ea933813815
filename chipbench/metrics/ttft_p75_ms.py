"""75th percentile, over every request due in the window, of due time to
first token (about 40 fall due in a window: the highest percentile with ten
of them beyond it).  The run goes on past the close until each has its first
token; one that never gets it is a failed request, not a sample."""

import numpy as np


def read(v):
    x = [r.first_token_at - r.arrived for r in v.due if r.first_token_at > 0]
    return float(np.percentile(x, 75) * 1e3) if x else None
