"""95th percentile of every gap between a request's consecutive output
tokens whose later token was harvested in the window, over all lanes."""

import numpy as np


def read(v):
    gaps = [t - r.token_times[j - 1] for r, j, t in v.window_tokens()
            if j >= 1]
    return float(np.percentile(gaps, 95) * 1e3) if gaps else None
