"""95th percentile of the gaps between consecutive output tokens, over every
gap that ends in the window."""
import numpy as np


def read(run):
    g = run.itl_gaps()
    return float(np.percentile(g, 95)) * 1e3 if g else None
