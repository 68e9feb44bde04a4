"""95th percentile of time to first token over the window's requests, from
the due time (open loop) or the send time (closed loop) to the first token,
waited for past the close."""
import numpy as np


def read(run):
    t = run.ttfts()
    return float(np.percentile(t, 95)) if t else None
