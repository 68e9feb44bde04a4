"""95th percentile over the window's requests of the wait from due (or
sent) to the engine's own admission time (``Request.admitted_at``); one not
admitted by the close counts with its wait so far, cut at the close as
``queue_wait_p95_s`` is."""
import numpy as np


def read(run):
    reqs = run.window_requests
    if not reqs or not all("admitted_at" in r for r in reqs):
        return None
    w = [min(run.we if r["admitted_at"] is None else r["admitted_at"],
             run.we) - r["due"] for r in reqs]
    return float(np.percentile(w, 95))
