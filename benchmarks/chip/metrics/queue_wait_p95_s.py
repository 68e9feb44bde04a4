"""95th percentile over the window's requests of the wait from due (or
sent) to the tick that admitted the request into a row; one not admitted by
the close counts with its wait so far.  It is cut at the close because this
reader runs in the traced run, where stopping the profiler there holds the
host for seconds."""
import numpy as np


def read(run):
    w = [min(r["admitted"] if r["admitted"] is not None else run.we, run.we)
         - r["due"] for r in run.window_requests]
    return float(np.percentile(w, 95)) if w else None
