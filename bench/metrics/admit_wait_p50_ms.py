"""Front end: median wait from a request's due time to its admission
into the engine's queue (``Request.arrived_at``, stamped by the engine's
``try_submit``), over the requests due in the window."""
import numpy as np


def read(run):
    w = [r.req.arrived_at - r.due for r in run.recs
         if r.counted and r.req is not None and r.req.arrived_at]
    return float(np.median(w) * 1e3) if w else None
