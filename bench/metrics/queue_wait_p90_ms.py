"""Scheduler: 90th percentile over the counted requests of the wait from
the engine's queue (``Request.arrived_at``) to a slot
(``Request.admitted_at``, stamped by the engine's ``_admit``)."""
from bench import loadgen
from bench import program_trace as ptr


def read(run):
    w = [r.req.admitted_at - r.req.arrived_at for r in run.recs
         if r.counted and r.req is not None
         and getattr(r.req, "admitted_at", None) is not None]
    ptr.log(f"queue_wait_p90_ms: {len(w)} requests")
    return loadgen.quantile(w, 0.9) * 1e3 if w else None
