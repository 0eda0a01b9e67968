"""Chunked prefill: 90th percentile over the counted requests of the
time from a slot (``Request.admitted_at``) to the first token
(``Request.first_token_at``, stamped by the engine's ``_emit``)."""
from bench import loadgen
from bench import program_trace as ptr


def read(run):
    w = [r.req.first_token_at - r.req.admitted_at for r in run.recs
         if r.counted and r.req is not None
         and getattr(r.req, "admitted_at", None) is not None
         and getattr(r.req, "first_token_at", None) is not None]
    ptr.log(f"prefill_wait_p90_ms: {len(w)} requests")
    return loadgen.quantile(w, 0.9) * 1e3 if w else None
