"""Engine host loop: device idle time inside the engine's ``serve.step``
spans of the traced window, per engine step. Also logs the idle time
charged to the innermost open host span (``bench.*`` or ``serve.*``)."""
from bench import program_trace as ptr
from bench import trace_reduce


def read(run):
    pt = ptr.of(run)
    if pt is None or not pt["ops"]:
        return None
    lo, hi = ptr.window(pt)
    steps = [(a, b) for a, b in ptr.host_spans(pt, ptr.STEP_SPAN)
             if lo <= a < hi]
    if not steps:
        return None
    dev = [(n, s, d) for n, s, d, _ in pt["ops"]]
    idle = ptr.idle_inside([(s, s + d) for _, s, d in dev], steps, lo, hi)
    by_span = trace_reduce.top(trace_reduce.gaps_by_host(dev, pt["host"],
                                                         lo, hi), 16)
    ptr.log("idle by host span (s): " + ", ".join(
        f"{n} {v:.4f}" for n, v in by_span))
    ptr.log(f"step_idle_ms: {idle:.4f} s idle in {len(steps)} serve.step "
            f"spans")
    return 1e3 * idle / len(steps)
