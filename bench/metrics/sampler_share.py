"""Sampler: share of the decode span's device time spent in ops charged
to the named scope ``sampler`` (``lm.decode_span`` opens it around the
sampler's call) in the traced window. An op the compiler made, with no
scope of its own (the sort and fusion that the keep-mask scatter becomes
on the TPU), is charged by what it is computed from
(``program_trace.charged_scopes``)."""
from bench import program_trace as ptr

PROGRAM = "serve_decode_span"


def read(run):
    pt = ptr.of(run)
    if pt is None or not pt["modules"]:
        return None
    lo, hi = ptr.window(pt)
    span, _ = ptr.module_seconds(pt, lo, hi, PROGRAM)
    samp = ptr.scope_seconds(pt, lo, hi, PROGRAM, "sampler")
    ptr.log(f"sampler_share: {samp:.4f} s under scope sampler of "
            f"{span:.4f} s of {PROGRAM}")
    if span <= 0 or samp <= 0:
        return None
    return 100.0 * samp / span
