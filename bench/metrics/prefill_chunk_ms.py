"""Model step: device time of one prefill chunk, from the device's
program events named ``jit_serve_prefill_chunk`` in the traced window
over the chunks the engine ran in the traced steps (``prefill_chunks``)."""
from bench import program_trace as ptr


def read(run):
    pt = ptr.of(run)
    if pt is None or not pt["modules"]:
        return None
    lo, hi = ptr.window(pt)
    secs, n = ptr.module_seconds(pt, lo, hi, "serve_prefill_chunk")
    chunks = ptr.stats_delta(run, "prefill_chunks")
    ptr.log(f"prefill_chunk_ms: {secs:.4f} s of {n} chunk programs over "
            f"{chunks} prefill chunks")
    if secs <= 0 or chunks <= 0:
        return None
    return 1e3 * secs / chunks
