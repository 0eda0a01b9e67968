"""Model step: device time of one decode step, from the device's program
events named ``jit_serve_decode_span`` in the traced window over the
decode steps those spans ran (``decode_steps``, the engine's counter,
grown over the traced steps). Also logs each program's device time and
the busy time that no named program covers."""
from bench import program_trace as ptr


def read(run):
    pt = ptr.of(run)
    if pt is None or not pt["modules"]:
        return None
    lo, hi = ptr.window(pt)
    secs, n = ptr.module_seconds(pt, lo, hi, "serve_decode_span")
    steps = ptr.stats_delta(run, "decode_steps")
    split = ptr.module_split(pt, lo, hi)
    ptr.log("programs (device s in the traced window): " + ", ".join(
        f"{k} {v:.4f}" for k, v in sorted(split.items(),
                                          key=lambda kv: -kv[1])))
    ptr.log(f"decode_step_ms: {secs:.4f} s of {n} decode spans over "
            f"{steps} decode steps")
    if secs <= 0 or steps <= 0:
        return None
    return 1e3 * secs / steps
