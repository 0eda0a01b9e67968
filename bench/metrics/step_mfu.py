"""Model step: operations that the tokens served in the traced window
need, over the traced window and the chip's peak. A decode token counts
the layer matmuls, the head and attention over its context; a first
token counts its whole prompt's prefill (``bench/flops.py``)."""
from bench import flops


def read(run):
    if run.summary is None:
        return None
    ops = 0.0
    for r, i in run.traced_tokens():
        P = len(r.plan.prompt)
        ops += (flops.prefill_flops(run.conf, P) if i == 0
                else flops.decode_token_flops(run.conf, P + i))
    if ops == 0:
        return None
    return 100.0 * ops / run.summary["window_s"] / run.peak["bf16_flops"]
