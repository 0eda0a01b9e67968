"""Kernel: roofline time of the paged decode attention that the traced
window's decode tokens need (``flops.paged_attn_work``: each token at
position p reads p cached K/V rows in every layer) over the device time
of the paged-attention kernel's events in the trace."""
from bench import flops
from bench import trace_reduce



def is_kernel(name: str) -> bool:
    """The Pallas kernel carries no name of its own in the trace: it is
    the Mosaic custom call whose first operand is the scalar-prefetched
    page table (s32), the only such call on the served path."""
    return ("paged_attention" in name
            or ('custom_call_target="tpu_custom_call"' in name
                and "custom-call(s32[" in name))


def read(run):
    if run.summary is None:
        return None
    lo, hi = trace_reduce.window(run.trace["host"])
    t_kernel = sum(trace_reduce.op_seconds(run.trace["device"], lo, hi,
                                           is_kernel).values())
    need = 0.0
    for r, i in run.traced_tokens():
        if i > 0:
            ops, nbytes = flops.paged_attn_work(run.conf,
                                                len(r.plan.prompt) + i)
            need += flops.roofline_seconds(ops, nbytes, run.peak)
    if t_kernel <= 0 or need <= 0:
        return None
    return 100.0 * need / t_kernel
