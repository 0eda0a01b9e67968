"""Operations and bytes that served work needs, from the configuration
and the lengths served, never from the program's shapes: the same work
reads the same whatever implements it. The counts are the model
family's (``bench/families``); this module finds the family by the
configuration's ``model_type``.

Conventions: a multiply-add is 2 operations. "Context" is the number of
key/value positions a token attends to, itself included.
"""
from __future__ import annotations

from bench import families


def decode_token_flops(conf: dict, context: int) -> int:
    """One decode token over ``context`` positions."""
    return families.of(conf).decode_token_flops(conf, context)


def prefill_flops(conf: dict, prompt_len: int) -> int:
    """A whole prompt, and the head once, for its first token."""
    return families.of(conf).prefill_flops(conf, prompt_len)


def paged_attn_work(conf: dict, context: int, itemsize: int = 2):
    """(operations, bytes) of decode attention for one token over
    ``context`` cached positions, all layers."""
    return families.of(conf).paged_attn_work(conf, context, itemsize)


def roofline_seconds(ops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(ops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
