"""Operations and bytes that served work needs, from the configuration
and the lengths served, never from the program's shapes: the same work
reads the same whatever implements it.

Conventions: a multiply-add is 2 operations. "Context" is the number of
key/value positions a token attends to, itself included.
"""
from __future__ import annotations


def _dims(conf: dict):
    d, L = conf["hidden_size"], conf["num_hidden_layers"]
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf.get("head_dim") or d // H
    return d, L, H, KV, hd, conf["intermediate_size"], conf["vocab_size"]


def layer_matmul_params(conf: dict) -> int:
    """Matrix parameters of the layer stack (no embedding, no head)."""
    d, L, H, KV, hd, ff, _ = _dims(conf)
    return L * (d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff)


def head_flops(conf: dict) -> int:
    d, *_, V = _dims(conf)
    return 2 * d * V


def attn_flops(conf: dict, context: int) -> int:
    """Scores and weighted sum over ``context`` positions, all layers."""
    d, L, H, KV, hd, ff, V = _dims(conf)
    return 4 * L * H * hd * int(context)


def decode_token_flops(conf: dict, context: int) -> int:
    """One decode token: the layer matmuls, the head and attention."""
    return (2 * layer_matmul_params(conf) + head_flops(conf)
            + attn_flops(conf, context))


def prefill_flops(conf: dict, prompt_len: int) -> int:
    """A whole prompt: layer matmuls per token, causal attention (token i
    attends to i positions) and the head once, for the first token."""
    P = int(prompt_len)
    d, L, H, KV, hd, ff, V = _dims(conf)
    return (2 * layer_matmul_params(conf) * P + head_flops(conf)
            + 4 * L * H * hd * P * (P + 1) // 2)


def paged_attn_work(conf: dict, context: int, itemsize: int = 2):
    """(operations, bytes) of decode attention for one token over
    ``context`` cached positions, all layers: q.k and p.v, reading every
    cached K and V row once and q and the output once per head."""
    d, L, H, KV, hd, ff, V = _dims(conf)
    c = int(context)
    ops = 4 * L * H * hd * c
    nbytes = L * itemsize * (2 * KV * hd * c + 2 * H * hd)
    return ops, nbytes


def roofline_seconds(ops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(ops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
