"""The dense decoder family: Qwen2 and Qwen3 (pre-RMSNorm blocks,
grouped-query causal attention with the rotary embedding on the two
halves of each head, SwiGLU MLP; q/k/v biases and per-head q/k RMSNorm
where the configuration has them).

Canonical weights, stacked over layers:

    embed [V, d]   head [d, V]   final_norm [d]
    layers: norm1, norm2 [L, d]; wq [L, d, H*hd]; wk, wv [L, d, KV*hd];
            wo [L, H*hd, d]; bq, bk, bv (with attention_bias);
            q_norm, k_norm [L, hd] (with qk_norm);
            w_gate, w_up [L, d, ff]; w_down [L, ff, d]

The reference follows the published Qwen2/Qwen3 description in float32,
batch 1, no cache and nothing of the program.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench.reference import HI, _fp8, _mm, _rms, _rope

MODEL_TYPES = ("qwen2", "qwen3")

Q_BLOCK = 512                 # reference: query rows per attention block

# public config.json key -> ModelConfig field of the program
_HF_KEYS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
    "attention_bias": "qkv_bias",
    "qk_norm": "qk_norm",
}


def model_config(conf: dict):
    """The program's ModelConfig (family "dense")."""
    from repro.configs.base import ModelConfig
    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {conf['hidden_act']!r} is not served")
    kw = {field: conf[key] for key, field in _HF_KEYS.items() if key in conf}
    kw.setdefault("head_dim", conf["hidden_size"]
                  // conf["num_attention_heads"])
    return ModelConfig(name=conf["name"], family="dense", act="swiglu",
                       source=conf["source"], **kw)


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------

def shapes(conf: dict) -> Dict[str, Tuple[int, ...]]:
    """Leaf name -> shape (canonical layout)."""
    d, L = conf["hidden_size"], conf["num_hidden_layers"]
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf.get("head_dim") or d // H
    ff, V = conf["intermediate_size"], conf["vocab_size"]
    s = {"embed": (V, d), "head": (d, V), "final_norm": (d,),
         "norm1": (L, d), "norm2": (L, d),
         "wq": (L, d, H * hd), "wk": (L, d, KV * hd), "wv": (L, d, KV * hd),
         "wo": (L, H * hd, d),
         "w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d)}
    if conf.get("attention_bias"):
        s.update(bq=(L, H * hd), bk=(L, KV * hd), bv=(L, KV * hd))
    if conf.get("qk_norm"):
        s.update(q_norm=(L, hd), k_norm=(L, hd))
    return s


def to_program(w: dict) -> dict:
    """The same arrays, nested as the program's dense stack takes them."""
    lw = w["layers"]
    attn = {k: lw[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv",
                               "q_norm", "k_norm") if k in lw}
    mlp = {k: lw[k] for k in ("w_gate", "w_up", "w_down")}
    block = {"norm1": lw["norm1"], "norm2": lw["norm2"], "attn": attn,
             "mlp": mlp}
    return {"embed": w["embed"], "head": w["head"],
            "final_norm": w["final_norm"],
            "stack": {"prefix": [], "groups": {"b0": block}}}


# --------------------------------------------------------------------------
# reference
# --------------------------------------------------------------------------

def _layer(x, lw, *, dims, eps, theta, control):
    S = x.shape[0]
    H, KV, hd = dims
    G = H // KV
    pos = jnp.arange(S)
    h = _rms(x, lw["norm1"], eps)
    q, k, v = (_mm(h, lw[n], control) for n in ("wq", "wk", "wv"))
    if "bq" in lw:
        q = q + lw["bq"].astype(jnp.float32)
        k = k + lw["bk"].astype(jnp.float32)
        v = v + lw["bv"].astype(jnp.float32)
    q, k, v = q.reshape(S, H, hd), k.reshape(S, KV, hd), v.reshape(S, KV, hd)
    if "q_norm" in lw:
        q = _rms(q, lw["q_norm"], eps)
        k = _rms(k, lw["k_norm"], eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    q = q.reshape(S, KV, G, hd) / math.sqrt(hd)
    outs = []
    for b0 in range(0, S, Q_BLOCK):
        qb = q[b0:b0 + Q_BLOCK]
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI)
        causal = pos[None, :] <= (b0 + jnp.arange(qb.shape[0]))[:, None]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI))
    a = jnp.concatenate(outs, 0).reshape(S, H * hd)
    x = x + _mm(a, lw["wo"], control)
    h = _rms(x, lw["norm2"], eps)
    g, u = _mm(h, lw["w_gate"], control), _mm(h, lw["w_up"], control)
    return x + _mm(jax.nn.silu(g) * u, lw["w_down"], control)


@partial(jax.jit, static_argnames=("dims", "eps", "theta", "control"))
def _logits(w, tokens, rows, *, dims, eps, theta, control):
    """Logits [len(rows), V] of a padded sequence at the given rows."""
    emb = w["embed"][tokens]
    x = (_fp8(emb, -1) if control else emb.astype(jnp.float32))

    def body(x, lw):
        return _layer(x, lw, dims=dims, eps=eps, theta=theta,
                      control=control), None

    x, _ = jax.lax.scan(body, x, w["layers"])
    x = _rms(x[rows], w["final_norm"], eps)
    return _mm(x, w["head"], control)


def logits(w, conf: dict, tokens, rows, control: bool):
    """The reference's (or the float8 control's) logits at ``rows``."""
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    dims = (H, KV, conf.get("head_dim") or conf["hidden_size"] // H)
    return _logits(w, tokens, rows, dims=dims,
                   eps=float(conf["rms_norm_eps"]),
                   theta=float(conf["rope_theta"]), control=control)


# --------------------------------------------------------------------------
# work counts
# --------------------------------------------------------------------------

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
