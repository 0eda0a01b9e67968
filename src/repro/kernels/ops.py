"""Jit'd public wrappers for the Pallas kernels.

Off the TPU the kernels execute in interpret mode (semantics validated
against kernels/ref.py); on TPU they lower to Mosaic, whose tiling rules
interpret mode does not check. tests/test_chip_compile.py compiles the
paged decode kernel for a described v5e chip; the other kernels have not
been compiled for the chip. ``interpret=None`` auto-detects.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import (flash_attention as _fa, linear_scan as _ls,
                           moe_dispatch as _md, paged_attention as _pd,
                           sampling as _sp, ssm_decode as _ssd,
                           wkv6 as _wkv)


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_k=128, interpret: Optional[bool] = None):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=_auto_interpret(interpret))


@partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pages, v_pages, page_table, lengths,
                           interpret: Optional[bool] = None):
    return _pd.paged_decode_attention(q, k_pages, v_pages, page_table,
                                      lengths, backend="pallas",
                                      interpret=_auto_interpret(interpret))


@jax.jit
def sample_logits(logits, keys, temperature, top_k, top_p):
    return _sp.sample_logits(logits, keys, temperature, top_k, top_p)


@partial(jax.jit, static_argnames=("n_experts", "capacity", "interpret"))
def moe_dispatch(tokens, expert_ids, positions, n_experts: int,
                 capacity: int, interpret: Optional[bool] = None):
    return _md.moe_dispatch(tokens, expert_ids, positions, n_experts,
                            capacity, interpret=_auto_interpret(interpret))


@partial(jax.jit, static_argnames=("block_d", "interpret"))
def linear_scan(a, b, h0, *, block_d=256, interpret: Optional[bool] = None):
    return _ls.linear_scan(a, b, h0, block_d=block_d,
                           interpret=_auto_interpret(interpret))


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_chunked(r, k, v, logw, u, state0, *, chunk=32,
                 interpret: Optional[bool] = None):
    return _wkv.wkv6_chunked(r, k, v, logw, u, state0, chunk=chunk,
                             interpret=_auto_interpret(interpret))


@partial(jax.jit, static_argnames=("interpret",))
def wkv6_decode(r, k, v, w, u, state, *, interpret: Optional[bool] = None):
    return _wkv.wkv6_decode(r, k, v, w, u, state,
                            interpret=_auto_interpret(interpret))


@partial(jax.jit, static_argnames=("block_d", "interpret"))
def ssm_decode_step(h, dA, dtx, B_ssm, C_ssm, *, block_d=256,
                    interpret: Optional[bool] = None):
    return _ssd.ssm_decode_step(h, dA, dtx, B_ssm, C_ssm, block_d=block_d,
                                interpret=_auto_interpret(interpret))
