"""Pallas TPU flash attention (forward), GQA-native, causal + SWA.

TPU adaptation of the paper's line-rate pipeline idea: the attention PPU is
tiled so each grid step's working set (one q block, one kv block, f32
accumulators) lives in VMEM and the MXU sees [block_q, hd] x [hd, block_k]
matmuls. The kv-block axis is the sequential ("arbitrary") grid dim with
online-softmax state carried in VMEM scratch; causal/SWA blocks outside the
band are skipped with @pl.when.

Layouts: q is flattened to [B*H, S, hd] (one program row per query head);
k/v to [B*KV, S, hd]; the head -> kv-head mapping is folded into the
BlockSpec index maps, so KV is never materialized at H heads.

This is the serving/prefill hot path; training uses the jnp pair-list scan
with its flash custom-VJP (models/attention.py), which doubles as this
kernel's oracle (kernels/ref.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale, block_q, block_k, seq_len, window, n_k):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # block participates iff the causal (and SWA) band intersects it
    q_lo = i * block_q
    k_lo = j * block_k
    in_band = k_lo <= q_lo + block_q - 1
    if window > 0:
        in_band = jnp.logical_and(in_band,
                                  k_lo + block_k - 1 > q_lo - window)

    @pl.when(in_band)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # [bq, hd]
        k = k_ref[0].astype(jnp.float32)            # [bk, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = (kpos <= qpos) & (kpos < seq_len) & (qpos < seq_len)
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == n_k - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale=None, block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: [B,H,S,hd]; k,v: [B,KV,S,hd] -> [B,H,S,hd]. Causal (+SWA)."""
    assert causal, "non-causal attention is not used by this framework"
    B, H, S, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    pad_q = (-S) % block_q
    pad_k = (-S) % block_k
    Sq, Sk = S + pad_q, S + pad_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    qf = q.reshape(B * H, Sq, hd)
    kf = k.reshape(B * KV, Sk, hd)
    vf = v.reshape(B * KV, Sk, hd)
    n_q = Sq // block_q
    n_k = Sk // block_k

    def q_map(bh, i, j):
        return (bh, i, 0)

    def kv_map(bh, i, j):
        return ((bh // H) * KV + (bh % H) // G, j, 0)

    scratch = [pltpu.VMEM((block_q,), jnp.float32),
               pltpu.VMEM((block_q,), jnp.float32),
               pltpu.VMEM((block_q, hd), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_fa_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, seq_len=S, window=window, n_k=n_k),
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, hd)[:, :, :S]
