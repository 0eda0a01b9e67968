"""Attention: chunked-causal (flash-style, custom VJP) + decode paths.

Design notes (DESIGN.md §4):
- Training/prefill attention is a *pair-list scan*: the lower-triangular set
  of (q-chunk, kv-chunk) pairs is enumerated statically and processed by one
  ``lax.scan``. This (a) does exactly S²/2 work for causal masks (no padding
  waste), (b) lowers to a single while loop whose ``known_trip_count`` the
  roofline HLO walker multiplies through, (c) supports sliding windows by
  shrinking the pair list, and (d) keeps peak memory at one-chunk-pair.
- GQA is computed natively (q reshaped to [B, S, KV, G, hd]) — KV is never
  materialized at H heads, so decode memory traffic stays at kv_heads width.
- The custom VJP implements the FlashAttention backward (recompute p from
  saved logsumexp) so the pair-list scan does not stash per-step residuals.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _pair_list(nq: int, window_chunks: Optional[int]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Static (i, j) kv<=q chunk pairs, optionally banded for SWA."""
    ii, jj = [], []
    for i in range(nq):
        j0 = 0 if window_chunks is None else max(0, i - window_chunks)
        for j in range(j0, i + 1):
            ii.append(i)
            jj.append(j)
    return jnp.asarray(ii, jnp.int32), jnp.asarray(jj, jnp.int32)


def _mask(i, j, chunk: int, seq_len: int, window: int) -> jnp.ndarray:
    """[C, C] validity mask for q-chunk i vs kv-chunk j (dynamic i, j)."""
    pos_q = i * chunk + jnp.arange(chunk)[:, None]
    pos_k = j * chunk + jnp.arange(chunk)[None, :]
    m = (pos_k <= pos_q) & (pos_k < seq_len) & (pos_q < seq_len)
    if window > 0:
        m &= pos_k > pos_q - window
    return m


def _constrain(x, spec):
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def _fwd_scan(q, k, v, ii, jj, chunk, seq_len, window, scale, specs=None):
    """q: [nq,B,C,KV,G,hd]; k: [nk,B,C,KV,hd]; v: [nk,B,C,KV,hd_v].

    specs: optional (acc_spec, row_spec) PartitionSpecs pinning the scan
    carries (otherwise GSPMD may replicate the zero-initialized carries,
    measured as multi-GiB buffers on 34B-class configs).
    """
    nq, B, C, KV, G, hd = q.shape
    hd_v = v.shape[-1]
    acc_spec, row_spec = specs if specs is not None else (None, None)
    acc = _constrain(jnp.zeros((nq, B, KV, G, C, hd_v), jnp.float32), acc_spec)
    m = _constrain(jnp.full((nq, B, KV, G, C), NEG_INF, jnp.float32), row_spec)
    l = _constrain(jnp.zeros((nq, B, KV, G, C), jnp.float32), row_spec)

    def body(carry, pij):
        acc, m, l = carry
        i, j = pij
        qi = jax.lax.dynamic_index_in_dim(q, i, 0, keepdims=False)
        kj = jax.lax.dynamic_index_in_dim(k, j, 0, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(v, j, 0, keepdims=False)
        s = jnp.einsum("bckgd,bxkd->bkgcx", qi, kj,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(_mask(i, j, chunk, seq_len, window)[None, None, None], s, NEG_INF)
        mi = jax.lax.dynamic_index_in_dim(m, i, 0, keepdims=False)
        li = jax.lax.dynamic_index_in_dim(l, i, 0, keepdims=False)
        ai = jax.lax.dynamic_index_in_dim(acc, i, 0, keepdims=False)
        m_new = jnp.maximum(mi, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(mi - m_new)
        l_new = li * corr + p.sum(-1)
        pv = jnp.einsum("bkgcx,bxkd->bkgcd", p.astype(vj.dtype), vj,
                        preferred_element_type=jnp.float32)
        a_new = ai * corr[..., None] + pv
        acc = _constrain(
            jax.lax.dynamic_update_index_in_dim(acc, a_new, i, 0), acc_spec)
        m = _constrain(
            jax.lax.dynamic_update_index_in_dim(m, m_new, i, 0), row_spec)
        l = _constrain(
            jax.lax.dynamic_update_index_in_dim(l, l_new, i, 0), row_spec)
        return (acc, m, l), None

    (acc, m, l), _ = jax.lax.scan(body, (acc, m, l), (ii, jj))
    l_safe = jnp.where(l == 0, 1.0, l)
    out = acc / l_safe[..., None]
    lse = m + jnp.log(l_safe)
    return out, lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, chunk, seq_len, window, scale, specs=None):
    out, _ = _fwd_scan(q, k, v, *_pair_list(q.shape[0], _wc(window, chunk)),
                       chunk, seq_len, window, scale, specs)
    return out


def _wc(window: int, chunk: int) -> Optional[int]:
    return None if window <= 0 else -(-(window - 1) // chunk)


def _flash_fwd(q, k, v, chunk, seq_len, window, scale, specs=None):
    out, lse = _fwd_scan(q, k, v, *_pair_list(q.shape[0], _wc(window, chunk)),
                         chunk, seq_len, window, scale, specs)
    return out, (q, k, v, out, lse)


def _flash_bwd(chunk, seq_len, window, scale, specs, res, dout):
    q, k, v, out, lse = res
    ii, jj = _pair_list(q.shape[0], _wc(window, chunk))
    acc_spec, _ = specs if specs is not None else (None, None)
    qg_spec = kvg_spec = None
    if specs is not None and acc_spec is not None:
        # acc layout [nq,B,KV,G,C,hd]; dq mirrors q [nq,B,C,KV,G,hd];
        # dk/dv mirror k/v [nk,B,C,KV,hd]
        sp = acc_spec.spec
        mesh = acc_spec.mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        qg_spec = NamedSharding(mesh, P(sp[0], sp[1], None, sp[2], sp[3], None))
        kvg_spec = NamedSharding(mesh, P(sp[0], sp[1], None, sp[2], None))
    # D_i = rowsum(dO * O)   [nq,B,KV,G,C]
    delta = jnp.sum(dout * out, axis=-1)
    dq = _constrain(jnp.zeros(q.shape, jnp.float32), qg_spec)
    dk = _constrain(jnp.zeros(k.shape, jnp.float32), kvg_spec)
    dv = _constrain(jnp.zeros(v.shape, jnp.float32), kvg_spec)

    def body(carry, pij):
        dq, dk, dv = carry
        i, j = pij
        qi = jax.lax.dynamic_index_in_dim(q, i, 0, keepdims=False)
        kj = jax.lax.dynamic_index_in_dim(k, j, 0, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(v, j, 0, keepdims=False)
        lse_i = jax.lax.dynamic_index_in_dim(lse, i, 0, keepdims=False)
        do_i = jax.lax.dynamic_index_in_dim(dout, i, 0, keepdims=False)
        dl_i = jax.lax.dynamic_index_in_dim(delta, i, 0, keepdims=False)
        s = jnp.einsum("bckgd,bxkd->bkgcx", qi, kj,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(_mask(i, j, chunk, seq_len, window)[None, None, None], s, NEG_INF)
        p = jnp.exp(s - lse_i[..., None])                    # [b,k,g,c,x]
        dv_j = jnp.einsum("bkgcx,bkgcd->bxkd", p, do_i,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bkgcd,bxkd->bkgcx", do_i, vj,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - dl_i[..., None]) * scale              # [b,k,g,c,x]
        dq_i = jnp.einsum("bkgcx,bxkd->bckgd", ds, kj,
                          preferred_element_type=jnp.float32)
        dk_j = jnp.einsum("bkgcx,bckgd->bxkd", ds, qi,
                          preferred_element_type=jnp.float32)
        dq = dq.at[i].add(dq_i)
        dk = dk.at[j].add(dk_j)
        dv = dv.at[j].add(dv_j)
        return (dq, dk, dv), None

    (dq, dk, dv), _ = jax.lax.scan(body, (dq, dk, dv), (ii, jj))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def chunked_causal_attention(q, k, v, *, chunk: int = 1024, window: int = 0,
                             policy=None, scale: Optional[float] = None):
    """q: [B,S,H,hd], k: [B,S,KV,hd], v: [B,S,KV,hd_v] -> [B,S,H,hd_v]."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    hd_v = v.shape[-1]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    n = Sp // chunk
    qc = q.reshape(B, n, chunk, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    kc = k.reshape(B, n, chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n, chunk, KV, hd_v).transpose(1, 0, 2, 3, 4)
    specs = None
    if policy is not None and policy.mesh is not None:
        qc = policy.constrain(qc, None, "batch", None, "kv_heads", None, None)
        kc = policy.constrain(kc, None, "batch", None, "kv_heads", None)
        vc = policy.constrain(vc, None, "batch", None, "kv_heads", None)
        specs = (policy.named(None, "batch", "kv_heads", None, None, None),
                 policy.named(None, "batch", "kv_heads", None, None))
    out = _flash(qc, kc, vc, chunk, S, window, scale, specs)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sp, H, hd_v)
    return out[:, :S].astype(q.dtype)


def decode_attention(q, k_cache, v_cache, lengths, *, window: int = 0,
                     policy=None, scale: Optional[float] = None):
    """Single-token attention against a (contiguous or ring) KV cache.

    q: [B,H,hd]; k_cache/v_cache: [B,Smax,KV,hd]; lengths: [B] number of
    valid cache entries. For SWA ring caches, Smax == window and all
    min(length, window) slots are valid.
    """
    B, H, hd = q.shape
    Smax, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, KV, G, hd)
    if policy is not None:
        qg = policy.constrain(qg, "batch", "kv_heads", None, None)
        k_cache = policy.constrain(k_cache, "batch", "kv_seq", "kv_heads", None)
        v_cache = policy.constrain(v_cache, "batch", "kv_seq", "kv_heads", None)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(Smax)[None, :] < lengths[:, None]       # [B,Smax]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, v_cache.shape[-1]).astype(q.dtype)


def chunk_prefix_attention(q, k_cache, v_cache, q_pos, *, policy=None,
                           scale: Optional[float] = None):
    """Prompt-chunk attention against a dense cache (chunked prefill).

    q: [B,C,H,hd] — one prompt chunk whose token i sits at absolute
    position q_pos[i]; k_cache/v_cache: [B,L,KV,hd] hold every position
    written so far *including this chunk* (the caller scatters the
    chunk's K/V before attending). Causal over absolute positions: chunk
    token i attends to cache slots <= q_pos[i], so running the prompt in
    chunks computes exactly the rows of full-prefill attention that
    belong to this chunk. Padded tail rows (q_pos past the prompt) are
    computed but never read by the caller.
    """
    B, C, H, hd = q.shape
    L, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, C, KV, G, hd)
    if policy is not None:
        qg = policy.constrain(qg, "batch", None, "kv_heads", None, None)
        k_cache = policy.constrain(k_cache, "batch", "kv_seq", "kv_heads", None)
        v_cache = policy.constrain(v_cache, "batch", "kv_seq", "kv_heads", None)
    s = jnp.einsum("bckgd,bskd->bkgcs", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    causal = jnp.arange(L)[None, :] <= q_pos[:, None]          # [C,L]
    s = jnp.where(causal[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgcs,bskd->bckgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, C, H, v_cache.shape[-1]).astype(q.dtype)


def paged_decode_attention(q, page_table, k_pages, v_pages, lengths, *,
                           policy=None, scale: Optional[float] = None):
    """Decode attention through a page table (Resource Subsystem path).

    q: [B,H,hd]; page_table: [B,MP] int32 page ids; k_pages/v_pages:
    [NP,KV,page,hd] shared page pools; lengths: [B].
    The gather of pages is the paper's Gather-Data primitive: KV for one
    sequence is scattered across the shared pool exactly as a NIC gathers a
    message from non-contiguous host buffers. Dispatches to the Pallas
    kernel on TPU and the jnp gather elsewhere (kernels/paged_attention).
    """
    from repro.kernels import paged_attention as pk
    if policy is not None:
        q = policy.constrain(q, "batch", "heads", None)
        k_pages = policy.constrain(k_pages, "pages", "kv_heads", None, None)
        v_pages = policy.constrain(v_pages, "pages", "kv_heads", None, None)
    return pk.paged_decode_attention(q, k_pages, v_pages, page_table,
                                     lengths, scale=scale, backend="auto")
