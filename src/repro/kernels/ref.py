"""Pure-jnp oracles for every Pallas kernel (the `ref.py` contract).

These are deliberately naive O(S²)/sequential implementations — the ground
truth the kernels' interpret-mode tests assert against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal=True, window=0, scale=None):
    """q: [B,H,S,hd]; k,v: [B,KV,S,hd] -> [B,H,S,hd]."""
    B, H, S, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        pos_q = jnp.arange(S)[:, None]
        pos_k = jnp.arange(S)[None, :]
        m = pos_k <= pos_q
        if window > 0:
            m &= pos_k > pos_q - window
        s = jnp.where(m[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def paged_decode_attention_ref(q, k_pages, v_pages, page_table, lengths,
                               scale=None):
    """q: [B,H,hd]; pages: [NP,KV,page,hd]; table: [B,MP]; lengths: [B]."""
    B, H, hd = q.shape
    NP, KV, page, _ = k_pages.shape
    MP = page_table.shape[1]
    G = H // KV
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    # [B, MP, KV, page, hd] -> [B, KV, MP*page, hd]: token order per head
    k = jnp.moveaxis(k_pages[page_table], 2, 1).reshape(B, KV, MP * page, hd)
    v = jnp.moveaxis(v_pages[page_table], 2, 1).reshape(B, KV, MP * page, hd)
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, k).astype(jnp.float32) * scale
    valid = jnp.arange(MP * page)[None] < lengths[:, None]
    s = jnp.where(valid[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bksd->bkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, hd).astype(q.dtype)


def moe_dispatch_ref(tokens, expert_ids, positions, n_experts, capacity):
    """tokens: [T,D]; expert_ids/positions: [T] -> buffers [E,C,D].

    Tokens with positions >= capacity are dropped (JingZhao Dynamic-Enqueue
    semantics: a full logical queue rejects the push).
    """
    T, D = tokens.shape
    buf = jnp.zeros((n_experts, capacity, D), tokens.dtype)
    keep = positions < capacity
    pos = jnp.where(keep, positions, capacity)  # -> dropped via mode="drop"
    buf = jnp.zeros((n_experts, capacity + 1, D), tokens.dtype)
    buf = buf.at[expert_ids, pos].set(tokens, mode="drop")
    return buf[:, :capacity]


def linear_scan_ref(a, b, h0):
    """h_t = a_t * h_{t-1} + b_t. a,b: [B,T,D,N]; h0: [B,D,N]."""
    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h
    h_last, hs = jax.lax.scan(step, h0, (a.transpose(1, 0, 2, 3),
                                         b.transpose(1, 0, 2, 3)))
    return hs.transpose(1, 0, 2, 3), h_last


def wkv6_ref(r, k, v, logw, u, state0):
    """Sequential WKV-6. r,k,v,logw: [B,S,H,hd]; u: [H,hd]; state0: [B,H,hd,hd]."""
    w = jnp.exp(logw)

    def step(S, x):
        rt, kt, vt, wt = x
        kv = jnp.einsum("bhd,bhe->bhde", kt, vt)
        y = jnp.einsum("bhd,bhde->bhe", rt, S + u[None, ..., None] * kv)
        S = wt[..., None] * S + kv
        return S, y

    xs = tuple(t.transpose(1, 0, 2, 3) for t in (r, k, v, w))
    S_last, ys = jax.lax.scan(step, state0, xs)
    return ys.transpose(1, 0, 2, 3), S_last


def wkv6_decode_ref(r, k, v, w, u, state):
    """Single-token WKV-6 step. r,k,v,w: [B,H,hd]; u: [H,hd];
    state: [B,H,hd,hd] — the t=1 slice of `wkv6_ref`'s recurrence."""
    kv = jnp.einsum("bhd,bhe->bhde", k, v)
    y = jnp.einsum("bhd,bhde->bhe", r, state + u[None, ..., None] * kv)
    return y, w[..., None] * state + kv


def ssm_decode_step_ref(h, dA, dtx, B_ssm, C_ssm):
    """Single-token S6 step. h, dA: [B,Di,N]; dtx: [B,Di]; B_ssm, C_ssm:
    [B,N] — the T=1 slice of `linear_scan_ref` with the C contraction."""
    h_new = dA * h + dtx[..., None] * B_ssm[:, None, :]
    return jnp.einsum("bdn,bn->bd", h_new, C_ssm), h_new


def sample_logits_ref(logits, keys, temperature, top_k, top_p):
    """Naive per-row sampling reference: each filter applied as its own
    separate step (scale, top-k cut, top-p nucleus over the renormalized
    top-k distribution), then the same categorical draw the fused kernel
    uses on the surviving logits in vocab order.
    logits: [B,V]; keys: [B,2] uint32; params: [B]. Returns [B] int32.
    """
    import numpy as np
    lg = np.asarray(logits, np.float32)
    B, V = lg.shape
    out = []
    for b in range(B):
        t = float(temperature[b])
        if t <= 0.0:
            out.append(int(np.argmax(lg[b])))
            continue
        scaled = jnp.asarray(lg[b] / np.float32(max(t, 1e-6)))
        order = np.argsort(-np.asarray(scaled), kind="stable")
        keep = np.zeros(V, bool)
        k = int(top_k[b])
        keep[order[:k if 0 < k < V else V]] = True
        p = float(top_p[b])
        if p < 1.0:
            # nucleus over the renormalized kept distribution: drop
            # entries whose preceding kept mass already reaches p
            probs = jax.nn.softmax(jnp.where(jnp.asarray(keep[order]),
                                             scaled[order], -jnp.inf))
            cum = np.asarray(jnp.cumsum(probs))
            probs = np.asarray(probs)
            keep[order] &= (cum - probs) < p
            keep[order[0]] = True
        masked = jnp.where(jnp.asarray(keep), scaled, -jnp.inf)
        out.append(int(jax.random.categorical(keys[b], masked)))
    return jnp.asarray(out, jnp.int32)
