"""Block assembly: per-layer mixers + MLPs, stacked with a grouped lax.scan.

A model is (prefix blocks) + (n_groups × repeating unit). The repeating unit
covers heterogeneous interleaves (Jamba: 8 sublayers — 7 mamba + 1 attention,
MoE every other) with one scan whose ``known_trip_count`` the roofline walker
multiplies through. Each block is a JingZhao pipeline: norm → mixer PPU →
residual → norm → MLP PPU → residual; mixers/MLPs are swappable
(Semantics Subsystem) without touching the runtime.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import mla as mla_mod
from repro.models import mamba as mamba_mod
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models.attention import (chunk_prefix_attention,
                                    chunked_causal_attention, decode_attention,
                                    paged_decode_attention)
from repro.models.layers import (apply_rope, dense_mlp, init_dense_mlp,
                                 mlp_specs, rms_norm, rope_angles)


# --------------------------------------------------------------------------
# layer plan
# --------------------------------------------------------------------------

def plan_layers(cfg: ModelConfig) -> Tuple[List, List, int]:
    """Return (prefix pairs, unit pairs, n_groups) of (kind, mlp_kind)."""
    pairs = list(zip(cfg.layer_kinds(), cfg.mlp_kinds()))
    for prefix in (0, 1, 2):
        rest = pairs[prefix:]
        if not rest:
            continue
        for p in (1, 2, 4, 8):
            if len(rest) % p:
                continue
            unit = rest[:p]
            if all(rest[i] == unit[i % p] for i in range(len(rest))):
                return pairs[:prefix], unit, len(rest) // p
    # fallback: fully unrolled prefix
    return pairs, [], 0


# --------------------------------------------------------------------------
# attention block (GQA / MHA, optional bias, qk-norm, SWA)
# --------------------------------------------------------------------------

def eff_heads(cfg: ModelConfig, tp: int = 1) -> Tuple[int, int]:
    """(H_eff, KV_eff) after TP alignment.

    When n_kv_heads < tp and tp % n_kv_heads == 0, KV heads are *duplicated*
    (Megatron convention — a checkpoint loader tiles the kv projections);
    when heads don't divide tp they are zero-padded up to a multiple. This
    keeps every head dim exactly divisible by the model axis, avoiding
    GSPMD uneven-shard resharding pathologies (DESIGN.md §7).
    """
    H, KV = cfg.n_heads, cfg.n_kv_heads
    if tp <= 1:
        return H, KV
    H_eff = -(-H // tp) * tp
    if KV < tp and tp % KV == 0 and H_eff == H:
        KV_eff = tp
    else:
        KV_eff = -(-KV // tp) * tp
    # grouping must stay integral
    if H_eff % KV_eff:
        KV_eff = H_eff
    return H_eff, KV_eff


def _init_attn(key, cfg: ModelConfig, dtype, tp: int = 1) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    H, KV = eff_heads(cfg, tp)
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": jax.random.normal(ks[0], (d, H * hd), dtype) * s,
        "wk": jax.random.normal(ks[1], (d, KV * hd), dtype) * s,
        "wv": jax.random.normal(ks[2], (d, KV * hd), dtype) * s,
        "wo": jax.random.normal(ks[3], (H * hd, d), dtype)
              * (1.0 / math.sqrt(H * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _attn_specs(cfg: ModelConfig) -> dict:
    s = {"wq": (None, "heads"), "wk": (None, "kv_heads"),
         "wv": (None, "kv_heads"), "wo": ("heads", None)}
    if cfg.qkv_bias:
        s.update(bq=("heads",), bk=("kv_heads",), bv=("kv_heads",))
    if cfg.qk_norm:
        s.update(q_norm=(None,), k_norm=(None,))
    return s


def _qkv(x, p, cfg):
    """x: [..., D] -> q [..., H, hd], k/v [..., KV, hd] (normed, no rope).

    Effective head counts are derived from the parameter shapes so the same
    code serves tp=1 smoke configs and TP-padded production configs.
    """
    hd = cfg.head_dim
    H = p["wq"].shape[1] // hd
    KV = p["wk"].shape[1] // hd
    q = x @ p["wq"] + (p["bq"] if cfg.qkv_bias else 0)
    k = x @ p["wk"] + (p["bk"] if cfg.qkv_bias else 0)
    v = x @ p["wv"] + (p["bv"] if cfg.qkv_bias else 0)
    q = q.reshape(*x.shape[:-1], H, hd)
    k = k.reshape(*x.shape[:-1], KV, hd)
    v = v.reshape(*x.shape[:-1], KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def attn_forward(x, p, cfg: ModelConfig, policy, ctx,
                 want_cache: bool = False):
    """Train/prefill attention. x: [B,S,D]."""
    B, S, _ = x.shape
    q, k, v = _qkv(x, p, cfg)
    angles = rope_angles(jnp.arange(S), cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, angles)
    k = apply_rope(k, angles)
    out = chunked_causal_attention(
        q, k, v, chunk=ctx.get("attn_chunk", 1024),
        window=cfg.swa_window, policy=policy)
    out = out.reshape(B, S, -1) @ p["wo"]
    cache = None
    if want_cache:
        if cfg.swa_window and S >= cfg.swa_window:
            W = cfg.swa_window
            # ring layout: slot t%W holds token t; for S>=W keep last W
            shift = S % W
            k_ring = jnp.roll(k[:, -W:], shift, axis=1)
            v_ring = jnp.roll(v[:, -W:], shift, axis=1)
            cache = {"k": k_ring, "v": v_ring}
        else:
            Smax = ctx.get("cache_len", S)
            padw = ((0, 0), (0, Smax - S), (0, 0), (0, 0))
            cache = {"k": jnp.pad(k, padw), "v": jnp.pad(v, padw)}
    return out, cache


def attn_prefill_chunk(x, p, cfg: ModelConfig, policy, ctx, cache):
    """Streamed prefill: extend a dense cache by one prompt chunk.

    x: [B,C,D] — chunk tokens at absolute positions start..start+C-1
    (ctx["start"] is a dynamic scalar, so one compiled program serves
    every chunk of a fixed width); cache {k,v: [B,L,KV,hd]} holds
    positions [0, start). The chunk's K/V is written in place with a
    dynamic slice, then attention runs causally over absolute positions
    — bit-for-bit the same rows full prefill would compute, which the
    chunked-vs-monolithic equivalence test pins to 1e-4.
    """
    B, C, _ = x.shape
    start = ctx["start"]
    q, k_new, v_new = _qkv(x, p, cfg)
    pos = start + jnp.arange(C)
    ang = rope_angles(pos, cfg.head_dim, cfg.rope_theta)       # [C, hd/2]
    q = apply_rope(q, ang)
    k_new = apply_rope(k_new, ang)
    # scatter by absolute position, NOT a dynamic slice: a slice of fixed
    # width C would *clamp* its start when a padded tail chunk straddles
    # cache_len, silently shifting the write over valid rows. The scatter
    # puts every token exactly at its position and drops out-of-range
    # padding rows instead.
    k_c = cache["k"].at[:, pos].set(k_new.astype(cache["k"].dtype),
                                    mode="drop")
    v_c = cache["v"].at[:, pos].set(v_new.astype(cache["v"].dtype),
                                    mode="drop")
    out = chunk_prefix_attention(q, k_c, v_c, pos, policy=policy)
    out = out.reshape(B, C, -1) @ p["wo"]
    return out, {"k": k_c, "v": v_c}


def attn_decode_paged(x, p, cfg: ModelConfig, policy, ctx, cache):
    """Paged decode: KV lives in a shared page pool, not a per-slot slab.

    x: [B,D]; cache {k,v: [NP,KV,page,hd]} — the *pool*, shared by every
    slot; ctx carries positions/lengths [B] and page_table [B,MP] (the MTT
    row per slot, exported by core.resource.PagePool). The new token's K/V
    is scattered into its owning page (parked slots' writes are dropped —
    see kernels.paged_attention.paged_append), then attention gathers
    through the table (DESIGN.md §3).
    """
    from repro.kernels.paged_attention import paged_append
    positions, lengths = ctx["positions"], ctx["lengths"]
    table = ctx["page_table"]
    q, k_new, v_new = _qkv(x, p, cfg)                  # [B,H,hd],[B,KV,hd]
    ang = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q[:, None], ang[:, None])[:, 0]
    k_new = apply_rope(k_new[:, None], ang[:, None])[:, 0]
    active = ctx.get("active")
    k_p, v_p = paged_append(cache["k"], cache["v"], k_new, v_new, table,
                            positions, active=active)
    lengths = lengths + 1
    if active is not None:
        # a slot outside the batch (free, prefilling, parked, stopped
        # mid-span) keeps a stale length and its output is dropped:
        # give it none, so the kernel fetches none of its pages
        lengths = jnp.where(active, lengths, 0)
    out = paged_decode_attention(q, table, k_p, v_p, lengths,
                                 policy=policy)
    out = out.reshape(x.shape[0], -1) @ p["wo"]
    return out, {"k": k_p, "v": v_p}


def attn_decode(x, p, cfg: ModelConfig, policy, ctx, cache):
    """x: [B,D]; cache {k,v: [B,Smax,KV,hd]}; ctx has positions/lengths [B]."""
    B, _ = x.shape
    positions, lengths = ctx["positions"], ctx["lengths"]
    q, k_new, v_new = _qkv(x, p, cfg)                  # [B,H,hd],[B,KV,hd]
    ang = rope_angles(positions, cfg.head_dim, cfg.rope_theta)  # [B, hd/2]
    q = apply_rope(q[:, None], ang[:, None])[:, 0]
    k_new = apply_rope(k_new[:, None], ang[:, None])[:, 0]
    W = cfg.swa_window
    Smax = cache["k"].shape[1]
    slot = positions % Smax if W else jnp.minimum(positions, Smax - 1)
    bidx = jnp.arange(B)
    k_c = cache["k"].at[bidx, slot].set(k_new.astype(cache["k"].dtype))
    v_c = cache["v"].at[bidx, slot].set(v_new.astype(cache["v"].dtype))
    eff_len = jnp.minimum(lengths + 1, Smax)
    out = decode_attention(q, k_c, v_c, eff_len, policy=policy)
    out = out.reshape(B, -1) @ p["wo"]
    return out, {"k": k_c, "v": v_c}


# --------------------------------------------------------------------------
# block init / specs / apply
# --------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, kind: str, mlp_kind: str, dtype,
               tp: int = 1) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    d = cfg.d_model
    p: Dict[str, Any] = {"norm1": jnp.ones((d,), dtype),
                         "norm2": jnp.ones((d,), dtype)}
    if kind == "attn":
        p["attn"] = (mla_mod.init_mla(k1, cfg, dtype) if cfg.mla is not None
                     else _init_attn(k1, cfg, dtype, tp=tp))
    elif kind == "mamba":
        p["mamba"] = mamba_mod.init_mamba(k1, cfg, dtype)
    elif kind == "rwkv":
        p["rwkv"] = rwkv_mod.init_rwkv(k1, cfg, dtype)
    else:
        raise ValueError(kind)
    if kind != "rwkv":
        if mlp_kind == "dense":
            d_ff = cfg.d_ff
            p["mlp"] = init_dense_mlp(k2, d, d_ff, cfg.act, dtype)
        elif mlp_kind == "moe":
            p["moe"] = moe_mod.init_moe(k2, cfg, dtype)
        else:
            raise ValueError(mlp_kind)
    return p


def block_specs(cfg: ModelConfig, kind: str, mlp_kind: str) -> dict:
    s: Dict[str, Any] = {"norm1": (None,), "norm2": (None,)}
    if kind == "attn":
        s["attn"] = (mla_mod.mla_specs(cfg) if cfg.mla is not None
                     else _attn_specs(cfg))
    elif kind == "mamba":
        s["mamba"] = mamba_mod.mamba_specs(cfg)
    elif kind == "rwkv":
        s["rwkv"] = rwkv_mod.rwkv_specs(cfg)
    if kind != "rwkv":
        s["mlp" if mlp_kind == "dense" else "moe"] = (
            mlp_specs(cfg) if mlp_kind == "dense" else moe_mod.moe_specs(cfg))
    return s


def _zero_stats():
    return {"moe_aux": jnp.zeros((), jnp.float32),
            "moe_dropped": jnp.zeros((), jnp.float32)}


def apply_block(p, x, kind: str, mlp_kind: str, cfg: ModelConfig, policy,
                ctx, cache=None, want_cache: bool = False):
    """Returns (x, new_cache, stats). Train mode: cache=None, want_cache=False."""
    mode = ctx["mode"]
    stats = _zero_stats()
    pool_cache = False       # cache is a shared page pool, not per-slot
    if policy is not None and mode != "decode":
        x = policy.constrain(x, "batch", "act_seq", None)
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    new_cache = None
    if kind == "attn":
        if cfg.mla is not None:
            if mode == "decode":
                if ctx.get("page_table") is not None:
                    # latent pages: absorbed decode through the MTT; the
                    # scatter drops parked writes, so skip the freeze below
                    a, new_cache = _mla_decode_paged(h, p["attn"], cfg, ctx,
                                                     cache, policy)
                    pool_cache = True
                else:
                    a, new_cache = _mla_decode_wrap(h, p["attn"], cfg, ctx,
                                                    cache, policy)
            else:
                angles = rope_angles(jnp.arange(x.shape[1]),
                                     cfg.mla.qk_rope_dim, cfg.rope_theta)
                a, new_cache = mla_mod.mla_prefill(
                    h, p["attn"], cfg, angles, policy, want_cache=want_cache)
                if new_cache is not None:
                    pad = ctx.get("cache_len", x.shape[1]) - x.shape[1]
                    if pad > 0:
                        new_cache = {
                            k2: jnp.pad(v2, ((0, 0), (0, pad), (0, 0)))
                            for k2, v2 in new_cache.items()}
        else:
            if mode == "decode":
                if ctx.get("page_table") is not None:
                    # shared-pool path: parking handled inside (dropped
                    # writes), so the per-slot freeze below must not run
                    a, new_cache = attn_decode_paged(h, p["attn"], cfg,
                                                     policy, ctx, cache)
                    pool_cache = True
                else:
                    a, new_cache = attn_decode(h, p["attn"], cfg, policy,
                                               ctx, cache)
            elif mode == "prefill_chunk":
                a, new_cache = attn_prefill_chunk(h, p["attn"], cfg, policy,
                                                  ctx, cache)
            else:
                a, new_cache = attn_forward(h, p["attn"], cfg, policy, ctx,
                                            want_cache=want_cache)
    elif kind == "mamba":
        if mode == "decode":
            a, new_cache = mamba_mod.mamba_decode(h, p["mamba"], cfg, cache, policy)
        else:
            a, new_cache = mamba_mod.mamba_forward(
                h, p["mamba"], cfg, policy, state=cache,
                want_state=want_cache)
    elif kind == "rwkv":
        if mode == "decode":
            a, tm_state = rwkv_mod.rwkv_time_mix_decode(h, p["rwkv"], cfg,
                                                        {k: cache[k] for k in
                                                         ("wkv", "shift_tm")})
        else:
            a, tm_state = rwkv_mod.rwkv_time_mix(h, p["rwkv"], cfg, policy,
                                                 state=cache,
                                                 want_state=want_cache)
    else:
        raise ValueError(kind)
    x = x + a
    h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
    if kind == "rwkv":
        if mode == "decode":
            m, cm_state = rwkv_mod.rwkv_channel_mix_decode(
                h2, p["rwkv"], cfg, {"shift_cm": cache["shift_cm"]})
        else:
            m, cm_state = rwkv_mod.rwkv_channel_mix(
                h2, p["rwkv"], cfg, policy,
                state=cache, want_state=want_cache)
        if tm_state is not None or cm_state is not None:
            new_cache = {**(tm_state or {}), **(cm_state or {})}
    elif mlp_kind == "dense":
        m = dense_mlp(h2, p["mlp"], cfg, policy)
    else:
        if mode == "decode":
            # group decode tokens so groups shard over the data axes
            B = h2.shape[0]
            dp = policy.dp_size if policy is not None else 1
            gdim = dp if (dp > 1 and B % dp == 0) else 1
            m3, st = moe_mod.moe_mlp(h2.reshape(gdim, B // gdim, -1),
                                     p["moe"], cfg, policy,
                                     capacity_factor=2.0)
            m = m3.reshape(B, -1)
        else:
            m, st = moe_mod.moe_mlp(h2, p["moe"], cfg, policy)
        stats = {**stats, **{k: v for k, v in st.items()}}
    x = x + m
    if policy is not None and mode != "decode":
        x = policy.constrain(x, "batch", "act_seq", None)
    if mode == "decode" and ctx.get("active") is not None and cache is not None \
            and new_cache is not None and not pool_cache:
        # VoQ parking: frozen (parked) sequences keep their old state; only
        # active connections advance (paper §4.1.1 per-connection blocking).
        # Shared page pools skip this: their leading dim is n_pages, not
        # batch, and parked writes were already dropped at the scatter.
        act = ctx["active"]

        def sel(n, o):
            a = act.reshape((act.shape[0],) + (1,) * (n.ndim - 1))
            return jnp.where(a, n, o)

        new_cache = jax.tree.map(sel, new_cache, cache)
    return x, new_cache, stats


def _mla_decode_wrap(h, p, cfg, ctx, cache, policy):
    full = {"c_kv": cache["c_kv"], "k_rope": cache["k_rope"],
            "length": jnp.minimum(ctx["lengths"] + 1,
                                  cache["c_kv"].shape[1])}
    out, new = mla_mod.mla_decode(h, p, cfg, full, ctx["positions"], policy)
    return out, {"c_kv": new["c_kv"], "k_rope": new["k_rope"]}


def _mla_decode_paged(h, p, cfg, ctx, cache, policy):
    """MLA decode against shared latent pages (the "latent" StateBackend).

    cache: {c_kv: [NP, page, lora], k_rope: [NP, page, rope]} — the pool,
    shared by every slot; ctx carries positions/lengths [B] and
    page_table [B, MP]. The slot's latent rows are gathered through the
    table into logical token order, the absorbed-attention math runs on
    that dense view (same code as the dense MLA path), and only the new
    token's [lora + rope] row is scattered back into its owning page —
    parked slots' writes are dropped via an out-of-range page id, the
    `paged_append` idiom.
    """
    table = ctx["page_table"]                          # [B, MP]
    positions = ctx["positions"]
    B, MP = table.shape
    NP, page = cache["c_kv"].shape[:2]
    c_dense = cache["c_kv"][table].reshape(B, MP * page, -1)
    r_dense = cache["k_rope"][table].reshape(B, MP * page, -1)
    full = {"c_kv": c_dense, "k_rope": r_dense,
            "length": jnp.minimum(ctx["lengths"] + 1, MP * page)}
    out, new = mla_mod.mla_decode(h, p, cfg, full, positions, policy)
    bidx = jnp.arange(B)
    c_new = new["c_kv"][bidx, positions]
    r_new = new["k_rope"][bidx, positions]
    pid = table[bidx, positions // page]
    off = positions % page
    active = ctx.get("active")
    if active is not None:
        pid = jnp.where(active, pid, NP)               # out of range -> drop
    c_p = cache["c_kv"].at[pid, off].set(
        c_new.astype(cache["c_kv"].dtype), mode="drop")
    r_p = cache["k_rope"].at[pid, off].set(
        r_new.astype(cache["k_rope"].dtype), mode="drop")
    return out, {"c_kv": c_p, "k_rope": r_p}


# --------------------------------------------------------------------------
# cache construction
# --------------------------------------------------------------------------

def init_block_cache(cfg: ModelConfig, kind: str, batch: int, cache_len: int,
                     dtype, tp: int = 1) -> Optional[dict]:
    d, hd = cfg.d_model, cfg.head_dim
    _, KV = eff_heads(cfg, tp)
    if kind == "attn":
        if cfg.mla is not None:
            m = cfg.mla
            return {"c_kv": jnp.zeros((batch, cache_len, m.kv_lora_rank), dtype),
                    "k_rope": jnp.zeros((batch, cache_len, m.qk_rope_dim), dtype)}
        S = min(cfg.swa_window, cache_len) if cfg.swa_window else cache_len
        return {"k": jnp.zeros((batch, S, KV, hd), dtype),
                "v": jnp.zeros((batch, S, KV, hd), dtype)}
    if kind == "mamba":
        m = cfg.mamba
        di = m.expand * d
        return {"conv": jnp.zeros((batch, m.d_conv - 1, di), dtype),
                "ssm": jnp.zeros((batch, di, m.d_state), jnp.float32)}
    if kind == "rwkv":
        H = d // cfg.rwkv.head_dim
        hd_r = cfg.rwkv.head_dim
        return {"wkv": jnp.zeros((batch, H, hd_r, hd_r), jnp.float32),
                "shift_tm": jnp.zeros((batch, d), dtype),
                "shift_cm": jnp.zeros((batch, d), dtype)}
    raise ValueError(kind)


def cache_specs(cfg: ModelConfig, kind: str) -> Optional[dict]:
    """Logical sharding axes for each cache leaf."""
    if kind == "attn":
        if cfg.mla is not None:
            # the latent cache has no head dim to shard; store it sharded
            # over the model axis along seq (gathered by the absorbed
            # attention's psum'd score reduction)
            return {"c_kv": ("batch", "mla_seq", None),
                    "k_rope": ("batch", "mla_seq", None)}
        return {"k": ("batch", "kv_seq", "kv_heads", None),
                "v": ("batch", "kv_seq", "kv_heads", None)}
    if kind == "mamba":
        return {"conv": ("batch", None, "inner"),
                "ssm": ("batch", "inner", None)}
    if kind == "rwkv":
        return {"wkv": ("batch", "inner", None, None),
                "shift_tm": ("batch", None), "shift_cm": ("batch", None)}
    raise ValueError(kind)


# --------------------------------------------------------------------------
# full stack: prefix + scanned groups
# --------------------------------------------------------------------------

def init_stack(key, cfg: ModelConfig, dtype, tp: int = 1) -> dict:
    prefix, unit, n_groups = plan_layers(cfg)
    keys = jax.random.split(key, len(prefix) + max(n_groups, 1) * max(len(unit), 1))
    params: Dict[str, Any] = {"prefix": [], "groups": None}
    ki = 0
    for kind, mlp in prefix:
        params["prefix"].append(init_block(keys[ki], cfg, kind, mlp, dtype, tp))
        ki += 1
    if n_groups:
        groups = []
        for g in range(n_groups):
            gp = {}
            for j, (kind, mlp) in enumerate(unit):
                gp[f"b{j}"] = init_block(keys[ki], cfg, kind, mlp, dtype, tp)
                ki += 1
            groups.append(gp)
        params["groups"] = jax.tree.map(lambda *xs: jnp.stack(xs), *groups)
    return params


def stack_specs(cfg: ModelConfig) -> dict:
    prefix, unit, n_groups = plan_layers(cfg)
    s: Dict[str, Any] = {"prefix": [], "groups": None}
    for kind, mlp in prefix:
        s["prefix"].append(block_specs(cfg, kind, mlp))
    if n_groups:
        gp = {}
        for j, (kind, mlp) in enumerate(unit):
            # stacked leaves gain a leading (unsharded) group axis
            gp[f"b{j}"] = jax.tree.map(
                lambda axes: (None,) + axes, block_specs(cfg, kind, mlp),
                is_leaf=lambda v: isinstance(v, tuple) and all(
                    a is None or isinstance(a, str) for a in v))
        s["groups"] = gp
    return s


def init_stack_caches(cfg: ModelConfig, batch: int, cache_len: int, dtype,
                      tp: int = 1) -> dict:
    prefix, unit, n_groups = plan_layers(cfg)
    caches: Dict[str, Any] = {"prefix": [], "groups": None}
    for kind, _ in prefix:
        caches["prefix"].append(
            init_block_cache(cfg, kind, batch, cache_len, dtype, tp))
    if n_groups:
        one = {f"b{j}": init_block_cache(cfg, kind, batch, cache_len, dtype, tp)
               for j, (kind, _) in enumerate(unit)}
        caches["groups"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_groups,) + x.shape), one)
    return caches


def init_paged_stack_caches(cfg: ModelConfig, n_pages: int, page_size: int,
                            dtype, tp: int = 1) -> dict:
    """Shared-pool caches: every attn layer holds [NP, KV, page, hd] pools.

    KV-head-major, so each (page, head) is one contiguous (page, hd) tile
    — the block the Pallas decode kernel streams per grid step.

    Unlike init_stack_caches there is no per-slot batch dim — all serving
    slots share one fixed block of page memory per layer and are separated
    only by the page table (the paper's MTT indirection). Paged serving is
    gated to pure-attention configs (no MLA/SWA/mamba/rwkv caches), which
    the caller (models.lm.init_paged_serve_state) enforces.
    """
    _, KV = eff_heads(cfg, tp)
    hd = cfg.head_dim

    def one_pool():
        return {"k": jnp.zeros((n_pages, KV, page_size, hd), dtype),
                "v": jnp.zeros((n_pages, KV, page_size, hd), dtype)}

    prefix, unit, n_groups = plan_layers(cfg)
    caches: Dict[str, Any] = {"prefix": [], "groups": None}
    for kind, _ in prefix:
        caches["prefix"].append(one_pool())
    if n_groups:
        one = {f"b{j}": one_pool() for j, _ in enumerate(unit)}
        caches["groups"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_groups,) + x.shape), one)
    return caches


def init_latent_paged_stack_caches(cfg: ModelConfig, n_pages: int,
                                   page_size: int, dtype,
                                   tp: int = 1) -> dict:
    """Shared latent pools: every MLA layer holds [NP, page, lora] +
    [NP, page, rope] pools — the absorbed-decode cache of models/mla.py
    put behind the same MTT indirection as init_paged_stack_caches, at
    ~[lora + rope] bytes per token instead of 2*KV*hd.
    """
    m = cfg.mla

    def one_pool():
        return {"c_kv": jnp.zeros((n_pages, page_size, m.kv_lora_rank),
                                  dtype),
                "k_rope": jnp.zeros((n_pages, page_size, m.qk_rope_dim),
                                    dtype)}

    prefix, unit, n_groups = plan_layers(cfg)
    caches: Dict[str, Any] = {"prefix": [], "groups": None}
    for kind, _ in prefix:
        caches["prefix"].append(one_pool())
    if n_groups:
        one = {f"b{j}": one_pool() for j, _ in enumerate(unit)}
        caches["groups"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_groups,) + x.shape), one)
    return caches


def paged_stack_supported(cfg: ModelConfig) -> bool:
    """Paged KV needs every layer to be plain (non-MLA, non-SWA) attention."""
    return (all(k == "attn" for k in cfg.layer_kinds())
            and cfg.mla is None and cfg.swa_window == 0)


def latent_paged_stack_supported(cfg: ModelConfig) -> bool:
    """Latent pages need every layer to be MLA attention (no SWA ring)."""
    return (all(k == "attn" for k in cfg.layer_kinds())
            and cfg.mla is not None and cfg.swa_window == 0)


def recurrent_state_supported(cfg: ModelConfig) -> bool:
    """Constant-size slot state needs every mixer to carry a recurrence
    (RWKV/Mamba) — any attention layer grows per token."""
    kinds = set(cfg.layer_kinds())
    return bool(kinds) and kinds <= {"mamba", "rwkv"}


# -- page-granular cache movement (engine: prefill insert, park/unpark) -----
#
# Pool leaves are [NP, *heads, page, feat] (prefix blocks) or
# [G, NP, *heads, page, feat] (group-scanned blocks): the token-in-page
# axis sits just before the feature axis, so attention pages are
# [NP, KV, page, hd] and MLA latent pages [NP, page, lora] / [NP, page,
# rope]. Dense caches keep tokens at axis 1 ([1, L, *heads, feat]), so a
# dense<->page move is a reshape plus one axis move. Whether a leaf
# carries the leading group axis is decided by which subtree it sits in —
# NOT by ndim. These tree maps are the engine's only way to touch pool
# memory: everything moves page-by-page, never as per-slot slabs.

def _map_stack(cache, fn):
    """Apply ``fn(leaf, grouped)`` across a stack-cache tree, tagging
    leaves in the scanned ``groups`` subtree with ``grouped=True``."""
    out: Dict[str, Any] = {
        "prefix": [jax.tree.map(lambda c: fn(c, False), t)
                   for t in cache["prefix"]],
        "groups": None}
    if cache.get("groups") is not None:
        out["groups"] = jax.tree.map(lambda c: fn(c, True), cache["groups"])
    return out


def _map_stack2(cache, other, fn):
    """Two-tree variant of ``_map_stack`` (same structure required)."""
    out: Dict[str, Any] = {
        "prefix": [jax.tree.map(lambda c, o: fn(c, o, False), t, u)
                   for t, u in zip(cache["prefix"], other["prefix"])],
        "groups": None}
    if cache.get("groups") is not None:
        out["groups"] = jax.tree.map(lambda c, o: fn(c, o, True),
                                     cache["groups"], other["groups"])
    return out


def dense_to_pages(dense_caches, n_pages: int, page_size: int,
                   first: int = 0):
    """Chunk a batch-1 dense cache tree into page-granular data.

    dense leaves [1, L, *heads, feat] -> pages ``first .. first+n_pages``
    as [n_pages, *heads, page, feat] (grouped leaves keep their leading
    G). Requires L >= (first+n_pages)*page_size (prefill pads to
    cache_len, so the tail pages beyond `length` are zeros — masked out
    by `lengths` at attention time).
    """
    def one(dense, grouped):
        d = dense[:, 0] if grouped else dense[0]  # [(G,) L, *heads, feat]
        t = 1 if grouped else 0                   # token axis
        d = jax.lax.slice_in_dim(d, first * page_size,
                                 (first + n_pages) * page_size, axis=t)
        d = d.reshape(d.shape[:t] + (n_pages, page_size) + d.shape[t + 1:])
        return jnp.moveaxis(d, t + 1, -2)
    return _map_stack(dense_caches, one)


def pages_to_dense(page_caches, cache_len: int, page_size: int):
    """Inverse of ``dense_to_pages``: page-granular data (token order) back
    to a batch-1 dense cache tree zero-padded to ``cache_len``.

    page leaves [P, *heads, page, feat] -> [1, cache_len, *heads, feat]
    (grouped leaves keep their leading G). Used by the chunked-prefill
    path to stage a paged slot's prefix as the dense cache
    `attn_prefill_chunk` extends.
    """
    def one(p, grouped):
        t = 1 if grouped else 0                   # page axis
        d = jnp.moveaxis(p, -2, t + 1)            # [(G,) P, page, ..., feat]
        P = d.shape[t]
        d = d.reshape(d.shape[:t] + (P * page_size,) + d.shape[t + 2:])
        pad = [(0, 0)] * d.ndim
        pad[t] = (0, cache_len - P * page_size)
        return jnp.expand_dims(jnp.pad(d, pad), t)
    return _map_stack(page_caches, one)


def chunked_prefill_supported(cfg: ModelConfig) -> bool:
    """Chunked prefill (and the block prefix cache built on it) needs
    plain full-attention caches — same gate as the paged layout."""
    return paged_stack_supported(cfg)


def gather_pages(pool_caches, page_ids):
    """Pull the listed pages out of every pool leaf (device -> host tier)."""
    ids = jnp.asarray(page_ids, jnp.int32)
    return _map_stack(
        pool_caches,
        lambda pool, grouped: pool[:, ids] if grouped else pool[ids])


def scatter_pages(pool_caches, page_data, page_ids):
    """Write page-granular data back into the listed pool pages."""
    ids = jnp.asarray(page_ids, jnp.int32)

    def one(pool, data, grouped):
        data = jnp.asarray(data).astype(pool.dtype)
        if grouped:
            return pool.at[:, ids].set(data)
        return pool.at[ids].set(data)
    return _map_stack2(pool_caches, page_data, one)


def stack_cache_specs(cfg: ModelConfig) -> dict:
    prefix, unit, n_groups = plan_layers(cfg)
    s: Dict[str, Any] = {"prefix": [], "groups": None}
    for kind, _ in prefix:
        s["prefix"].append(cache_specs(cfg, kind))
    if n_groups:
        s["groups"] = {
            f"b{j}": jax.tree.map(
                lambda axes: (None,) + axes, cache_specs(cfg, kind),
                is_leaf=lambda v: isinstance(v, tuple) and all(
                    a is None or isinstance(a, str) for a in v))
            for j, (kind, _) in enumerate(unit)}
    return s


def apply_stack(params, x, cfg: ModelConfig, policy, ctx,
                caches=None, want_caches: bool = False):
    """Run all blocks. Returns (x, new_caches, stats)."""
    prefix, unit, n_groups = plan_layers(cfg)
    stats = _zero_stats()
    new_caches: Dict[str, Any] = {"prefix": [], "groups": None}

    for i, (kind, mlp) in enumerate(prefix):
        c = caches["prefix"][i] if caches is not None else None
        x, nc, st = apply_block(params["prefix"][i], x, kind, mlp, cfg,
                                policy, ctx, cache=c, want_cache=want_caches)
        new_caches["prefix"].append(nc)
        stats = jax.tree.map(jnp.add, stats, st)

    if n_groups:
        remat = ctx.get("remat", False)

        def one_block(j, kind, mlp, bp, x, c):
            return apply_block(bp, x, kind, mlp, cfg, policy, ctx,
                               cache=c, want_cache=want_caches)

        def group_body(carry, xs):
            x, stats = carry
            gp = xs[0]
            gcache = xs[1] if caches is not None else None
            out_caches = {}
            for j, (kind, mlp) in enumerate(unit):
                c = gcache[f"b{j}"] if gcache is not None else None
                fn = functools.partial(one_block, j, kind, mlp)
                if remat:
                    # per-block remat: backward replays one block at a
                    # time, so residuals never exceed a single block's
                    fn = jax.checkpoint(fn)
                x, nc, st = fn(gp[f"b{j}"], x, c)
                if nc is not None:
                    out_caches[f"b{j}"] = nc
                stats = jax.tree.map(jnp.add, stats, st)
            ys = out_caches if (want_caches or caches is not None) else None
            return (x, stats), ys

        xs = (params["groups"],) if caches is None else (
            params["groups"], caches["groups"])
        (x, stats), group_caches = jax.lax.scan(group_body, (x, stats), xs)
        new_caches["groups"] = group_caches

    return x, new_caches, stats
