"""Mixture-of-Experts with capacity-based dispatch/combine.

This is the framework's flagship instance of the JingZhao *Dynamic
MultiQueue* building block (Table 1): tokens are dynamically enqueued into
per-expert logical queues that live in one shared capacity buffer
([groups, experts, capacity, d_model]); dequeue happens after the grouped
expert GEMMs, and the combine is a scatter-add back to token order. Dispatch
is a pure scatter (local under expert-sharding); combine lowers to a local
scatter-add + all-reduce over the model axis — the same collective a dense
TP layer already pays. Expert weights are sharded over the `model` axis
(expert parallelism); groups are data-parallel.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import init_dense_mlp, dense_mlp, mlp_specs


def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    moe = cfg.moe
    d, E, dE = cfg.d_model, moe.n_experts, moe.d_expert
    ks = jax.random.split(key, 5)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(dE)
    p = {
        "router": jax.random.normal(ks[0], (d, E), jnp.float32) * 0.02,
        "w_gate": jax.random.normal(ks[1], (E, d, dE), dtype) * s_in,
        "w_up": jax.random.normal(ks[2], (E, d, dE), dtype) * s_in,
        "w_down": jax.random.normal(ks[3], (E, dE, d), dtype) * s_out,
    }
    if moe.n_shared:
        p["shared"] = init_dense_mlp(ks[4], d, moe.n_shared * dE, cfg.act, dtype)
    return p


def moe_specs(cfg: ModelConfig) -> dict:
    s = {
        "router": (None, "experts"),
        "w_gate": ("experts", None, None),
        "w_up": ("experts", None, None),
        "w_down": ("experts", None, None),
    }
    if cfg.moe.n_shared:
        s["shared"] = mlp_specs(cfg)
    return s


def _capacity(tokens_per_group: int, cfg: ModelConfig, cf: Optional[float]) -> int:
    moe = cfg.moe
    cf = cf if cf is not None else moe.capacity_factor
    return max(4, int(math.ceil(moe.top_k * tokens_per_group / moe.n_experts * cf)))


def moe_mlp(x: jnp.ndarray, p: dict, cfg: ModelConfig, policy,
            capacity_factor: Optional[float] = None
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: [G, S, D] (groups are sequences, or one group of decode tokens).

    Under a mesh this runs expert-parallel inside shard_map: each model
    shard enqueues only the tokens routed to its local experts (the
    MultiQueue holds E/tp logical queues per shard), runs the local expert
    GEMMs, scatter-adds its partial combine and psums over the model axis.
    GSPMD-only dispatch was measured to replicate the scatter operands
    (50+ GiB on 32k-seq MoE prefill) — locality here is by construction.
    """
    if policy is not None and policy.mesh is not None:
        return _moe_mlp_sharded(x, p, cfg, policy, capacity_factor)
    return _moe_mlp_local(x, p, cfg, policy, capacity_factor)


def _moe_mlp_sharded(x, p, cfg, policy, capacity_factor):
    from jax.sharding import PartitionSpec as P
    moe = cfg.moe
    dp, tp = policy.dp_axes, policy.tp_axis
    E = moe.n_experts
    tp_size = policy.tp_size
    assert E % tp_size == 0, (E, tp_size)

    # expert weights enter fsdp-sharded along their d_model dim; gathered
    # in-body (the gather's transpose is the FSDP grad reduce-scatter).
    # Gated on the policy flag: serving keeps weights TP-stationary, and
    # slicing-then-gathering them anyway costs 6+ GB wire per decode step.
    d_model = cfg.d_model
    fsdp_ax = "data" if "data" in policy.mesh.axis_names else None
    use_fsdp = (policy.rules.get("fsdp_params", False)
                and fsdp_ax is not None
                and d_model % policy.axis_size(fsdp_ax) == 0)
    dm_axis = {k: list(p[k].shape).index(d_model)
               for k in ("w_gate", "w_up", "w_down")}

    def w_spec(k):
        parts = [None, None, None]
        parts[0] = tp
        if use_fsdp:
            parts[dm_axis[k]] = fsdp_ax
        return P(*parts)

    def body(x_loc, router, wg, wu, wd):
        if use_fsdp:
            wg = jax.lax.all_gather(wg, fsdp_ax, axis=dm_axis["w_gate"],
                                    tiled=True)
            wu = jax.lax.all_gather(wu, fsdp_ax, axis=dm_axis["w_up"],
                                    tiled=True)
            wd = jax.lax.all_gather(wd, fsdp_ax, axis=dm_axis["w_down"],
                                    tiled=True)
        E_loc = wg.shape[0]
        e0 = jax.lax.axis_index(tp) * E_loc
        out, stats = _moe_dispatch_local(
            x_loc, router, wg, wu, wd, e0, cfg, capacity_factor)
        out = jax.lax.psum(out, tp)
        stats = {k: (jax.lax.psum(v, tp) if k == "moe_aux" else v)
                 for k, v in stats.items()}
        if dp:
            stats = {k: jax.lax.pmean(v, dp) for k, v in stats.items()}
        return out, stats

    g_spec = P(dp, None, None) if dp else P(None, None, None)
    out, stats = jax.shard_map(
        body, mesh=policy.mesh,
        in_specs=(g_spec, P(None, None),
                  w_spec("w_gate"), w_spec("w_up"), w_spec("w_down")),
        out_specs=(g_spec, P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    if moe.n_shared:
        out = out + dense_mlp(x, p["shared"], cfg, policy)
    return out, stats


def _moe_dispatch_local(x, router, wg, wu, wd, e0, cfg, capacity_factor):
    """Per-shard dispatch/compute/combine for the local expert slice.

    x: [G_loc, S, D]; router: [D, E]; wg/wu/wd: [E_loc, ...]; e0: first
    local expert id. Returns partial output (needs psum over model axis).
    """
    moe = cfg.moe
    G, S, D = x.shape
    E, K = moe.n_experts, moe.top_k
    E_loc = wg.shape[0]
    C = _capacity(S, cfg, capacity_factor)

    logits = x.astype(jnp.float32) @ router                      # [G,S,E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, K)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    frac_routed = jnp.mean(
        jax.nn.one_hot(top_e[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    # local slice of the aux loss (psum'd over tp by the caller)
    probs_mean = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(jax.lax.dynamic_slice(frac_routed * probs_mean,
                                            (e0,), (E_loc,)))

    e_flat = top_e.reshape(G, S * K)
    w_flat = top_w.reshape(G, S * K)
    # queue position among tokens of the same expert (global pos so drop
    # behaviour matches the single-device oracle)
    oh = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(oh, axis=1), e_flat[..., None],
                              axis=-1)[..., 0] - 1
    local_e = e_flat - e0
    keep = (pos < C) & (local_e >= 0) & (local_e < E_loc)
    dropped = 1.0 - jnp.mean((pos < C).astype(jnp.float32))
    le_safe = jnp.where(keep, local_e, 0)
    pos_safe = jnp.where(keep, pos, C)

    g_idx = jnp.broadcast_to(jnp.arange(G, dtype=jnp.int32)[:, None], (G, S * K))
    s_idx = jnp.tile(jnp.repeat(jnp.arange(S, dtype=jnp.int32), K)[None], (G, 1))

    # index-scatter + payload-gather: only int32 slot maps are scattered
    # (the K-times-duplicated payload scatter was measured at 2+ GiB/device
    # in f32 on 32k MoE cells); the payload moves once, via gather.
    src = jnp.full((G, E_loc, C + 1), S, jnp.int32)
    src = src.at[g_idx, le_safe, pos_safe].set(
        jnp.where(keep, s_idx, S), mode="drop")[:, :, :C]
    wgt = jnp.zeros((G, E_loc, C + 1), jnp.float32)
    wgt = wgt.at[g_idx, le_safe, pos_safe].set(
        jnp.where(keep, w_flat, 0.0), mode="drop")[:, :, :C]
    x_pad = jnp.concatenate([x, jnp.zeros((G, 1, D), x.dtype)], axis=1)
    buf = jnp.take_along_axis(
        x_pad[:, None], src[..., None], axis=2)         # [G,E_loc,C,D]

    if cfg.act == "swiglu":
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, wg)) \
            * jnp.einsum("gecd,edf->gecf", buf, wu)
    else:
        from repro.models.layers import activation
        h = activation(cfg.act)(jnp.einsum("gecd,edf->gecf", buf, wu))
    y = jnp.einsum("gecf,efd->gecd", h, wd)

    y_w = (y.astype(jnp.float32) * wgt[..., None]).astype(x.dtype)
    out = jnp.zeros((G, S + 1, D), x.dtype)
    out = out.at[jnp.arange(G)[:, None, None], src, :].add(y_w)[:, :S]
    return out, {"moe_aux": aux, "moe_dropped": dropped}


def _moe_mlp_local(x, p, cfg, policy, capacity_factor):
    """Single-device reference path (smoke tests, oracles)."""
    moe = cfg.moe
    G, S, D = x.shape
    E, K = moe.n_experts, moe.top_k
    C = _capacity(S, cfg, capacity_factor)

    # ---- router (fp32) -------------------------------------------------
    logits = x.astype(jnp.float32) @ p["router"]                 # [G,S,E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, K)                       # [G,S,K]
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # Switch-style load-balance aux loss.
    frac_routed = jnp.mean(
        jax.nn.one_hot(top_e[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    aux = E * jnp.sum(frac_routed * jnp.mean(probs, axis=(0, 1)))

    # ---- dispatch: dynamic-enqueue into per-expert queues ---------------
    def c(t, *axes):
        return policy.constrain(t, *axes) if policy is not None else t

    e_flat = top_e.reshape(G, S * K)                             # [G,SK]
    w_flat = top_w.reshape(G, S * K)
    oh = c(jax.nn.one_hot(e_flat, E, dtype=jnp.int32),
           "batch", None, "experts")                             # [G,SK,E]
    pos = c(jnp.take_along_axis(jnp.cumsum(oh, axis=1), e_flat[..., None],
                                axis=-1)[..., 0] - 1,
            "batch", None)                                       # [G,SK]
    keep = pos < C
    pos_safe = jnp.where(keep, pos, C)                           # C -> dropped
    dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))

    g_idx = jnp.broadcast_to(jnp.arange(G, dtype=jnp.int32)[:, None], (G, S * K))
    x_rep = c(jnp.repeat(x, K, axis=1), "batch", None, None)     # [G,SK,D]
    s_idx = jnp.tile(jnp.repeat(jnp.arange(S, dtype=jnp.int32), K)[None], (G, 1))

    buf = jnp.zeros((G, E, C + 1, D), x.dtype)
    buf = buf.at[g_idx, e_flat, pos_safe].set(x_rep, mode="drop")
    buf = c(buf[:, :, :C], "batch", "experts", None, None)

    # slot -> source token index / weight (sentinel S = empty slot)
    src = jnp.full((G, E, C + 1), S, jnp.int32)
    src = c(src.at[g_idx, e_flat, pos_safe].set(s_idx, mode="drop")[:, :, :C],
            "batch", "experts", None)
    wgt = jnp.zeros((G, E, C + 1), jnp.float32)
    wgt = c(wgt.at[g_idx, e_flat, pos_safe].set(w_flat, mode="drop")[:, :, :C],
            "batch", "experts", None)

    # ---- grouped expert GEMMs (local under expert sharding) ------------
    if cfg.act == "swiglu":
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["w_gate"])) \
            * jnp.einsum("gecd,edf->gecf", buf, p["w_up"])
    else:
        from repro.models.layers import activation
        h = activation(cfg.act)(jnp.einsum("gecd,edf->gecf", buf, p["w_up"]))
    y = jnp.einsum("gecf,efd->gecd", h, p["w_down"])             # [G,E,C,D]
    if policy is not None:
        y = policy.constrain(y, "batch", "experts", None, None)

    # ---- combine: scatter-add back to token order (dequeue) ------------
    y_w = (y.astype(jnp.float32) * wgt[..., None]).astype(x.dtype)
    out = jnp.zeros((G, S + 1, D), x.dtype)
    out = out.at[jnp.arange(G)[:, None, None], src, :].add(y_w)[:, :S]
    if policy is not None:
        out = policy.constrain(out, "batch", None, None)

    if moe.n_shared:
        out = out + dense_mlp(x, p["shared"], cfg, policy)

    stats = {"moe_aux": aux, "moe_dropped": dropped}
    return out, stats
