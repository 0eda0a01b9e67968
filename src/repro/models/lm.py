"""LM wrapper: embedding, stack, vocab-parallel chunked cross-entropy,
train/prefill/decode entry points, and ``input_specs`` for the dry-run.

The 256k-vocab architectures make global logits [B,S,V] untenable; the loss
is computed Megatron-style inside ``shard_map``: local [*,V/tp] logits per
sequence chunk, global log-sum-exp via psum, logits never materialized.
This is a *Remove Header / Scatter Data* composition in JingZhao terms: the
vocab dimension is scattered across the model axis and only 8-byte-per-token
metadata (lse, target logit) crosses shards.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels import sampling as ksamp
from repro.models import transformer as tf
from repro.models.layers import rms_norm
from repro.sharding.policy import Policy

CE_CHUNK = 512


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key, dtype=None, tp: int = 1) -> dict:
    dtype = dtype or jnp.dtype(cfg.dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    d, V = cfg.d_model, cfg.vocab_size
    params = {
        "embed": jax.random.normal(k1, (V, d), dtype) * 0.02,
        "stack": tf.init_stack(k2, cfg, dtype, tp=tp),
        "final_norm": jnp.ones((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = jax.random.normal(k3, (d, V), dtype) / math.sqrt(d)
    return params


def param_specs(cfg: ModelConfig) -> dict:
    s = {
        "embed": ("vocab", None),
        "stack": tf.stack_specs(cfg),
        "final_norm": (None,),
    }
    if not cfg.tie_embeddings:
        s["head"] = (None, "vocab")
    return s


def abstract_params(cfg: ModelConfig, tp: int = 1):
    return jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), tp=tp))


# --------------------------------------------------------------------------
# vocab-parallel embedding / loss
# --------------------------------------------------------------------------

def _embed_plain(table, ids):
    return jnp.take(table, ids, axis=0)


def embed(table, ids, policy: Policy):
    """ids [...]-> [..., D]; vocab-parallel under a mesh.

    The table enters fsdp-sharded (vocab x data); the body all-gathers the
    d_model dim explicitly — letting GSPMD reshard instead was measured to
    replicate-then-partition (full-table f32 copies). The gather's
    transpose is a reduce-scatter, which is exactly the FSDP grad flow.
    """
    if policy.mesh is None:
        return _embed_plain(table, ids)
    dp = policy.dp_axes
    tp = policy.tp_axis
    fsdp_ax = "data" if "data" in policy.mesh.axis_names else None
    d_model = table.shape[1]
    use_fsdp = (policy.rules.get("fsdp_params", False)
                and fsdp_ax is not None
                and d_model % policy.axis_size(fsdp_ax) == 0)

    def body(tbl, ids_loc):
        if use_fsdp:
            tbl = jax.lax.all_gather(tbl, fsdp_ax, axis=1, tiled=True)
        vloc = tbl.shape[0]
        start = jax.lax.axis_index(tp) * vloc
        loc = ids_loc - start
        ok = (loc >= 0) & (loc < vloc)
        out = jnp.where(ok[..., None],
                        jnp.take(tbl, jnp.clip(loc, 0, vloc - 1), axis=0),
                        jnp.zeros((), tbl.dtype))
        return jax.lax.psum(out, tp)

    nd = ids.ndim
    return jax.shard_map(
        body, mesh=policy.mesh,
        in_specs=(P(tp, fsdp_ax if use_fsdp else None),
                  P(dp, *([None] * (nd - 1)))),
        out_specs=P(dp, *([None] * nd)),
        check_vma=False,
    )(table, ids)


def head_logits(x, head_w, policy: Policy):
    """x [B,D] (decode) -> logits [B,V] (vocab-sharded under mesh)."""
    logits = x @ head_w
    if policy.mesh is not None:
        logits = policy.constrain(logits, "batch", "vocab")
    return logits


def _ce_from_logits(logits, targets):
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return lse - tgt


def chunked_ce_loss(x, head_w, targets, mask, policy: Policy,
                    chunk: int = CE_CHUNK):
    """Mean CE over masked tokens. x: [B,S,D]; targets/mask: [B,S]."""
    B, S, D = x.shape
    if policy.mesh is None:
        per_tok = _ce_from_logits(x @ head_w, targets)
        return jnp.sum(per_tok * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    dp, tp = policy.dp_axes, policy.tp_axis
    chunk = min(chunk, S)
    pad = (-S) % chunk
    fsdp_ax = "data" if "data" in policy.mesh.axis_names else None
    use_fsdp = (policy.rules.get("fsdp_params", False)
                and fsdp_ax is not None
                and head_w.shape[0] % policy.axis_size(fsdp_ax) == 0)

    def body(x_loc, w_loc, tgt_loc, mask_loc):
        # x_loc: [b,S,D]; w_loc: [D/fsdp,V/tp] -> gathered [D,V/tp]
        if use_fsdp:
            w_loc = jax.lax.all_gather(w_loc, fsdp_ax, axis=0, tiled=True)
        vloc = w_loc.shape[1]
        v0 = jax.lax.axis_index(tp) * vloc
        b = x_loc.shape[0]
        if pad:
            x_loc = jnp.pad(x_loc, ((0, 0), (0, pad), (0, 0)))
            tgt_loc = jnp.pad(tgt_loc, ((0, 0), (0, pad)))
            mask_loc = jnp.pad(mask_loc, ((0, 0), (0, pad)))
        nc = (S + pad) // chunk
        xc = x_loc.reshape(b, nc, chunk, D).transpose(1, 0, 2, 3)
        tc = tgt_loc.reshape(b, nc, chunk).transpose(1, 0, 2)
        mc = mask_loc.reshape(b, nc, chunk).transpose(1, 0, 2)
        # keep the scan xs in bf16: without the barrier XLA-CPU pushes the
        # f32 dot-input convert above the loop (full-sequence f32 copies)
        xc = jax.lax.optimization_barrier(xc)

        @jax.checkpoint
        def chunk_fn(carry, xs):
            xcu, tcu, mcu = xs
            logits = (xcu @ w_loc).astype(jnp.float32)      # [b,C,V/tp]
            lmax = jax.lax.pmax(
                jax.lax.stop_gradient(jnp.max(logits, axis=-1)), tp)
            se = jnp.sum(jnp.exp(logits - lmax[..., None]), axis=-1)
            lse = jnp.log(jax.lax.psum(se, tp)) + lmax
            loc = tcu - v0
            ok = (loc >= 0) & (loc < vloc)
            tl = jnp.take_along_axis(
                logits, jnp.clip(loc, 0, vloc - 1)[..., None], axis=-1)[..., 0]
            tl = jax.lax.psum(jnp.where(ok, tl, 0.0), tp)
            per_tok = (lse - tl) * mcu
            return (carry[0] + jnp.sum(per_tok), carry[1] + jnp.sum(mcu)), None

        (tot, cnt), _ = jax.lax.scan(
            chunk_fn, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (xc, tc, mc))
        tot = jax.lax.psum(tot, dp)
        cnt = jax.lax.psum(cnt, dp)
        return (tot / jnp.maximum(cnt, 1.0))[None]

    loss = jax.shard_map(
        body, mesh=policy.mesh,
        in_specs=(P(dp, None, None),
                  P(fsdp_ax if use_fsdp else None, tp),
                  P(dp, None), P(dp, None)),
        out_specs=P(None),
        check_vma=False,
    )(x, head_w, targets, mask.astype(jnp.float32))
    return loss[0]


# --------------------------------------------------------------------------
# model entry points
# --------------------------------------------------------------------------

def _head_weight(params, cfg):
    return params["embed"].T if cfg.tie_embeddings else params["head"]


def forward_loss(params, tokens, cfg: ModelConfig, policy: Policy,
                 remat: bool = True) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Next-token CE loss. tokens: [B,S] int32."""
    x = embed(params["embed"], tokens, policy)
    ctx = {"mode": "train", "remat": remat}
    x, _, stats = tf.apply_stack(params["stack"], x, cfg, policy, ctx)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
    mask = jnp.concatenate(
        [jnp.ones_like(tokens[:, 1:]), jnp.zeros_like(tokens[:, :1])],
        axis=1).astype(jnp.float32)
    ce = chunked_ce_loss(x, _head_weight(params, cfg), targets, mask, policy)
    loss = ce
    if cfg.moe is not None:
        loss = loss + cfg.moe.router_aux_weight * stats["moe_aux"]
    metrics = {"ce": ce, **stats}
    return loss, metrics


def prefill(params, tokens, cfg: ModelConfig, policy: Policy,
            cache_len: Optional[int] = None):
    """Build caches for `tokens` [B,S]; returns (last_logits [B,V], state)."""
    B, S = tokens.shape
    x = embed(params["embed"], tokens, policy)
    ctx = {"mode": "prefill", "cache_len": cache_len or S}
    x, caches, _ = tf.apply_stack(params["stack"], x, cfg, policy, ctx,
                                  want_caches=True)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = head_logits(x[:, -1], _head_weight(params, cfg), policy)
    state = {
        "caches": caches,
        "lengths": jnp.full((B,), S, jnp.int32),
        "positions": jnp.full((B,), S, jnp.int32),
    }
    return logits, state


def prefill_chunk(params, tokens, caches, start, n_valid, cfg: ModelConfig,
                  policy: Policy):
    """Streamed prefill: extend dense caches by one prompt chunk.

    tokens: [B,C] — the chunk at absolute positions start..start+C-1
    (`start` and `n_valid` are dynamic scalars, so a fixed chunk width
    compiles once and serves the whole prompt). `caches` is a dense
    serving cache tree (leaves [B, cache_len, KV, hd]) holding positions
    [0, start); tokens past `n_valid` are padding — their K/V lands
    beyond the valid length (masked by `lengths` downstream, overwritten
    by the first decode append) and their outputs are never read.
    Returns (logits [B,V] at the last valid chunk token, extended caches).
    Chaining chunks over a prompt is logit-identical to `prefill`.
    """
    if not tf.chunked_prefill_supported(cfg):
        # name the capability that's actually missing: this path extends
        # per-token dense K/V rows in place, which MLA latent caches, SWA
        # rings, and recurrent (mamba/rwkv) carries don't expose
        kinds = sorted(set(cfg.layer_kinds()))
        raise ValueError(
            f"chunked prefill needs per-token dense attention caches that "
            f"extend row-by-row; {cfg.name} (layer kinds {kinds}, "
            f"mla={cfg.mla is not None}, swa_window={cfg.swa_window}) "
            f"doesn't expose them — use monolithic prefill")
    x = embed(params["embed"], tokens, policy)
    ctx = {"mode": "prefill_chunk", "start": start}
    x, caches, _ = tf.apply_stack(params["stack"], x, cfg, policy, ctx,
                                  caches=caches)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    B = x.shape[0]
    last = jnp.full((B,), n_valid - 1, jnp.int32)
    x_last = jnp.take_along_axis(
        x, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    logits = head_logits(x_last, _head_weight(params, cfg), policy)
    return logits, caches


def decode_step(params, tokens, state, cfg: ModelConfig, policy: Policy,
                active=None):
    """One decode step. tokens: [B] int32. Returns (logits [B,V], state).

    `active` [B] bool (optional): parked sequences (VoQ miss handling in
    the serving engine) keep their caches/counters frozen.
    """
    x = embed(params["embed"], tokens[:, None], policy)[:, 0]
    ctx = {"mode": "decode",
           "positions": state["positions"],
           "lengths": state["lengths"],
           "active": active,
           "page_table": state.get("page_table")}
    x, caches, _ = tf.apply_stack(params["stack"], x, cfg, policy, ctx,
                                  caches=state["caches"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = head_logits(x, _head_weight(params, cfg), policy)
    # per-layer attention paths clamp effective lengths to their own cache
    # size (ring buffers clamp to the window), so the global counters just
    # advance monotonically.
    adv = 1 if active is None else active.astype(jnp.int32)
    new_state = {
        "caches": caches,
        "lengths": state["lengths"] + adv,
        "positions": state["positions"] + adv,
    }
    if state.get("page_table") is not None:
        new_state["page_table"] = state["page_table"]
    return logits, new_state


def decode_span(params, tokens, state, cfg: ModelConfig, policy: Policy,
                active, budgets, *, span: int, eos_token: int,
                cache_len: int, sample_fn=None, sampler_params=None,
                rng=None, want_logprobs: bool = False):
    """Run up to ``span`` decode steps inside one jitted ``lax.scan``.

    The serving engine's per-token host round-trip (dispatch, argmax
    transfer, position reads) is the decode path's bottleneck on small
    models — the JingZhao doorbell argument: the host should ring once
    per batch of work, not once per packet. This entry point keeps the
    whole span device-resident; the engine syncs host state once per
    span instead of once per token.

    tokens: [B] int32 — each slot's last emitted token; active: [B] bool
    — slots decoding this span; budgets: [B] int32 — tokens each slot
    may emit this span (<= span; the engine folds max_new_tokens
    remaining and reserved page headroom into this one counter, since
    alloc-on-append cannot fire mid-scan). Stop conditions evaluate on
    device: a slot freezes through the existing active-mask mechanics
    (caches bit-frozen, counters halted, paged writes dropped) as soon
    as it emits ``eos_token``, exhausts its budget, or fills
    ``cache_len``; the rest of the batch keeps decoding.

    Token selection is pluggable (DESIGN.md §3.7): ``sample_fn(logits,
    keys, sampler_params)`` runs on device each scan step (None =
    argmax). With ``rng = (seeds [B], req_ids [B], counters [B])`` the
    carry threads a per-slot emitted-token counter: step keys are
    ``derive_keys(seed, req_id, counter)`` and the counter advances
    only on real emissions, so a slot's key sequence depends solely on
    its ``(seed, req_id)`` stream position — invariant to span length,
    span bucketing, batch neighbors, and park/unpark (the engine
    re-derives counters from host bookkeeping, exactly like KV state).

    Returns (toks [span, B] int32, emit [span, B] bool, state) — with
    ``want_logprobs`` (toks, emit, logprobs [span, B] f32, state), the
    chosen tokens' raw-logit logprobs riding the same host sync.
    emit[t,i] marks a real emission at scan step t, so the
    host-applied token streams are byte-identical to per-step decode
    (span == 1 is exactly ``decode_step``).
    """
    if rng is not None:
        seeds, req_ids, counters = rng
    else:
        counters = jnp.zeros_like(budgets)

    def body(carry, _):
        toks, st, act, left, ctr = carry
        logits, st = decode_step(params, toks, st, cfg, policy, active=act)
        if sample_fn is None:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            keys = (ksamp.derive_keys(seeds, req_ids, ctr)
                    if rng is not None else None)
            with jax.named_scope("sampler"):
                nxt = sample_fn(logits, keys,
                                sampler_params).astype(jnp.int32)
        nxt = jnp.where(act, nxt, toks)
        out = (nxt, act)
        if want_logprobs:
            out = out + (ksamp.token_logprob(logits, nxt),)
        left = left - act.astype(jnp.int32)
        ctr = ctr + act.astype(jnp.int32)
        done = ((nxt == jnp.int32(eos_token)) | (left <= 0)
                | (st["positions"] >= cache_len))
        return (nxt, st, act & ~done, left, ctr), out

    carry = (tokens, state, active, budgets, counters)
    (_, state, _, _, _), outs = jax.lax.scan(body, carry, None, length=span)
    if want_logprobs:
        toks, emit, lps = outs
        return toks, emit, lps, state
    toks, emit = outs
    return toks, emit, state


def select_token(logits, sample_fn=None, sampler_params=None, rng=None):
    """On-device token selection for a batch of final logits — the
    prefill first-token path (DESIGN.md §3.7). Same sampler contract as
    ``decode_span``; ``rng = (seeds, req_ids, indices)`` with index 0
    for a prefill token. Returns (tokens [B] int32, logprobs [B] f32):
    one fused computation, so the host's only cost is a single scalar
    sync instead of an eager argmax chain.
    """
    keys = ksamp.derive_keys(*rng) if rng is not None else None
    if sample_fn is None:
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        with jax.named_scope("sampler"):
            tok = sample_fn(logits, keys, sampler_params).astype(jnp.int32)
    return tok, ksamp.token_logprob(logits, tok)


def init_serve_state(cfg: ModelConfig, batch: int, cache_len: int,
                     dtype=None, filled: bool = True, tp: int = 1) -> dict:
    """Fresh (or 'already full', for dry-runs) decoding state."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    caches = tf.init_stack_caches(cfg, batch, cache_len, dtype, tp=tp)
    fill = cache_len if filled else 0
    return {
        "caches": caches,
        "lengths": jnp.full((batch,), fill, jnp.int32),
        "positions": jnp.full((batch,), fill, jnp.int32),
    }


def init_paged_serve_state(cfg: ModelConfig, batch: int, n_pages: int,
                           page_size: int, max_pages: int, dtype=None,
                           tp: int = 1) -> dict:
    """Paged decoding state: shared per-layer page pools + per-slot MTT.

    ``caches`` leaves are [n_pages, KV, page_size, hd] pools (plain
    attention) or [n_pages, page_size, lora|rope] latent pools (MLA)
    shared by all `batch` slots; ``page_table`` [batch, max_pages] names
    each slot's pages in token order (rows are rewritten by the engine as
    the PagePool allocates on append). Total pool memory is
    n_pages*page_size tokens — the budget the engine admits against —
    independent of `batch`.
    """
    dtype = dtype or jnp.dtype(cfg.dtype)
    if tf.paged_stack_supported(cfg):
        caches = tf.init_paged_stack_caches(cfg, n_pages, page_size,
                                            dtype, tp=tp)
    elif tf.latent_paged_stack_supported(cfg):
        caches = tf.init_latent_paged_stack_caches(cfg, n_pages, page_size,
                                                   dtype, tp=tp)
    else:
        # name the capability that's actually missing: page indirection
        # needs per-token cache blocks, which SWA rings and recurrent
        # (mamba/rwkv) carries don't have
        kinds = sorted(set(cfg.layer_kinds()))
        raise ValueError(
            f"paged serving needs per-token cache blocks (plain attention "
            f"KV or an MLA latent cache, no SWA ring); {cfg.name} (layer "
            f"kinds {kinds}, swa_window={cfg.swa_window}) has none — use "
            f"the 'dense' layout (serves every config) or 'recurrent' "
            f"(constant-size state for pure RWKV/Mamba configs)")
    return {
        "caches": caches,
        "lengths": jnp.zeros((batch,), jnp.int32),
        "positions": jnp.zeros((batch,), jnp.int32),
        "page_table": jnp.zeros((batch, max_pages), jnp.int32),
    }


def serve_state_specs(cfg: ModelConfig) -> dict:
    return {
        "caches": tf.stack_cache_specs(cfg),
        "lengths": ("batch",),
        "positions": ("batch",),
    }


# --------------------------------------------------------------------------
# dry-run input specs
# --------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape, tp: int = 1) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of the given shape.

    Modality frontends (VQ-GAN for chameleon, EnCodec for musicgen) are
    stubs: they produce the discrete token streams these specs describe.
    """
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind == "train":
        return {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
    if shape.kind == "prefill":
        return {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
    if shape.kind == "decode":
        state = jax.eval_shape(
            lambda: init_serve_state(cfg, B, S, tp=tp))
        return {"tokens": jax.ShapeDtypeStruct((B,), i32), "state": state}
    raise ValueError(shape.kind)
