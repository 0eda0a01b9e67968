"""Pallas TPU WKV-6 chunked kernel (RWKV-6 data-dependent-decay attention).

One grid program per (batch, head): the [hd, hd] state matrix is VMEM
scratch, and each sequence chunk becomes dense [C, hd] x [hd, hd] / [C, C]
MXU matmuls with cumulative-decay weighting (GLA-style chunking — see
models/rwkv.py for the derivation and the pure-jnp oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, y_ref, sout_ref,
                state_scr, *, chunk, n_chunks):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state_scr[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)             # [C, hd]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)             # [1? hd] -> [hd]
    S_in = state_scr[...]                        # [hd, hd]

    cum = jnp.cumsum(lw, axis=0)
    cum_excl = cum - lw
    r_dec = r * jnp.exp(cum_excl)
    k_inv = k * jnp.exp(-cum)
    A = jax.lax.dot_general(r_dec, k_inv, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [C, C]
    tri = jax.lax.broadcasted_iota(jnp.int32, A.shape, 0) > \
        jax.lax.broadcasted_iota(jnp.int32, A.shape, 1)
    A = jnp.where(tri, A, 0.0)
    diag = jnp.sum(r * (u[None] * k), axis=1)    # [C]
    y = jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y += diag[:, None] * v
    y += jax.lax.dot_general(r_dec, S_in, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    w_last = jnp.exp(cum[-1])                    # [hd]
    k_carry = k * jnp.exp(cum[-1][None] - cum)   # [C, hd]
    state_scr[...] = w_last[:, None] * S_in + jax.lax.dot_general(
        k_carry, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(c == n_chunks - 1)
    def _final():
        sout_ref[0] = state_scr[...].astype(sout_ref.dtype)


def _wkv_decode_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s_ref,
                       y_ref, sout_ref):
    r = r_ref[...].astype(jnp.float32)           # [1, hd]
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    S = s_ref[0].astype(jnp.float32)             # [hd, hd]
    # y_e = Σ_d r_d (S_de + u_d k_d v_e);  S'_de = w_d S_de + k_d v_e
    kv = k[0][:, None] * v                       # [hd, hd] rank-1 outer
    y = jax.lax.dot_general(r, S + (u[0] * k[0])[:, None] * v,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [1, hd]
    y_ref[...] = y.astype(y_ref.dtype)
    sout_ref[0] = (w[0][:, None] * S + kv).astype(sout_ref.dtype)


def wkv6_decode(r, k, v, w, u, state, *, interpret: bool = False):
    """Single-token WKV-6 step (the serving decode recurrence, O(hd²)).

    r, k, v, w: [B,H,hd] (w is the per-channel decay multiplier, already
    exp(-exp(...))); u: [H,hd]; state: [B,H,hd,hd].
    Returns (y [B,H,hd] f32, state' [B,H,hd,hd] f32).
    """
    B, H, hd = r.shape
    rf, kf, vf, wf = (t.reshape(B * H, hd) for t in (r, k, v, w))
    uf = jnp.tile(u, (B, 1)).reshape(B * H, hd)
    sf = state.reshape(B * H, hd, hd)

    def vec_map(bh):
        return (bh, 0)

    def st_map(bh):
        return (bh, 0, 0)

    y, s_out = pl.pallas_call(
        _wkv_decode_kernel,
        grid=(B * H,),
        in_specs=[pl.BlockSpec((1, hd), vec_map)] * 5
        + [pl.BlockSpec((1, hd, hd), st_map)],
        out_specs=[
            pl.BlockSpec((1, hd), vec_map),
            pl.BlockSpec((1, hd, hd), st_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, hd), jnp.float32),
            jax.ShapeDtypeStruct((B * H, hd, hd), jnp.float32),
        ],
        interpret=interpret,
    )(rf, kf, vf, wf, uf, sf)
    return y.reshape(B, H, hd), s_out.reshape(B, H, hd, hd)


def wkv6_chunked(r, k, v, logw, u, state0, *, chunk: int = 32,
                 interpret: bool = False):
    """r,k,v,logw: [B,S,H,hd]; u: [H,hd]; state0: [B,H,hd,hd].

    Returns (y [B,S,H,hd] f32, state [B,H,hd,hd] f32).
    """
    B, S, H, hd = r.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        r, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (r, k, v))
        logw = jnp.pad(logw, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nc = Sp // chunk
    # [B*H, S, hd] layouts
    rf = r.transpose(0, 2, 1, 3).reshape(B * H, Sp, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Sp, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Sp, hd)
    lwf = logw.transpose(0, 2, 1, 3).reshape(B * H, Sp, hd)
    uf = jnp.tile(u, (B, 1)).reshape(B * H, hd)
    s0f = state0.reshape(B * H, hd, hd)

    def seq_map(bh, c):
        return (bh, c, 0)

    def bh_map(bh, c):
        return (bh, 0)

    def st_map(bh, c):
        return (bh, 0, 0)

    y, s_out = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk, n_chunks=nc),
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), seq_map),
            pl.BlockSpec((1, chunk, hd), seq_map),
            pl.BlockSpec((1, chunk, hd), seq_map),
            pl.BlockSpec((1, chunk, hd), seq_map),
            pl.BlockSpec((1, hd), bh_map),
            pl.BlockSpec((1, hd, hd), st_map),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hd), seq_map),
            pl.BlockSpec((1, hd, hd), st_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sp, hd), jnp.float32),
            jax.ShapeDtypeStruct((B * H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, lwf, uf, s0f)
    y = y.reshape(B, H, Sp, hd).transpose(0, 2, 1, 3)[:, :S]
    return y, s_out.reshape(B, H, hd, hd)
