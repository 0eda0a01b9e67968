"""Pallas TPU MoE token dispatch — the Dynamic-MultiQueue enqueue in kernel
form (JingZhao Table 1: Dynamic Enqueue / Dynamic Insert).

Tokens are scattered into per-expert logical queues that share one capacity
buffer [E, C, D]. The (expert, position) assignment is computed upstream
(router top-k + cumsum) and scalar-prefetched into SMEM so each grid step's
output BlockSpec can chase it: program t copies token t's row from HBM into
its queue slot through VMEM. Tokens whose queue is full (pos >= C) are
dropped exactly as a full NIC queue rejects a push — they write to a
sacrificial overflow row that is sliced off.

The output aliases a zero-initialized buffer (input_output_aliasing) so
untouched slots stay zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dispatch_kernel(eids_ref, pos_ref, tok_ref, init_ref, out_ref):
    del eids_ref, pos_ref, init_ref
    out_ref[0, 0] = tok_ref[0]


def moe_dispatch(tokens, expert_ids, positions, n_experts: int,
                 capacity: int, *, interpret: bool = False):
    """tokens: [T, D]; expert_ids/positions: [T] int32 -> [E, C, D]."""
    T, D = tokens.shape
    # overflow row C is the drop target; clamp positions into it
    pos_safe = jnp.minimum(positions, capacity).astype(jnp.int32)
    eids = expert_ids.astype(jnp.int32)
    zeros = jnp.zeros((n_experts, capacity + 1, D), tokens.dtype)

    def tok_map(t, eids_s, pos_s):
        return (t, 0)

    def out_map(t, eids_s, pos_s):
        return (eids_s[t], pos_s[t], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, D), tok_map),
            pl.BlockSpec((1, 1, D), out_map),   # aliased zero init
        ],
        out_specs=pl.BlockSpec((1, 1, D), out_map),
    )
    out = pl.pallas_call(
        _dispatch_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_experts, capacity + 1, D),
                                       tokens.dtype),
        input_output_aliases={3: 0},   # zeros buffer -> output
        interpret=interpret,
    )(eids, pos_safe, tokens, zeros)
    return out[:, :capacity]
