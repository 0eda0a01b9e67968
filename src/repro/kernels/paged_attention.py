"""Paged KV decode attention — the Resource Subsystem's Gather-Data kernel.

JingZhao mapping (DESIGN.md §3): a sequence's KV lives scattered across a
shared page pool (the paper's ICM block); the page table (MTT analogue) is
scalar-prefetched into SMEM so BlockSpec index maps can chase it, and pages
stream through VMEM one block per grid step with online-softmax
accumulation in scratch.

Two backends behind one entry point:

- ``backend="pallas"`` — the TPU kernel below (interpret mode on CPU).
  Grid (B, KV, MP), last dim sequential; q: [B, H, hd]; k_pages/v_pages:
  [NP, KV, page, hd]; page_table: [B, MP] int32; lengths: [B] int32.
  The pools are KV-head-major so one grid step's block is a whole
  ``(page, hd)`` tile: Mosaic requires a block's last two dims to be
  (8, 128)-divisible or full, which a size-1 slice of a token-major
  ``[NP, page, KV, hd]`` pool's KV axis is not.
- ``backend="jnp"`` — a dense gather (``k_pages[page_table]``) feeding
  plain softmax attention; fast under jit on CPU, and the shape contract
  oracle for the kernel (see kernels/ref.py).

``paged_append`` is the matching Scatter-Data half: it writes one new
token's K/V into the pool slot named by (page_table, position), dropping
writes of inactive (VoQ-parked) slots instead of corrupting shared pages.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Pallas kernel
# --------------------------------------------------------------------------

def _pd_kernel(table_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
               m_scr, l_scr, acc_scr, *, scale, page, n_pages):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]
    base = p * page
    in_range = base < length

    @pl.when(in_range)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)         # [G, hd]
        k = k_ref[0, 0].astype(jnp.float32)         # [page, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [G, page]
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_scr[...]                         # [G, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        pr = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + pr.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            pr, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(p == n_pages - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _paged_decode_pallas(q, k_pages, v_pages, page_table, lengths, *,
                         scale, interpret: bool):
    B, H, hd = q.shape
    NP, KV, page, _ = k_pages.shape
    MP = page_table.shape[1]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)

    def q_map(b, kv, p, tbl, lens):
        return (b, kv, 0, 0)

    def kv_map(b, kv, p, tbl, lens):
        return (tbl[b, p], kv, 0, 0)

    def o_map(b, kv, p, tbl, lens):
        return (b, kv, 0, 0)

    # m/l scratch keep a trailing unit dim: 2-D VMEM refs tile cleanly,
    # where a 1-D (G,) ref would put G on the lane axis
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, MP),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), q_map),
            pl.BlockSpec((1, 1, page, hd), kv_map),
            pl.BlockSpec((1, 1, page, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), o_map),
        scratch_shapes=[pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, hd), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_pd_kernel, scale=scale, page=page, n_pages=MP),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(page_table, lengths, qg, k_pages, v_pages)
    return out.reshape(B, H, hd)


# --------------------------------------------------------------------------
# jnp backend (gather + softmax; also the serving path on CPU)
# --------------------------------------------------------------------------

def _paged_decode_jnp(q, k_pages, v_pages, page_table, lengths, *, scale):
    # one implementation of gathered paged softmax exists: the ref oracle
    # (it stays an *independent* check for the Pallas kernel above).
    # Both backends pay O(MP) for the table walk — the gather touches
    # every table entry and the Pallas grid runs MP sequential steps —
    # so callers bound MP to the batch's live page count via
    # `live_table_width` (the engine's PagedKV.sync exports tables at
    # that bucketed width) instead of the worst-case max_pages.
    from repro.kernels.ref import paged_decode_attention_ref
    return paged_decode_attention_ref(q, k_pages, v_pages, page_table,
                                      lengths, scale=scale)


def live_table_width(n_live_pages: int, max_pages: int) -> int:
    """Page-table width covering ``n_live_pages``, bucketed to powers of
    two (capped at ``max_pages``).

    Exporting a max_pages-wide table makes every decode pay for the
    worst-case sequence length: the jnp oracle gathers
    ``k_pages[page_table]`` for all MP entries and the Pallas kernel's
    grid runs MP sequential steps, live or not. Bucketing the exported
    width to the next power of two bounds the work by the batch's
    actual page residency while capping the number of distinct compiled
    decode shapes at log2(max_pages). Entries past a slot's live pages
    are id 0 — attention masks them via ``lengths``, so any width >=
    the live count is math-identical (pinned by tests).
    """
    w = 1
    while w < min(max(n_live_pages, 1), max_pages):
        w *= 2
    return min(w, max_pages)


# --------------------------------------------------------------------------
# public entry points
# --------------------------------------------------------------------------

def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           scale=None, backend: str = "auto",
                           interpret: bool = False):
    """Single-token attention through a page table. Returns [B, H, hd].

    backend: "pallas" (TPU kernel; interpret-mode elsewhere when
    ``interpret=True``), "jnp" (gathered dense softmax), or "auto"
    (pallas on TPU, jnp otherwise — the serving default).
    """
    hd = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend == "pallas":
        return _paged_decode_pallas(q, k_pages, v_pages, page_table, lengths,
                                    scale=scale, interpret=interpret)
    if backend == "jnp":
        return _paged_decode_jnp(q, k_pages, v_pages, page_table, lengths,
                                 scale=scale)
    raise ValueError(backend)


def paged_append(k_pages, v_pages, k_new, v_new, page_table, positions,
                 active: Optional[jnp.ndarray] = None):
    """Write one token's K/V into the shared pools (Scatter-Data half).

    k_pages/v_pages: [NP, KV, page, hd]; k_new/v_new: [B, KV, hd];
    page_table: [B, MP]; positions: [B] slot each token lands at.
    ``active`` [B] bool: inactive (parked) slots' writes are *dropped* —
    routed to an out-of-range page id — so a frozen sequence can never
    corrupt pages owned by someone else (paper §4.1.1 per-connection
    isolation).  Pages are exclusively owned, so the batched scatter is
    conflict-free by construction.
    """
    NP, _, page, _ = k_pages.shape
    B = positions.shape[0]
    bidx = jnp.arange(B)
    pid = page_table[bidx, positions // page]          # [B]
    off = positions % page
    if active is not None:
        pid = jnp.where(active, pid, NP)               # out of range -> drop
    # [pid, :, off] indexes one token row across every KV head; numpy
    # advanced-index rules put the batch dim first, so the update is
    # exactly k_new's [B, KV, hd]
    k_pages = k_pages.at[pid, :, off].set(
        k_new.astype(k_pages.dtype), mode="drop")
    v_pages = v_pages.at[pid, :, off].set(
        v_new.astype(v_pages.dtype), mode="drop")
    return k_pages, v_pages
