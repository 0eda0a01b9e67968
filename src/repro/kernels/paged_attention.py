"""Paged KV decode attention — the Resource Subsystem's Gather-Data kernel.

JingZhao mapping (DESIGN.md §3): a sequence's KV lives scattered across a
shared page pool (the paper's ICM block); the page table (MTT analogue) is
scalar-prefetched into SMEM, and the kernel chases it with its own DMAs,
fetching only the pages a sequence holds, with online-softmax
accumulation in scratch.

Two backends behind one entry point:

- ``backend="pallas"`` — the TPU kernel below (interpret mode on CPU).
  Grid (B, ceil(MP / ppb)), both dims sequential; q: [B, H, hd];
  k_pages/v_pages: [NP, KV, page, hd] left in HBM; page_table: [B, MP]
  int32; lengths: [B] int32. One grid step covers one slot, all of its
  KV heads and a block of ``ppb`` consecutive table entries. The pools
  are KV-head-major, so ``k_pages[id]`` is one contiguous
  ``(KV, page, hd)`` tile: one DMA per live page, into a double-buffered
  ``[2, KV, ppb, page, hd]`` VMEM buffer. Pages at or past a slot's
  ``ceil(length / page)`` are never fetched, and blocks past them are
  skipped; the next live block (of this slot or the next) is fetched
  while the current one computes.
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

# VMEM bytes of one K (or V) block buffer; the kernel holds four (K and V,
# double-buffered). Fixed from a sweep on a TPU v5e (PERF.md §6, PR 14).
BLOCK_BYTES = 1 << 20


def pages_per_block(n_kv: int, page: int, head_dim: int, itemsize: int,
                    max_pages: int) -> int:
    """Table entries one grid step covers: the largest power of two whose
    ``(n_kv, ppb * page, head_dim)`` block fits ``BLOCK_BYTES``, capped at
    ``max_pages`` (and at least 1)."""
    tile = n_kv * page * head_dim * itemsize
    ppb = 1
    while 2 * ppb * tile <= BLOCK_BYTES:
        ppb *= 2
    return min(ppb, max_pages)


# --------------------------------------------------------------------------
# Pallas kernel
# --------------------------------------------------------------------------

def _pd_kernel(table_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
               k_buf, v_buf, sems, m_scr, l_scr, acc_scr, cursor,
               *, scale, page, ppb, n_blocks):
    b, i = pl.program_id(0), pl.program_id(1)
    n_slots = pl.num_programs(0)
    max_pages = table_ref.shape[1]

    def live_pages(slot):
        return jnp.minimum(pl.cdiv(lengths_ref[slot], page), max_pages)

    def start_block(slot, blk, buf, n):
        # one DMA per live page: pool tile [KV, page, hd] -> page j of
        # every head in the buffer (whole (page, hd) tiles, so head_dim
        # under the 128 lanes needs no lane-aligned slice)
        def body(j, carry):
            pid = table_ref[slot, blk * ppb + j]
            pltpu.make_async_copy(k_hbm.at[pid], k_buf.at[buf, :, j],
                                  sems.at[0, buf]).start()
            pltpu.make_async_copy(v_hbm.at[pid], v_buf.at[buf, :, j],
                                  sems.at[1, buf]).start()
            return carry
        jax.lax.fori_loop(0, n, body, 0)

    def wait_block(buf, n):
        # each wait consumes one page's bytes of the shared semaphore
        def body(j, carry):
            pltpu.make_async_copy(k_hbm.at[0], k_buf.at[buf, :, 0],
                                  sems.at[0, buf]).wait()
            pltpu.make_async_copy(v_hbm.at[0], v_buf.at[buf, :, 0],
                                  sems.at[1, buf]).wait()
            return carry
        jax.lax.fori_loop(0, n, body, 0)

    # cursor[0]: buffer the next live block lands in; cursor[1]: 1 when
    # the previous live step already started that block's copies
    @pl.when((b == 0) & (i == 0))
    def _first():
        cursor[0] = 0
        cursor[1] = 0

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]
    n_live = live_pages(b)
    first = i * ppb

    @pl.when(first < n_live)
    def _compute():
        buf = cursor[0]
        n = jnp.minimum(ppb, n_live - first)

        @pl.when(cursor[1] == 0)
        def _fetch_own():
            start_block(b, i, buf, n)

        # fetch the next live block into the other buffer before computing
        more_here = first + ppb < n_live
        nb = jnp.minimum(b + 1, n_slots - 1)
        next_slot = (b + 1 < n_slots) & (live_pages(nb) > 0)

        @pl.when(more_here)
        def _prefetch_here():
            start_block(b, i + 1, 1 - buf,
                        jnp.minimum(ppb, n_live - first - ppb))

        @pl.when(jnp.logical_not(more_here) & next_slot)
        def _prefetch_next_slot():
            start_block(nb, 0, 1 - buf, jnp.minimum(ppb, live_pages(nb)))

        cursor[0] = 1 - buf
        cursor[1] = (more_here | next_slot).astype(jnp.int32)
        wait_block(buf, n)

        _, KV, _, hd = k_hbm.shape
        q = q_ref[0]                                # [KV, G, hd]
        k = k_buf[buf].reshape(KV, ppb * page, hd)  # token order per head
        if q.dtype != k.dtype:
            q, k = q.astype(jnp.float32), k.astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # [KV, G, T]
        pos = first * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_scr[...]                         # [KV, G, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
        pr = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + pr.sum(axis=2, keepdims=True)
        # rows past the length hold pages never fetched (stale or
        # uninitialized VMEM): zero them, since 0 * NaN is NaN
        v = v_buf[buf].reshape(KV, ppb * page, hd).astype(jnp.float32)
        row = first * page + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
        v = jnp.where(row < length, v, 0.0)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            pr, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(i == n_blocks - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _paged_decode_pallas(q, k_pages, v_pages, page_table, lengths, *,
                         scale, interpret):
    B, H, hd = q.shape
    NP, KV, page, _ = k_pages.shape
    MP = page_table.shape[1]
    G = H // KV
    ppb = pages_per_block(KV, page, hd, k_pages.dtype.itemsize, MP)
    n_blocks = pl.cdiv(MP, ppb)
    qg = q.reshape(B, KV, G, hd)

    def slot_map(b, i, tbl, lens):
        return (b, 0, 0, 0)

    # m/l scratch keep a trailing unit dim: the lane axis stays whole
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_blocks),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), slot_map),
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd), slot_map),
        scratch_shapes=[
            pltpu.VMEM((2, KV, ppb, page, hd), k_pages.dtype),
            pltpu.VMEM((2, KV, ppb, page, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, hd), jnp.float32),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_pd_kernel, scale=scale, page=page, ppb=ppb,
                          n_blocks=n_blocks),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        # a block's prefetch crosses slots: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
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
    # The gather touches every table entry, so callers bound MP to the
    # batch's live page count via `live_table_width` (the engine's
    # PagedKV.sync exports tables at that bucketed width) instead of the
    # worst-case max_pages.
    from repro.kernels.ref import paged_decode_attention_ref
    return paged_decode_attention_ref(q, k_pages, v_pages, page_table,
                                      lengths, scale=scale)


def live_table_width(n_live_pages: int, max_pages: int) -> int:
    """Page-table width covering ``n_live_pages``, bucketed to powers of
    two (capped at ``max_pages``).

    Exporting a max_pages-wide table makes every decode pay for the
    worst-case sequence length: the jnp oracle gathers
    ``k_pages[page_table]`` for all MP entries, and the Pallas kernel
    takes a grid step (skipped when dead, but not free) for each block
    of entries. Bucketing the exported
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
                           interpret=False):
    """Single-token attention through a page table. Returns [B, H, hd].

    backend: "pallas" (TPU kernel; interpret-mode elsewhere when
    ``interpret`` is True or a ``pltpu.InterpretParams``), "jnp"
    (gathered dense softmax), or "auto"
    (pallas on TPU, jnp otherwise — the serving default). The kernel
    slices the pools page by page, which the TPU compiler accepts only
    for a lane-aligned head_dim (a multiple of 128): "auto" serves other
    head sizes through the gather.
    """
    hd = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    if backend == "auto":
        backend = ("pallas" if jax.default_backend() == "tpu"
                   and hd % 128 == 0 else "jnp")
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
