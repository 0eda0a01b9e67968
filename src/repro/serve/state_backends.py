"""Built-in StateBackend implementations (Resource Subsystem, DESIGN.md
§2§3§10).

A slot's decode state is backend-defined — the JingZhao move of keeping
the frame fixed while the resource tier swaps layouts:

- `DenseKV` ("dense"): per-slot `[slots, cache_len, KV, hd]` slabs.
  Serves every architecture (init_stack_caches is kind-generic).
- `PagedKV` ("paged"): shared `[n_pages, KV, page_size, hd]` pool behind
  per-slot page tables (the MTT made into the actual memory layout).
- `LatentPagedKV` ("latent"): MLA's absorbed-decode cache behind the
  same MTT — `[kv_lora_rank + qk_rope_dim]` bytes per token instead of
  2*KV*hd (DeepSeek-style, ~1/10th the page bytes).
- `RecurrentState` ("recurrent"): constant-size `[H, hd, hd]`-style
  carries for pure RWKV/Mamba stacks — O(1) footprint, no growth, no
  pages; park/unpark moves a few KB.

All four sit behind the same `StateBackend` protocol, so the engine
drives them through one code path; `tests/test_paged_kv.py` pins
dense/paged logit-identical and `tests/test_state_backends.py` pins
engine streams byte-identical to model-level decode per backend. The
PagePool (admission accounting + alloc-on-append) is owned here; `sync`
re-exports MTT rows into the decode state only when some
park/admit/growth dirtied them.
"""
from __future__ import annotations

from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.resource import PagePool
from repro.kernels.paged_attention import live_table_width
from repro.models import lm
from repro.models import transformer as tf
from repro.serve.api import (EngineConfig, ParkMeta, Request,
                             register_state_backend)


class _PooledKV:
    """Shared plumbing: the PagePool (MTT accounting) + growth helpers."""

    def __init__(self, cfg, ecfg: EngineConfig):
        self.cfg = cfg
        self.ecfg = ecfg
        self.pool = PagePool(ecfg.n_pages, ecfg.page_size)
        # capability flags (StateBackend protocol): chunked prefill and
        # the block prefix cache extend per-token dense K/V rows, which
        # only plain-attention caches expose
        self.supports_chunked_prefill = tf.chunked_prefill_supported(cfg)
        self.supports_prefix_share = tf.chunked_prefill_supported(cfg)

    def admission_error(self, req: Request) -> Optional[str]:
        """A single request needing more pages than the whole pool can
        never complete — it would park/preempt-cycle forever."""
        worst = min(len(req.prompt) + req.max_new_tokens,
                    self.ecfg.cache_len)
        if -(-worst // self.ecfg.page_size) > self.ecfg.n_pages:
            return (f"request needs {worst} KV tokens but the pool holds "
                    f"only {self.ecfg.n_pages * self.ecfg.page_size}")
        return None

    def append(self, req_id: int, n_tokens: int) -> bool:
        """Alloc-on-append: grow req's page claim to cover n_tokens."""
        return self.pool.ensure_capacity(req_id, n_tokens)

    def reserve_span(self, req_id: int, n_tokens: int) -> bool:
        """Decode-span headroom: claim pages covering `n_tokens` total
        tokens *before* a fused decode span runs — alloc-on-append
        cannot fire inside the jitted lax.scan (DESIGN.md §3.6). Same
        page accounting as `append`; dense slabs are covered by the
        admission footprint, so for them this never allocates."""
        return self.pool.ensure_capacity(req_id, n_tokens)

    def held(self, req_id: int) -> int:
        return len(self.pool.pages_of(req_id))

    def release(self, req_id: int) -> None:
        self.pool.release(req_id)

    # prefix-cache payload pinning: only layouts whose payloads live in
    # the pool (paged) need real refcounts
    def cache_retain(self, payload) -> None:
        pass

    def cache_release(self, payload) -> None:
        pass

    # -- crash recovery (DESIGN.md §9) ----------------------------------
    # Pool bookkeeping travels as JSON-able pairs (not int-keyed dicts:
    # a JSON round-trip through the Checkpointer manifest would turn
    # int keys into strings).
    def _export_pool(self) -> dict:
        p = self.pool
        return {
            "free": [int(x) for x in p.free],
            "tables": [[int(r), [int(x) for x in pages]]
                       for r, pages in p.tables.items()],
            "refcnt": [[int(g), int(c)] for g, c in p.refcnt.items()],
            "peak": int(p.peak),
        }

    def _import_pool(self, snap: dict) -> None:
        p = self.pool
        p.free = [int(x) for x in snap["free"]]
        p.tables = {int(r): [int(x) for x in pages]
                    for r, pages in snap["tables"]}
        p.refcnt = {int(g): int(c) for g, c in snap["refcnt"]}
        p.peak = int(snap["peak"])

    # Default payload codec: payloads are device KV trees (the dense
    # layout) — copy to host arrays and back. Layouts with pool
    # indirection override with their handle type.
    def snapshot_payload(self, payload):
        return jax.tree.map(np.asarray, payload)

    def restore_payload(self, data):
        return jax.tree.map(jnp.asarray, data)


@register_state_backend("dense")
class DenseKV(_PooledKV):
    """Per-slot contiguous slabs; worst-case reservation at admission.

    No indirection tables -> `sync` is a no-op and capacity can never run
    out mid-decode (`needs_growth = False`): the footprint reserved up
    front covers every token the request may write. The slab layout is
    kind-generic (init_stack_caches allocates whatever each block kind
    declares), so dense serves every architecture in configs/ — at
    worst-case bytes per slot.
    """

    needs_growth = False

    def init_state(self) -> dict:
        return lm.init_serve_state(self.cfg, self.ecfg.slots,
                                   self.ecfg.cache_len, filled=False)

    def footprint(self, req: Request) -> int:
        return min(len(req.prompt) + req.max_new_tokens,
                   self.ecfg.cache_len)

    def prefill_into_slot(self, state: dict, slot: int, req_id: int,
                          caches, length: int) -> dict:
        state["caches"] = _slot_insert(state["caches"], caches, slot)
        return state

    def slot_caches(self, state: dict, slot: int, req_id: int):
        return _slot_view(state["caches"], slot)

    def store_chunk(self, state: dict, slot: int, req_id: int, caches,
                    start: int, n_tokens: int) -> dict:
        # write back only the rows the chunk produced (a full-slab copy
        # per chunk would be O(cache_len) traffic for O(chunk) new data);
        # this also discards pad-row scatter past n_tokens, keeping the
        # slab zero beyond the valid length like monolithic prefill
        src = {
            "prefix": [jax.tree.map(
                lambda c: c[:, start:start + n_tokens], t)
                for t in caches["prefix"]],
            "groups": (jax.tree.map(
                lambda c: c[:, :, start:start + n_tokens], caches["groups"])
                if caches.get("groups") is not None else None),
        }
        state["caches"] = _slot_write_range(
            state["caches"], src, slot, start, n_tokens)
        return state

    def share_prefix(self, state: dict, slot: int, req_id: int,
                     payloads, n_tokens: int) -> dict:
        # dense has no indirection to share through: copy the cached
        # per-block KV slices into the slot's slab
        state["caches"] = _slot_write_range(
            state["caches"], _cat_blocks(payloads), slot, 0, n_tokens)
        return state

    def block_payload(self, state: dict, slot: int, req_id: int,
                      block: int) -> Any:
        ps = self.ecfg.page_size
        return _slot_range_view(state["caches"], slot,
                                block * ps, (block + 1) * ps)

    def park(self, state: dict, slot: int,
             req_id: int) -> Tuple[Any, ParkMeta]:
        caches = _slot_extract(state["caches"], slot)
        meta = ParkMeta(int(state["lengths"][slot]),
                        int(state["positions"][slot]), slot, 0)
        self.pool.release(req_id)
        return caches, meta

    def unpark(self, state: dict, slot: int, req: Request, caches,
               meta: ParkMeta) -> Tuple[bool, dict]:
        # clamp to cache_len exactly like `footprint` does: a request
        # admitted with a clamped footprint must not demand more capacity
        # at unpark than submit validated, or it re-parks forever
        need = min(meta.length + req.max_new_tokens - len(req.tokens_out),
                   self.ecfg.cache_len)
        if not self.pool.ensure_capacity(req.req_id, need):
            return False, state
        state["caches"] = _slot_restore(state["caches"], caches, slot)
        return True, state

    def mark_dirty(self) -> None:
        pass

    def sync(self, state: dict,
             slot_req_ids: List[Optional[int]]) -> dict:
        return state

    def export_state(self, state: dict) -> dict:
        return {
            "pool": self._export_pool(),
            "lengths": np.asarray(state["lengths"]),
            "positions": np.asarray(state["positions"]),
            "caches": jax.tree.map(np.asarray, state["caches"]),
        }

    def import_state(self, snap: dict) -> dict:
        self._import_pool(snap["pool"])
        state = self.init_state()
        state["lengths"] = jnp.asarray(np.asarray(snap["lengths"]))
        state["positions"] = jnp.asarray(np.asarray(snap["positions"]))
        state["caches"] = jax.tree.map(jnp.asarray, snap["caches"])
        return state


@register_state_backend("recurrent")
class RecurrentState(DenseKV):
    """Constant-size recurrent carries for pure RWKV/Mamba stacks.

    The state a slot decodes from is the scan carry itself — RWKV's
    `[H, hd, hd]` wkv matrix + token-shift rows, Mamba's conv window +
    `[Di, N]` SSM state — a few KB that never grows with sequence
    length. So: `footprint()` is O(1) (one accounting page pins the
    slot), `needs_growth = False` (`reserve_span` trivially succeeds —
    the engine never even calls it), park/unpark moves the carry with no
    page movement (`ParkMeta.n_pages = 0`), and prefill runs the models'
    chunked scans (`wkv_chunked` / `mamba_forward`, backed by the
    `kernels/wkv6.py` / `kernels/linear_scan.py` paths on TPU) and hands
    the final carry to the slot via the same `_slot_insert` as dense.

    Prefix sharing is explicitly declined (`supports_prefix_share =
    False`): a recurrent carry folds the whole prefix into one tensor,
    so there are no per-token blocks to share or to extend chunk-wise.
    """

    needs_growth = False

    def __init__(self, cfg, ecfg: EngineConfig):
        if not tf.recurrent_state_supported(cfg):
            kinds = sorted(set(cfg.layer_kinds()))
            raise ValueError(
                f"recurrent state serving needs every mixer to carry a "
                f"constant-size recurrence (mamba/rwkv); {cfg.name} has "
                f"layer kinds {kinds} — attention layers grow per token, "
                f"use the 'dense' or 'paged' layout")
        super().__init__(cfg, ecfg)
        self.supports_chunked_prefill = False
        self.supports_prefix_share = False

    def footprint(self, req: Request) -> int:
        # O(1): one accounting page marks the slot resident in the MTT;
        # the carry's bytes are fixed at init and never grow
        return 1

    def admission_error(self, req: Request) -> Optional[str]:
        return None               # constant-size state always fits a slot

    def slot_caches(self, state: dict, slot: int, req_id: int):
        raise NotImplementedError(
            "recurrent state has no per-token rows to stage: chunked "
            "prefill is unsupported (supports_chunked_prefill = False)")

    def store_chunk(self, state: dict, slot: int, req_id: int, caches,
                    start: int, n_tokens: int) -> dict:
        raise NotImplementedError(
            "recurrent state has no per-token rows to extend: chunked "
            "prefill is unsupported (supports_chunked_prefill = False)")

    def share_prefix(self, state: dict, slot: int, req_id: int,
                     payloads, n_tokens: int) -> dict:
        raise NotImplementedError(
            "a recurrent carry folds the whole prefix into one tensor — "
            "no per-token blocks to share (supports_prefix_share = False)")

    def block_payload(self, state: dict, slot: int, req_id: int,
                      block: int) -> Any:
        raise NotImplementedError(
            "a recurrent carry folds the whole prefix into one tensor — "
            "no per-token blocks to export (supports_prefix_share = False)")

    def unpark(self, state: dict, slot: int, req: Request, caches,
               meta: ParkMeta) -> Tuple[bool, dict]:
        if not self.pool.ensure_capacity(req.req_id, 1):
            return False, state
        state["caches"] = _slot_restore(state["caches"], caches, slot)
        return True, state


@register_state_backend("paged")
class PagedKV(_PooledKV):
    """Shared page pool + per-slot MTT rows (DESIGN.md §3).

    Admission charges the prompt footprint only; growth happens at page
    boundaries (`needs_growth = True` -> the engine runs its
    alloc-on-append pass each step). Park moves exactly the sequence's
    pages to host arrays; unpark re-allocates (ids may differ — the
    table is re-exported by `sync`).
    """

    needs_growth = True

    def __init__(self, cfg, ecfg: EngineConfig):
        if ecfg.cache_len % ecfg.page_size:
            raise ValueError("cache_len must be a page_size multiple")
        super().__init__(cfg, ecfg)
        self.max_pages = ecfg.cache_len // ecfg.page_size
        self._dirty = False

    def init_state(self) -> dict:
        return lm.init_paged_serve_state(
            self.cfg, self.ecfg.slots, self.ecfg.n_pages,
            self.ecfg.page_size, self.max_pages)

    def footprint(self, req: Request) -> int:
        return len(req.prompt) + 1

    def prefill_into_slot(self, state: dict, slot: int, req_id: int,
                          caches, length: int) -> dict:
        pages = self.pool.pages_of(req_id)
        chunks = tf.dense_to_pages(caches, len(pages), self.ecfg.page_size)
        state["caches"] = tf.scatter_pages(state["caches"], chunks, pages)
        self._dirty = True
        return state

    def slot_caches(self, state: dict, slot: int, req_id: int):
        # stage the slot's pages (token order, shared prefix included) as
        # the dense batch-1 tree the chunked-prefill step extends
        pages = self.pool.pages_of(req_id)
        gathered = tf.gather_pages(state["caches"], pages)
        return tf.pages_to_dense(gathered, self.ecfg.cache_len,
                                 self.ecfg.page_size)

    def store_chunk(self, state: dict, slot: int, req_id: int, caches,
                    start: int, n_tokens: int) -> dict:
        """Scatter exactly the pages the chunk touched back into the pool.

        start is page-aligned and >= the shared-prefix extent, so a chunk
        write can never land in a page another sequence (or the prefix
        cache) also references.
        """
        ps = self.ecfg.page_size
        p0, p1 = start // ps, -(-(start + n_tokens) // ps)
        pages = self.pool.pages_of(req_id)[p0:p1]
        data = tf.dense_to_pages(caches, len(pages), ps, first=p0)
        state["caches"] = tf.scatter_pages(state["caches"], data, pages)
        self._dirty = True
        return state

    def share_prefix(self, state: dict, slot: int, req_id: int,
                     payloads, n_tokens: int) -> dict:
        # zero-copy: the cached pages join this sequence's table (one new
        # ref each); the pool data is already the prefix KV
        self.pool.share(req_id, list(payloads))
        self._dirty = True
        return state

    def block_payload(self, state: dict, slot: int, req_id: int,
                      block: int) -> Any:
        return self.pool.pages_of(req_id)[block]

    def cache_retain(self, payload) -> None:
        self.pool.addref([payload])

    def cache_release(self, payload) -> None:
        self.pool.decref([payload])

    def park(self, state: dict, slot: int,
             req_id: int) -> Tuple[Any, ParkMeta]:
        page_ids = self.pool.pages_of(req_id)
        caches = jax.tree.map(
            np.asarray, tf.gather_pages(state["caches"], page_ids))
        meta = ParkMeta(int(state["lengths"][slot]),
                        int(state["positions"][slot]), slot, len(page_ids))
        self.pool.release(req_id)
        self._dirty = True
        return caches, meta

    def unpark(self, state: dict, slot: int, req: Request, caches,
               meta: ParkMeta) -> Tuple[bool, dict]:
        pages = self.pool.alloc(req.req_id, meta.n_pages)
        if pages is None:
            return False, state
        state["caches"] = tf.scatter_pages(state["caches"], caches, pages)
        self._dirty = True
        return True, state

    def mark_dirty(self) -> None:
        self._dirty = True

    def sync(self, state: dict,
             slot_req_ids: List[Optional[int]]) -> dict:
        if self._dirty:
            # export the MTT at the batch's live width (pow2-bucketed),
            # not max_pages: the decode gather/grid walks every exported
            # entry, so table width is decode cost. Any growth or
            # release dirties the table, so the bucket can never lag
            # behind the true live page count.
            live = max((len(self.pool.tables.get(r, []))
                        for r in slot_req_ids if r is not None), default=0)
            width = live_table_width(live, self.max_pages)
            state["page_table"] = jnp.asarray(
                self.pool.table_matrix(slot_req_ids, width))
            self._dirty = False
        return state

    # -- crash recovery (DESIGN.md §9) ----------------------------------
    # Prefix-cache payloads are pool page ids: a plain int round-trips.
    def snapshot_payload(self, payload):
        return int(payload)

    def restore_payload(self, data):
        return int(data)

    def export_state(self, state: dict) -> dict:
        """Capture only the referenced pages (tables + cache-held), in
        sorted-id order — free pages hold stale bytes no table can reach,
        so restoring them would be wasted snapshot bytes."""
        used = sorted(int(g) for g in self.pool.refcnt)
        pages = (jax.tree.map(
            np.asarray, tf.gather_pages(state["caches"], used))
            if used else None)
        return {
            "pool": self._export_pool(),
            "lengths": np.asarray(state["lengths"]),
            "positions": np.asarray(state["positions"]),
            "page_ids": used,
            "pages": pages,
        }

    def import_state(self, snap: dict) -> dict:
        """Rebuild the pool contents at the SAME page ids the snapshot
        recorded — tables, refcounts, and the free stack restore
        verbatim, so post-restore alloc order (and therefore the MTT)
        matches the crashed process exactly."""
        self._import_pool(snap["pool"])
        state = self.init_state()
        state["lengths"] = jnp.asarray(np.asarray(snap["lengths"]))
        state["positions"] = jnp.asarray(np.asarray(snap["positions"]))
        page_ids = [int(g) for g in snap["page_ids"]]
        if page_ids:
            state["caches"] = tf.scatter_pages(
                state["caches"],
                jax.tree.map(jnp.asarray, snap["pages"]), page_ids)
        self._dirty = True
        return state


@register_state_backend("latent")
class LatentPagedKV(PagedKV):
    """MLA latent cache behind the paged MTT (DESIGN.md §10).

    Same pool mechanics as `PagedKV` — page tables, alloc-on-append
    growth, page-granular park/unpark, referenced-page snapshots — but
    the pool leaves hold the absorbed-decode cache of `models/mla.py`:
    `[n_pages, page, kv_lora_rank]` + `[n_pages, page, qk_rope_dim]` per
    layer, ~1/10th the bytes of full K/V pages. Decode dispatches to the
    absorbed path through the table (`transformer._mla_decode_paged`).

    Chunked prefill and prefix sharing are declined for now: the MLA
    prefill path is monolithic (no per-chunk latent extension), and a
    capability flag — not a config sniff — is what tells the engine.
    """

    def __init__(self, cfg, ecfg: EngineConfig):
        if not tf.latent_paged_stack_supported(cfg):
            kinds = sorted(set(cfg.layer_kinds()))
            raise ValueError(
                f"latent-paged serving needs every layer to be MLA "
                f"attention (cfg.mla set, no SWA ring); {cfg.name} has "
                f"layer kinds {kinds} with mla={cfg.mla is not None}, "
                f"swa_window={cfg.swa_window} — use 'dense' (any config) "
                f"or 'paged' (plain-attention configs)")
        super().__init__(cfg, ecfg)
        self.supports_chunked_prefill = False
        self.supports_prefix_share = False


# -- structure-aware slot insert / extract ---------------------------------
#
# Stack caches are {"prefix": [leaf trees with batch at axis 0],
# "groups": leaf trees with a leading n_groups axis, batch at axis 1}.
# Indexing every leaf at axis 0 (the seed's `_tree_insert`) silently hits
# the *group* axis of scanned leaves; these helpers pick the batch axis by
# subtree, which the paged-vs-dense equivalence test pins down. They are
# leaf-shape-generic: attention K/V rows, MLA latent rows, and recurrent
# carries all move through the same maps.

def _slot_set(dst, src, slot: int, pre_slice, grp_slice):
    """Write per-slot data into every leaf, batch axis chosen by subtree."""

    def pre(d, s):
        return d.at[slot].set(jnp.asarray(pre_slice(s)).astype(d.dtype))

    def grp(d, s):
        return d.at[:, slot].set(jnp.asarray(grp_slice(s)).astype(d.dtype))

    out = {"prefix": [jax.tree.map(pre, d, s)
                      for d, s in zip(dst["prefix"], src["prefix"])],
           "groups": None}
    if dst.get("groups") is not None:
        out["groups"] = jax.tree.map(grp, dst["groups"], src["groups"])
    return out


def _slot_insert(dst, src, slot: int):
    """Insert a batch-1 cache tree `src` into slot `slot` of `dst`."""
    return _slot_set(dst, src, slot, lambda s: s[0], lambda s: s[:, 0])


def _slot_restore(dst, src, slot: int):
    """Insert a batch-free extracted tree (from _slot_extract) back."""
    return _slot_set(dst, src, slot, lambda s: s, lambda s: s)


def _slot_extract(tree, slot: int):
    """Pull slot `slot` out of every leaf (host numpy copies)."""
    return {
        "prefix": [jax.tree.map(lambda c: np.asarray(c[slot]), t)
                   for t in tree["prefix"]],
        "groups": (jax.tree.map(lambda c: np.asarray(c[:, slot]),
                                tree["groups"])
                   if tree.get("groups") is not None else None),
    }


def _slot_view(tree, slot: int):
    """Batch-1 device view of one slot (keeps the batch axis, no host
    round-trip) — the staging tree chunked prefill extends in place."""
    return {
        "prefix": [jax.tree.map(lambda c: c[slot:slot + 1], t)
                   for t in tree["prefix"]],
        "groups": (jax.tree.map(lambda c: c[:, slot:slot + 1],
                                tree["groups"])
                   if tree.get("groups") is not None else None),
    }


def _slot_range_view(tree, slot: int, t0: int, t1: int):
    """Batch-1 view of one slot restricted to token positions [t0, t1)
    (the per-block payload the dense prefix cache stores)."""
    return {
        "prefix": [jax.tree.map(lambda c: c[slot:slot + 1, t0:t1], t)
                   for t in tree["prefix"]],
        "groups": (jax.tree.map(lambda c: c[:, slot:slot + 1, t0:t1],
                                tree["groups"])
                   if tree.get("groups") is not None else None),
    }


def _cat_blocks(blocks):
    """Concatenate per-block payload trees along the token axis."""
    if len(blocks) == 1:
        return blocks[0]
    return {
        "prefix": [jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1),
                                *[b["prefix"][i] for b in blocks])
                   for i in range(len(blocks[0]["prefix"]))],
        "groups": (jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=2),
                                *[b["groups"] for b in blocks])
                   if blocks[0].get("groups") is not None else None),
    }


def _slot_write_range(dst, src, slot: int, t0: int, length: int):
    """Write a batch-1 tree `src` (token extent `length`) into slot
    `slot` of `dst` at token positions [t0, t0+length)."""

    def pre(d, s):
        return d.at[slot, t0:t0 + length].set(
            jnp.asarray(s[0]).astype(d.dtype))

    def grp(d, s):
        return d.at[:, slot, t0:t0 + length].set(
            jnp.asarray(s[:, 0]).astype(d.dtype))

    out = {"prefix": [jax.tree.map(pre, d, s)
                      for d, s in zip(dst["prefix"], src["prefix"])],
           "groups": None}
    if dst.get("groups") is not None:
        out["groups"] = jax.tree.map(grp, dst["groups"], src["groups"])
    return out
