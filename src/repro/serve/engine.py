"""Continuous-batching serving engine — a thin driver over the pluggable
subsystem API (serve/api.py; DESIGN.md §2, §3).

JingZhao mapping: the engine is the fixed frame; the subsystems plug in
behind protocols and are selected by name through `EngineConfig`:

  Scheduler        (Queue Subsystem)    -> admission/ordering over QoS
                   class queues: fcfs | priority | round_robin
                   (serve/schedulers.py)
  StateBackend     (Resource Subsystem) -> decode-state layout + page
                   accounting: dense slabs | paged pool behind MTT rows
                   | MLA latent pages | constant-size recurrent carries
                   (serve/state_backends.py)
  ParkingTransport (Transport Subsystem)-> host-tier VoQ overflow moves,
                   bus-timed (serve/parking.py)
  Sampler          (per-token handler)  -> on-device token selection:
                   greedy | stochastic (serve/samplers.py, §3.7)

The engine loop itself is layout- and policy-free: admit from the
scheduler, restore due unparks, stream one chunk of each PREFILLING
slot's prompt under the per-step token budget (DESIGN.md §3.4), run the
backend's alloc-on-append pass, reserve page headroom for the coming
decode span, sync indirection tables, then decode up to `decode_span`
tokens inside one jitted lax.scan with the active mask freezing parked
slots (DESIGN.md §3.6). Decode is the paper's doorbell batching: stop
conditions (EOS, max_new_tokens, cache_len, span budget) evaluate on
device, and the host syncs emitted tokens/positions once per span
instead of once per token — O(tokens/span) round-trips on the hottest
path. Prompt ingestion is the paper's packet-granular streaming: with
`prefill_chunk > 0` a long prompt flows through the frame in
page-aligned chunks interleaved with decode spans, so it never
head-of-line-blocks running sequences. The engine is exact (not a
simulation): parked slots' caches are bit-frozen, evicted KV really
moves to host numpy arrays and back, prompts sharing a page-aligned
prefix share physical pages through the refcounted block cache
(DESIGN.md §3.5), and span decode is token-for-token identical to
per-step decode in both KV layouts.
"""
from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import lm
from repro.serve.api import (EngineConfig, ParkingTransport, Request,
                             Sampler, Scheduler, StateBackend,
                             make_sampler, make_scheduler,
                             make_state_backend, request_from_state,
                             request_to_state)
# Re-exports: the public request/config types live in serve/api.py and the
# slot helpers in serve/state_backends.py; older call sites import them here.
from repro.serve.state_backends import (_slot_extract,  # noqa: F401
                                        _slot_insert, _slot_restore,
                                        _slot_set)
from repro.kernels.paged_attention import live_table_width
from repro.serve.parking import HostParkingTransport
from repro.serve.prefix_cache import PrefixCache
from repro.sharding.policy import NULL_POLICY, Policy


def _wrap_i32(v: int) -> np.int32:
    """Wrap an arbitrary Python int into int32 (two's complement)."""
    return np.uint32(int(v) & 0xFFFFFFFF).astype(np.int32)


SNAPSHOT_VERSION = 1

# Cross-engine compile cache. Crash-recovery rebuilds (ft/crash.py) and
# multi-engine benchmarks construct many engines over the same config;
# jax.jit caches on function identity, so per-instance lambdas would
# recompile every rebuild. Keys use id(cfg)/id(policy) — safe because
# each cached closure holds those objects alive, so their ids cannot be
# recycled while the entry exists. Samplers are keyed by TYPE: the
# Sampler protocol requires `sample` to be a pure traceable function of
# its arguments (per-request state arrives via `params`/`rng`), so two
# instances of one class compile identically.
_COMPILE_CACHE: dict = {}


def _cached_jit(key, make, name, donate_argnums=()):
    """``jax.jit`` of ``make()``, cached under ``key``. The function is
    renamed ``name`` first, so the profiler's trace and the compiler name
    the program ``jit_<name>`` instead of ``jit__lambda``."""
    fn = _COMPILE_CACHE.get(key)
    if fn is None:
        f = make()
        f.__name__ = f.__qualname__ = name
        fn = _COMPILE_CACHE[key] = jax.jit(f, donate_argnums=donate_argnums)
    return fn


def _span(name: str):
    """Method decorator: run the call inside the profiler span ``name``
    (a ``TraceAnnotation``: one inactive check when no trace is open)."""
    return functools.partial(jax.profiler.annotate_function, name=name)


def span_program(cfg: ModelConfig, policy: Policy, ecfg: EngineConfig,
                 sampler: Sampler, span: int, want_lp: bool):
    """The jitted fused-decode scan for one executed span length, with
    ``sampler`` closed over as the per-step selection handler (DESIGN.md
    §3.7). Called as ``fn(params, tokens, state, active, budgets,
    sampler_params, rng)``. Executed lengths are pow2-bucketed (capped at
    decode_span), so shrunken spans cost at most log2(decode_span) extra
    compiles (×2 when logprobs are on), shared across engines through the
    module compile cache.

    The decode state (argument 2) is donated: the span returns the whole
    updated state, so its K/V pools are updated in place instead of
    holding an old and a new copy of every pool at once. Callers must
    drop the state they passed in and keep the returned one."""
    eos, L = ecfg.eos_token, ecfg.cache_len
    sample = sampler.sample
    return _cached_jit(
        ("span", id(cfg), id(policy), eos, L, type(sampler), span, want_lp),
        lambda: lambda p, t, s, a, b, sp, rng: lm.decode_span(
            p, t, s, cfg, policy, a, b, span=span, eos_token=eos,
            cache_len=L, sample_fn=sample, sampler_params=sp,
            rng=rng, want_logprobs=want_lp),
        "serve_decode_span", donate_argnums=(2,))


class ServingEngine:
    # Snapshot manifest (DESIGN.md §9), enforced by jzlint rule JZ006:
    # EVERY attribute `__init__` assigns must be declared here with its
    # recovery treatment — "captured" (serialized by snapshot()),
    # "rebuilt" (reconstructed from config at fresh construction), or
    # "config" (immutable construction input). Adding engine state
    # without deciding its crash-recovery story fails `make lint`.
    _SNAPSHOT_FIELDS = {
        "cfg": "config", "params": "config", "ecfg": "config",
        "policy": "config", "sampler": "config",
        "clock": "captured", "kv": "captured", "state": "captured",
        "sched": "captured", "transport": "captured",
        "active": "captured", "running": "captured",
        "prefilling": "captured", "prefill_pos": "captured",
        "_prefill_rr": "captured", "slot_req": "captured",
        "prefix": "captured", "_stalled": "captured",
        "completed": "captured", "stats": "captured",
        "_needs_rng": "rebuilt", "_chunked_ok": "rebuilt",
        "_prefill": "rebuilt", "_prefill_chunk": "rebuilt",
        "_select_fn": "rebuilt",
    }

    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig,
                 policy: Policy = NULL_POLICY,
                 scheduler: Optional[Scheduler] = None,
                 kv_backend: Optional[StateBackend] = None,
                 transport: Optional[ParkingTransport] = None,
                 sampler: Optional[Sampler] = None):
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        self.policy = policy
        B, L = ecfg.slots, ecfg.cache_len
        if ecfg.prefill_chunk and ecfg.prefill_chunk % ecfg.page_size:
            raise ValueError(
                f"prefill_chunk {ecfg.prefill_chunk} must be a page_size "
                f"({ecfg.page_size}) multiple so chunk boundaries stay "
                f"page-aligned")
        if ecfg.decode_span < 1:
            raise ValueError(
                f"decode_span must be >= 1, got {ecfg.decode_span}")
        # the injected time source (EngineConfig.clock): arrival stamps,
        # completion stamps and the parking bus all read it, so a virtual
        # clock makes ordering and eviction tie-breaks fully deterministic
        self.clock = ecfg.clock
        self.kv = kv_backend or make_state_backend(ecfg.kv_layout, cfg, ecfg)
        self.state = self.kv.init_state()
        self.sched = scheduler or make_scheduler(
            ecfg.scheduler, n_classes=ecfg.qos_classes,
            capacity=ecfg.queue_capacity)
        self.transport = transport or HostParkingTransport(
            ecfg.bus, clock=self.clock)
        self.sampler = sampler or make_sampler(ecfg.sampler)
        self._needs_rng = bool(getattr(self.sampler, "needs_rng", False))
        self.active = np.zeros(B, bool)          # slot has a sequence
        self.running = np.zeros(B, bool)         # decoding (not parked,
        #                                          not mid-prefill)
        self.prefilling = np.zeros(B, bool)      # streaming its prompt in
        self.prefill_pos = np.zeros(B, np.int64)  # prompt tokens ingested
        self._prefill_rr = 0                     # chunk-budget round-robin
        self.slot_req: List[Optional[Request]] = [None] * B
        # capability routing (DESIGN.md §10): the backend — not a config
        # sniff — says whether its slot state extends a chunk at a time
        # and whether per-token blocks can back the prefix cache; other
        # layouts fall back to monolithic prefill with no prefix reuse
        self._chunked_ok = bool(
            getattr(self.kv, "supports_chunked_prefill", False))
        self.prefix = PrefixCache(
            ecfg.prefix_cache_entries
            if (self._chunked_ok
                and getattr(self.kv, "supports_prefix_share", False)) else 0,
            block=ecfg.page_size,
            retain=self.kv.cache_retain, release=self.kv.cache_release)
        self._stalled: set = set()               # req_ids frozen in place
        self.completed: List[Request] = []
        self.stats = {"decode_steps": 0, "decode_tokens": 0,
                      "decode_spans": 0, "host_syncs": 0, "span_shrinks": 0,
                      "prefills": 0,
                      "prefill_tokens": 0, "prefill_chunks": 0,
                      "parked": 0, "unparked": 0,
                      "prefix_hits": 0, "prefix_tokens_reused": 0,
                      "page_allocs": 0, "pages_peak": 0,
                      "preempt_restarts": 0,
                      "attn_pages_table": 0, "attn_pages_live": 0}

        # compiled entry points come from the module-level _COMPILE_CACHE
        # so engine rebuilds (crash recovery, benchmark sweeps) over the
        # same config never recompile; closures bind locals, not self, so
        # a cache entry cannot keep a dead engine's device state alive
        sample = self.sampler.sample
        self._prefill = _cached_jit(
            ("prefill", id(cfg), id(policy), L),
            lambda: lambda p, t: lm.prefill(p, t, cfg, policy, cache_len=L),
            "serve_prefill")
        self._prefill_chunk = _cached_jit(
            ("prefill_chunk", id(cfg), id(policy)),
            lambda: lambda p, t, c, s, nv: lm.prefill_chunk(
                p, t, c, s, nv, cfg, policy),
            "serve_prefill_chunk")
        self._select_fn = _cached_jit(
            ("select", type(self.sampler)),
            lambda: lambda lg, sp, rng: lm.select_token(lg, sample, sp, rng),
            "serve_select")

    @property
    def pool(self):
        """The StateBackend's PagePool (MTT accounting), for introspection."""
        return self.kv.pool

    def _streaming(self) -> bool:
        return bool(self.ecfg.prefill_chunk) and self._chunked_ok

    @_span("serve.host_sync")
    def _host_sync(self, tree):
        """THE accounted blocking device->host transfer. Every read the
        serving loop makes off the device — one per decode span, one per
        prefill first token — funnels through here so
        ``stats["host_syncs"]`` is the true round-trip count."""
        self.stats["host_syncs"] += 1
        return jax.device_get(tree)

    # -- sampler inputs (DESIGN.md §3.7) ----------------------------------
    def _sampler_params(self, reqs: List[Optional[Request]]):
        """Stack per-request sampling parameters into per-slot arrays
        (a tuple of [len(reqs)] arrays; () for parameterless samplers)."""
        per = [self.sampler.slot_params(r) for r in reqs]
        if not per[0]:
            return ()
        return tuple(jnp.asarray(np.asarray([p[j] for p in per]))
                     for j in range(len(per[0])))

    def _sampler_rng(self, reqs: List[Optional[Request]]):
        """(seeds, req_ids, counters) for `derive_keys` — or None for
        RNG-free samplers. The counter is the request's emitted-token
        count from *host bookkeeping*, so a restored (unparked or
        preempt-restarted) request resumes its key stream exactly where
        the undisturbed run would be: PRNG state is re-derived the same
        way KV state is restored, never re-seeded from scratch."""
        if not self._needs_rng:
            return None
        n = len(reqs)
        seeds = np.zeros(n, np.int32)
        rids = np.zeros(n, np.int32)
        ctrs = np.zeros(n, np.int32)
        for i, r in enumerate(reqs):
            if r is not None:
                # seeds/req_ids fold into the key modulo 2^32: wrap here
                # instead of letting numpy raise on out-of-int32 values
                # (hash-derived seeds routinely exceed 2^31)
                seeds[i] = _wrap_i32(r.sampling.seed)
                rids[i] = _wrap_i32(r.req_id)
                ctrs[i] = len(r.tokens_out)
        return (jnp.asarray(seeds), jnp.asarray(rids), jnp.asarray(ctrs))

    # ------------------------------------------------------------------
    def try_submit(self, req: Request) -> bool:
        """Validate + enqueue; False means scheduler-queue backpressure
        (the caller keeps the request — nothing was consumed). Impossible
        requests still raise: no queue state can ever make them fit."""
        if len(req.prompt) + 1 > self.ecfg.cache_len:
            # the prompt plus one generated token must fit the per-slot
            # table/slab; longer prompts would scatter past max_pages
            raise ValueError(
                f"prompt length {len(req.prompt)} does not fit "
                f"cache_len {self.ecfg.cache_len} (need len+1 <= cache_len)")
        err = self.kv.admission_error(req)
        if err is not None:
            # layout-specific impossibility (e.g. more pages than the
            # whole pool holds); constant-size layouts never refuse
            raise ValueError(err)
        req.arrived_at = self.clock()
        return self.sched.submit(req)

    def submit(self, req: Request):
        if not self.try_submit(req):
            raise RuntimeError(
                f"scheduler queue full (capacity "
                f"{self.ecfg.queue_capacity}); request {req.req_id} rejected")

    # -- slot management -------------------------------------------------
    def _free_slot(self) -> Optional[int]:
        idle = np.nonzero(~self.active)[0]
        return int(idle[0]) if len(idle) else None

    def _release_slot(self, slot: int):
        self.active[slot] = False
        self.running[slot] = False
        self.prefilling[slot] = False
        self.prefill_pos[slot] = 0
        self.slot_req[slot] = None

    def _complete(self, slot: int, req: Request):
        req.finished_at = self.clock()
        self.completed.append(req)
        self.kv.release(req.req_id)
        self._release_slot(slot)
        if req.on_done is not None:
            req.on_done(req)

    def _emit(self, req: Request, toks: List[int],
              lps: Optional[List[float]] = None):
        """THE token-emission funnel: every token a request ever receives
        — prefill first tokens (monolithic or chunked) and decode-span
        batches — is appended here, at a point the host already holds the
        values from its one accounted sync. Streaming therefore costs
        zero extra host syncs: `on_tokens` observes exactly what
        `tokens_out` received, in the same order."""
        if toks and req.first_token_at is None:
            req.first_token_at = self.clock()
        req.tokens_out.extend(toks)
        if lps is not None and req.sampling.logprobs:
            req.logprobs_out.extend(lps)
        if req.on_tokens is not None and toks:
            req.on_tokens(req, toks)

    @_span("serve.admit")
    def _admit(self) -> int:
        admitted = 0
        while True:
            slot = self._free_slot()
            if slot is None:
                break
            req: Optional[Request] = self.sched.next()
            if req is None:
                break
            prompt = np.asarray(req.prompt, np.int32)
            matched, payloads = self.prefix.match(prompt)
            streaming = self._streaming()
            if self.kv.needs_growth:
                # charge only what this step will write: the shared
                # prefix joins by reference, the first chunk (or the
                # whole tail when not streaming) is new pages
                first = len(prompt) - matched
                if streaming:
                    first = min(self.ecfg.prefill_chunk, first)
                n_tok = matched + first
                if matched + first == len(prompt):
                    n_tok += 1                   # first decode token
            else:
                n_tok = self.kv.footprint(req)
            if matched:
                self.state = self.kv.share_prefix(
                    self.state, slot, req.req_id, payloads, matched)
            if not self._append_or_free(req.req_id, n_tok,
                                        self.sched.class_of(req)):
                self.kv.release(req.req_id)      # drop shared-prefix refs
                self.prefix.unrecord(matched)    # retry will re-match
                self._requeue(req)               # requeue; others proceed
                break
            if req.admitted_at is None:
                req.admitted_at = self.clock()
            self.active[slot] = True
            self.running[slot] = False
            self.prefilling[slot] = True
            self.prefill_pos[slot] = matched
            self.slot_req[slot] = req
            if matched:
                self.stats["prefix_hits"] += 1
                self.stats["prefix_tokens_reused"] += matched
            self.stats["prefills"] += 1
            if not streaming:
                if matched:
                    # cached prefix installed: compute only the tail,
                    # in one chunk
                    self._process_chunk(slot, len(prompt) - matched)
                else:
                    self._prefill_full(slot, req)
            admitted += 1
        return admitted

    @_span("serve.prefill")
    def _prefill_full(self, slot: int, req: Request):
        """Monolithic prefill (chunking disabled / unsupported config)."""
        prompt = np.asarray(req.prompt, np.int32)
        logits, st = self._prefill(self.params, jnp.asarray(prompt[None]))
        self.state = self.kv.prefill_into_slot(
            self.state, slot, req.req_id, st["caches"], len(prompt))
        self.stats["prefill_tokens"] += len(prompt)
        self._finish_prefill(slot, req, *self._first_token(req, logits))

    def _first_token(self, req: Request, logits):
        """Select a finished prefill's first token ON DEVICE through the
        sampler (index 0 of the request's key stream) and sync exactly
        one accounted (token, logprob) scalar pair — not an eager argmax
        dispatch chain with an unaccounted blocking read."""
        sp = self._sampler_params([req])
        tok, lp = self._host_sync(
            self._select_fn(logits, sp, self._sampler_rng([req])))
        return int(tok[0]), float(lp[0])

    # -- chunked prefill (DESIGN.md §3.4) ---------------------------------
    @_span("serve.prefill")
    def _prefill_step(self):
        """Stream page-aligned chunks of the PREFILLING slots' prompts,
        bounded by the per-step token budget — long prompts interleave
        with decode instead of head-of-line-blocking it. The budget is
        spent in whole chunks (chunk width is the compiled shape), with a
        floor of one chunk per step so prefill always progresses."""
        if not self._streaming():
            return
        chunk = self.ecfg.prefill_chunk
        budget = self.ecfg.prefill_budget or chunk
        quota = max(1, budget // chunk)          # whole chunks this step
        n = self.ecfg.slots
        for k in range(n):                       # rotate so concurrent
            i = (self._prefill_rr + k) % n       # prefills share the
            if quota <= 0:                       # budget round-robin
                break
            if not (self.active[i] and self.prefilling[i]):
                continue
            if self._process_chunk(i, chunk):
                quota -= 1
                self._prefill_rr = (i + 1) % n

    def _process_chunk(self, slot: int, width: int) -> int:
        """Ingest up to `width` prompt tokens for one PREFILLING slot.
        Returns the number of tokens processed (0 if out of pages)."""
        req = self.slot_req[slot]
        pos = int(self.prefill_pos[slot])
        total = len(req.prompt)
        n_valid = min(width, total - pos)
        last = pos + n_valid == total
        need = pos + n_valid + (1 if last else 0)
        if self.kv.needs_growth and not self._append_or_free(
                req.req_id, need, self.sched.class_of(req)):
            # no pages for this chunk: wait in place (decodes continue).
            # If nothing is decoding and someone else is waiting on pages
            # too (a lower prefilling slot or a stalled decode), back off
            # (preempt-restart) so the other side can make progress
            # instead of both waiting on each other's pages forever.
            if (not self.running.any()
                    and (self._stalled
                         or any(self.prefilling[j] and self.active[j]
                                for j in range(slot)))):
                self._preempt_restart(slot)
            return 0
        chunk = np.zeros(width, np.int32)
        chunk[:n_valid] = np.asarray(req.prompt[pos:pos + n_valid], np.int32)
        with jax.profiler.TraceAnnotation("serve.kv.stage"):
            caches = self.kv.slot_caches(self.state, slot, req.req_id)
        logits, caches = self._prefill_chunk(
            self.params, jnp.asarray(chunk[None]), caches,
            jnp.int32(pos), jnp.int32(n_valid))
        with jax.profiler.TraceAnnotation("serve.kv.store"):
            self.state = self.kv.store_chunk(
                self.state, slot, req.req_id, caches, pos, n_valid)
        self.prefill_pos[slot] = pos + n_valid
        self.stats["prefill_chunks"] += 1
        self.stats["prefill_tokens"] += n_valid
        if last:
            self._finish_prefill(slot, req, *self._first_token(req, logits))
        return n_valid

    def _finish_prefill(self, slot: int, req: Request, first_tok: int,
                        first_lp: float = 0.0):
        total = len(req.prompt)
        self.state["lengths"] = self.state["lengths"].at[slot].set(total)
        self.state["positions"] = self.state["positions"].at[slot].set(total)
        self.prefilling[slot] = False
        self.prefill_pos[slot] = total
        self._donate_prefix(slot, req)
        self._emit(req, [first_tok], [first_lp])
        # the prefill token can already satisfy the contract: never run
        # (or append) a decode token past max_new_tokens or EOS
        if (len(req.tokens_out) >= req.max_new_tokens
                or first_tok == self.ecfg.eos_token):
            self._complete(slot, req)
        else:
            self.running[slot] = True

    def _donate_prefix(self, slot: int, req: Request):
        """Offer the prompt's full page-aligned blocks to the block cache
        (paged: pages pinned by refcount; dense: per-block KV slices)."""
        n_blocks = len(req.prompt) // self.ecfg.page_size
        if n_blocks <= 0 or self.prefix.capacity <= 0:
            return
        prompt = np.asarray(req.prompt, np.int32)
        self.prefix.insert(
            prompt, n_blocks,
            lambda b: self.kv.block_payload(self.state, slot, req.req_id, b))

    def _claim_reclaim(self, claim) -> bool:
        """Run a page-claiming thunk, dropping LRU cached blocks under
        page pressure — cache-pinned pages are the cheapest to free (no
        live slot recomputes, a future request merely re-prefills its
        prefix)."""
        if claim():
            return True
        if self.kv.needs_growth:
            # evict until the claim fits or the cache is empty: an
            # eviction that frees nothing (blocks still shared by live
            # sequences) may still be followed by freeable chains later
            # in LRU order, and a flushed cache is cheaper than parking
            # a live decode or bouncing an admission
            while self.prefix.evict_one():
                if claim():
                    return True
        return False

    def _append_reclaim(self, req_id: int, n_tok: int) -> bool:
        return self._claim_reclaim(lambda: self.kv.append(req_id, n_tok))

    def _reserve_reclaim(self, req_id: int, n_tok: int) -> bool:
        return self._claim_reclaim(
            lambda: self.kv.reserve_span(req_id, n_tok))

    def _append_or_free(self, req_id: int, n_tok: int,
                        for_class: Optional[int]) -> bool:
        """`_append_reclaim` plus the second pressure valve: VoQ eviction
        of a same-or-lower-priority victim."""
        if self._append_reclaim(req_id, n_tok):
            return True
        if self._evict_someone(exclude=req_id, for_class=for_class):
            # reclaim again: cached blocks pinning the victim's pages
            # free for real only now that its table refs are gone
            return self._append_reclaim(req_id, n_tok)
        return False

    def _requeue(self, req: Request):
        """Return bounced work to its class queue; a lost request is an
        invariant break (its pages/slot are already released), so a full
        pool is fatal rather than silent."""
        if not self.sched.requeue(req):
            raise RuntimeError(
                f"scheduler queue full on requeue; request {req.req_id} "
                f"would be lost")

    # -- VoQ parking / eviction -------------------------------------------
    def _evict_someone(self, exclude: int,
                       for_class: Optional[int] = None) -> bool:
        """Park a running sequence: move its KV to the host tier
        (non-blocking for everyone else). The victim is drawn from the
        lowest QoS class present (most recently admitted on ties), and
        when `for_class` is given, never from a class above it — the
        Resource tier must not invert the Queue tier's priorities."""
        cands = [i for i in range(self.ecfg.slots)
                 if self.active[i] and self.running[i]
                 and self.slot_req[i] is not None
                 and self.slot_req[i].req_id != exclude]
        if for_class is not None:
            cands = [i for i in cands
                     if self.sched.class_of(self.slot_req[i]) >= for_class]
        if not cands:
            return False
        worst = max(self.sched.class_of(self.slot_req[i]) for i in cands)
        victim = max(
            (i for i in cands
             if self.sched.class_of(self.slot_req[i]) == worst),
            key=lambda i: self.slot_req[i].arrived_at)
        return self._park_slot(victim)

    def _park_slot(self, slot: int) -> bool:
        if not self.ecfg.host_offload:
            return False
        req = self.slot_req[slot]
        if req is None or not self.running[slot]:
            return False
        caches, meta = self.kv.park(self.state, slot, req.req_id)
        self.transport.begin(req.req_id, caches, meta)
        self.running[slot] = False
        self.stats["parked"] += 1
        return True

    def _try_unpark(self):
        for req_id in self.transport.ready():
            caches, meta = self.transport.peek(req_id)
            req = self.slot_req[meta.slot]
            if (req is None or req.req_id != req_id
                    or self.running[meta.slot]):
                continue
            ok, self.state = self.kv.unpark(
                self.state, meta.slot, req, caches, meta)
            while (not ok and self.kv.needs_growth
                   and self.prefix.evict_one()):
                ok, self.state = self.kv.unpark(
                    self.state, meta.slot, req, caches, meta)
            if not ok:
                continue                     # no pages yet; retry later
            self.running[meta.slot] = True
            self.transport.complete(req_id)
            self.stats["unparked"] += 1

    # -- capacity growth ---------------------------------------------------
    @_span("serve.grow")
    def _grow(self):
        """Alloc-on-append: claim a fresh page for every running slot whose
        next token crosses a page boundary. When the pool is dry and nobody
        is evictable the slot itself stops (per-connection blocking — the
        rest of the batch keeps decoding): park to the host tier if one
        exists, else *stall in place* (pages kept, slot frozen via the
        active mask) until a release frees pages; if stalling would freeze
        the whole batch (deadlock), preempt-restart the request instead
        (release pages, requeue for fresh prefill — recompute preemption).
        """
        changed = False
        for i in range(self.ecfg.slots):
            req = self.slot_req[i]
            if req is None or not self.active[i] or self.prefilling[i]:
                continue                     # chunks manage their own pages
            if not self.running[i]:
                if req.req_id in self._stalled:
                    before = self.kv.held(req.req_id)
                    if self._append_reclaim(req.req_id,
                                            self._slot_pos(req) + 1):
                        self._stalled.discard(req.req_id)
                        self.running[i] = True
                        self.stats["page_allocs"] += (
                            self.kv.held(req.req_id) - before)
                        changed = True
                continue
            pos = self._slot_pos(req)        # host bookkeeping, no device read
            before = self.kv.held(req.req_id)
            if self._append_reclaim(req.req_id, pos + 1):
                grown = self.kv.held(req.req_id) - before
                if grown:
                    self.stats["page_allocs"] += grown
                    changed = True
                continue
            if (self._evict_someone(exclude=req.req_id,
                                    for_class=self.sched.class_of(req))
                    and self._append_reclaim(req.req_id, pos + 1)):
                self.stats["page_allocs"] += (
                    self.kv.held(req.req_id) - before)
                changed = True
                continue
            changed = True
            if self._park_slot(i):
                continue
            others_running = any(
                self.running[j] for j in range(self.ecfg.slots) if j != i)
            if others_running:
                self._stalled.add(req.req_id)      # freeze; resume later
                self.running[i] = False
            else:
                self._preempt_restart(i)           # avoid whole-batch stall
        if changed:
            self.kv.mark_dirty()

    def _preempt_restart(self, slot: int):
        """Release a slot's pages and requeue its request from scratch
        (recompute preemption — the no-host-tier escape hatch). The
        request keeps its QoS class: requeue routes through the
        scheduler's class mapping, not queue 0."""
        req = self.slot_req[slot]
        self.kv.release(req.req_id)
        self._stalled.discard(req.req_id)
        req.tokens_out.clear()
        req.logprobs_out.clear()
        self._release_slot(slot)
        self._requeue(req)
        self.stats["preempt_restarts"] += 1

    # -- decode spans (DESIGN.md §3.6) -------------------------------------
    @staticmethod
    def _slot_pos(req: Request) -> int:
        """A decoding slot's device position, from host bookkeeping alone
        (no device read): prefill leaves `positions = len(prompt)` with
        one emitted token, and every span emission advances the device
        counter by exactly one (frozen slots emit nothing)."""
        return len(req.prompt) + len(req.tokens_out) - 1

    def _reserve_headroom(self, req_id: int, pos: int, want: int) -> int:
        """Claim pages covering up to `want` upcoming decode tokens for
        one slot; returns the granted token count (>= 1 — `_grow` already
        secured the next token or the slot would not be running). Uses
        the prefix-cache reclaim valve but never the VoQ eviction valve:
        parking a live sequence to lengthen another's span would trade
        one slot's throughput for another's, a wash."""
        if self._reserve_reclaim(req_id, pos + want):
            return want
        # the reclaim loop drained the cache; what is left is exactly the
        # pages already held plus the free list
        ps = self.ecfg.page_size
        avail = (self.kv.held(req_id) + self.pool.n_free) * ps - pos
        got = int(max(1, min(want, avail)))
        if got > 1:
            self.kv.reserve_span(req_id, pos + got)   # fits by construction
        self.stats["span_shrinks"] += 1
        return got

    @_span("serve.reserve")
    def _reserve_decode_span(self, act: np.ndarray):
        """Per-slot span budgets + the executed span length.

        budgets[i] folds the request's remaining max_new_tokens, the
        cache_len distance, and (paged) the page headroom this slot
        could actually reserve into one on-device counter; a slot whose
        budget runs out mid-span freezes via the active mask and retries
        next span. The executed span is the pow2 bucket of the largest
        budget so shrunken spans reuse at most log2(decode_span)
        compiled scans."""
        span = self.ecfg.decode_span
        L = self.ecfg.cache_len
        budgets = np.zeros(self.ecfg.slots, np.int32)
        grew = False
        for i in np.nonzero(act)[0]:
            req = self.slot_req[int(i)]
            pos = self._slot_pos(req)
            want = max(1, min(span, req.max_new_tokens - len(req.tokens_out),
                              L - pos))
            if want > 1 and self.kv.needs_growth:
                before = self.kv.held(req.req_id)
                want = self._reserve_headroom(req.req_id, pos, want)
                grown = self.kv.held(req.req_id) - before
                if grown:
                    # per-slot held delta, NOT a pool n_used diff: an
                    # eviction that frees one page while the claim takes
                    # another nets to zero pool change but still rewrote
                    # this slot's table row
                    self.stats["page_allocs"] += grown
                    grew = True
            budgets[i] = want
        if grew:
            self.kv.mark_dirty()             # headroom pages joined tables
        # one bucketing rule for both compile caps: span lengths and the
        # paged table width share live_table_width's pow2-with-cap shape
        span_exec = live_table_width(int(budgets.max()), span)
        return budgets, span_exec

    # -- main loop ---------------------------------------------------------
    def step(self):
        try:
            self._step()
        finally:
            # the stat is a MIRROR of the pool's own high-water mark:
            # allocation paths internal to backends (unpark re-allocs,
            # third-party subsystems driving the pool directly) register
            # in PagePool.alloc, where every page claim funnels
            self.stats["pages_peak"] = self.pool.peak

    @_span("serve.step")
    def _step(self):
        self._admit()
        self._try_unpark()
        self._prefill_step()
        if self.kv.needs_growth:
            self._grow()
        act = self.active & self.running
        if act.any():
            # reserve before sync: headroom pages must be in the exported
            # tables the scan chases
            budgets, span_exec = self._reserve_decode_span(act)
        with jax.profiler.TraceAnnotation("serve.kv.sync"):
            self.state = self.kv.sync(
                self.state,
                [r.req_id if r is not None else None for r in self.slot_req])
        if not act.any():
            return                           # only prefilling/parked slots
        with jax.profiler.TraceAnnotation("serve.dispatch"):
            tokens = np.zeros(self.ecfg.slots, np.int32)
            for i, req in enumerate(self.slot_req):
                if req is not None and req.tokens_out:
                    tokens[i] = req.tokens_out[-1]
            want_lp = any(r is not None and r.sampling.logprobs
                          for r in self.slot_req)
            table = self.state.get("page_table")
            if table is not None:
                # what the paged kernel's table walk covers, and the
                # pages the decoding slots hold: the kernel fetches no
                # others
                self.stats["attn_pages_table"] += span_exec * table.size
                self.stats["attn_pages_live"] += span_exec * sum(
                    self.kv.held(self.slot_req[i].req_id)
                    for i in np.nonzero(act)[0])
            out = span_program(self.cfg, self.policy, self.ecfg,
                               self.sampler, span_exec, want_lp)(
                self.params, jnp.asarray(tokens), self.state,
                jnp.asarray(act), jnp.asarray(budgets),
                self._sampler_params(self.slot_req),
                self._sampler_rng(self.slot_req))
        if want_lp:
            toks, emit, lps, self.state = out
        else:
            (toks, emit, self.state), lps = out, None
        self.stats["decode_steps"] += span_exec
        self.stats["decode_spans"] += 1
        # ONE blocking device->host sync per span — the stacked emissions
        # and their per-step mask (and, when requested, logprobs);
        # positions are rederived from host bookkeeping (_slot_pos),
        # not transferred
        got = self._host_sync((toks, emit) if lps is None
                              else (toks, emit, lps))
        toks, emit, lps = got if lps is not None else (*got, None)
        with jax.profiler.TraceAnnotation("serve.emit"):
            for i in range(self.ecfg.slots):
                req = self.slot_req[i]
                if req is None or not act[i]:
                    continue
                new = [int(t) for t in toks[emit[:, i], i]]  # slot i's
                #                                       emissions, in order
                self._emit(req, new,
                           None if lps is None
                           else [float(x) for x in lps[emit[:, i], i]])
                self.stats["decode_tokens"] += len(new)
                done = (len(req.tokens_out) >= req.max_new_tokens
                        or (len(new)
                            and int(new[-1]) == self.ecfg.eos_token)
                        or self._slot_pos(req) >= self.ecfg.cache_len)
                if done:
                    self._complete(i, req)

    def run_until_done(self, max_steps: int = 10_000):
        """Drive the engine until every submitted request completes.

        Exhausting `max_steps` with work still queued/active/parked
        raises instead of returning silently — a caller that drops
        stranded requests on the floor has no way to notice otherwise.
        `stats["incomplete"]` records the on-slot (active or parked)
        req_ids; still-queued requests stay in the scheduler (the
        protocol has no enumeration) and are reported as a count — the
        engine remains resumable with another run_until_done call."""
        for _ in range(max_steps):
            if (not self.active.any() and self.sched.pending == 0
                    and self.transport.in_flight == 0):
                self.stats["pages_peak"] = self.pool.peak
                return self.completed
            self.step()
        if (not self.active.any() and self.sched.pending == 0
                and self.transport.in_flight == 0):
            return self.completed
        stranded = sorted({r.req_id for r in self.slot_req if r is not None})
        self.stats["incomplete"] = stranded
        raise RuntimeError(
            f"run_until_done exhausted max_steps={max_steps} with "
            f"{len(stranded)} request(s) still on slots "
            f"(req_ids {stranded}), {self.sched.pending} more queued in "
            f"the scheduler and {self.transport.in_flight} parked in "
            f"transport; call run_until_done again to resume")

    # -- crash recovery (DESIGN.md §9) -------------------------------------
    def _snapshot_config(self) -> dict:
        """The geometry a snapshot is only valid against — restore
        refuses a mismatch instead of silently scattering into wrongly
        shaped state."""
        e = self.ecfg
        return {"slots": int(e.slots), "cache_len": int(e.cache_len),
                "page_size": int(e.page_size), "n_pages": int(e.n_pages),
                "kv_layout": str(e.kv_layout), "scheduler": str(e.scheduler),
                "sampler": str(e.sampler), "decode_span": int(e.decode_span),
                "prefill_chunk": int(e.prefill_chunk),
                "eos_token": int(e.eos_token),
                "qos_classes": int(e.qos_classes)}

    def snapshot(self) -> dict:
        """Capture the COMPLETE engine state as host arrays and JSON-able
        scalars — every field `_SNAPSHOT_FIELDS` marks "captured":
        slot bookkeeping, scheduler queues, device KV + MTT + pool
        refcounts, prefix-cache chains, parked host-tier payloads, stats,
        and the PR 5 determinism anchors (per-request seeds + emitted
        counts travel inside the serialized Requests). Reads nothing
        through `_host_sync`: snapshotting is not a decode-path read, so
        it must not perturb the `host_syncs == prefills + decode_spans`
        invariant it is later asserted against."""
        queues, aux = self.sched.export()
        return {
            "version": SNAPSHOT_VERSION,
            "config": self._snapshot_config(),
            "clock_t": float(self.clock()),
            "active": [bool(x) for x in self.active],
            "running": [bool(x) for x in self.running],
            "prefilling": [bool(x) for x in self.prefilling],
            "prefill_pos": [int(x) for x in self.prefill_pos],
            "prefill_rr": int(self._prefill_rr),
            "slot_req": [None if r is None else request_to_state(r)
                         for r in self.slot_req],
            "stalled": sorted(int(x) for x in self._stalled),
            "sched": {"queues": [[request_to_state(r) for r in q]
                                 for q in queues],
                      "aux": dict(aux)},
            "completed": [request_to_state(r) for r in self.completed],
            "stats": {k: (list(v) if isinstance(v, list) else int(v))
                      for k, v in self.stats.items()},
            "kv": self.kv.export_state(self.state),
            "transport": self.transport.export_state(),
            "prefix": self.prefix.export_state(self.kv.snapshot_payload),
        }

    def restore(self, snap: dict) -> None:
        """Load a `snapshot()` onto this (freshly constructed) engine.

        After restore the engine is step-for-step identical to the
        snapshotted one: same slot/queue/pool/prefix state, same device
        KV bytes, same PRNG anchors — so the continued token streams are
        byte-identical to a run that never crashed."""
        if snap.get("version") != SNAPSHOT_VERSION:
            raise ValueError(
                f"snapshot version {snap.get('version')!r} != engine "
                f"version {SNAPSHOT_VERSION}")
        want = self._snapshot_config()
        have = {k: snap["config"].get(k) for k in want}
        if have != want:
            diff = {k: (have[k], want[k]) for k in want
                    if have[k] != want[k]}
            raise ValueError(
                f"snapshot config mismatch (snapshot vs engine): {diff}")
        self.state = self.kv.import_state(snap["kv"])
        self.transport.import_state(snap["transport"])
        self.prefix.import_state(snap["prefix"], self.kv.restore_payload)
        self.sched.import_(
            [[request_from_state(d) for d in q]
             for q in snap["sched"]["queues"]],
            dict(snap["sched"]["aux"]))
        self.active = np.asarray(snap["active"], bool)
        self.running = np.asarray(snap["running"], bool)
        self.prefilling = np.asarray(snap["prefilling"], bool)
        self.prefill_pos = np.asarray(snap["prefill_pos"], np.int64)
        self._prefill_rr = int(snap["prefill_rr"])
        self.slot_req = [None if d is None else request_from_state(d)
                         for d in snap["slot_req"]]
        self._stalled = set(int(x) for x in snap["stalled"])
        self.completed = [request_from_state(d) for d in snap["completed"]]
        self.stats = {k: (list(v) if isinstance(v, list) else int(v))
                      for k, v in snap["stats"].items()}
        # never rewind the injected clock: in-process recovery keeps time
        # monotonic, while a fresh process fast-forwards to the snapshot
        # time so parked-payload bus deadlines stay reachable
        if hasattr(self.clock, "t"):
            self.clock.t = max(float(self.clock()), float(snap["clock_t"]))

    def live_requests(self) -> dict:
        """req_id -> Request for every request the engine still owns
        (on a slot or queued) — what a frontend reattaches its streaming
        handles to after a restore."""
        out = {r.req_id: r for r in self.slot_req if r is not None}
        queues, _ = self.sched.export()
        for q in queues:
            for r in q:
                out[r.req_id] = r
        return out

    def replay_from_zero(self, slot: int) -> None:
        """The recompute (SR-analog) recovery policy for one slot: drop
        its restored KV and any parked host copy, requeue the request for
        a from-scratch prefill. Streams stay byte-identical because the
        frontend handle dedupes by emitted index and the PR 5 key
        derivation replays from `len(tokens_out)`."""
        req = self.slot_req[slot]
        if req is None:
            return
        try:
            self.transport.complete(req.req_id)
        except KeyError:
            pass
        self._preempt_restart(slot)

    def save_snapshot(self, ckpt, step: int, blocking: bool = True) -> None:
        """Persist `snapshot()` through the Checkpointer manifest format
        (checkpoint/checkpointer.py): array leaves go to the npz shard,
        the JSON-able skeleton rides in the manifest's `extra`."""
        from repro.checkpoint.checkpointer import pack_tree
        leaves, meta = pack_tree(self.snapshot())
        ckpt.save(step, leaves, extra={"engine_snapshot": meta},
                  blocking=blocking)

    def load_snapshot(self, ckpt, step: Optional[int] = None) -> dict:
        """Restore this engine from the latest (or given) persisted
        snapshot; returns the decoded snapshot dict."""
        from repro.checkpoint.checkpointer import unpack_tree
        meta, leaves = ckpt.load(step)
        snap = unpack_tree(meta["extra"]["engine_snapshot"], leaves)
        self.restore(snap)
        return snap
