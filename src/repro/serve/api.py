"""Pluggable serving subsystem API (DESIGN.md §2).

JingZhao's pitch is a fixed frame with swappable subsystems: prototype the
Queue / Resource / Transport machinery once, then drop new network
functions into stable interfaces. This module is that frame for the
serving engine. `ServingEngine` (serve/engine.py) is a thin driver over
five protocols, each the serving analogue of a paper subsystem:

  Scheduler        <- Queue Subsystem   (doorbell -> WQE dispatch, QoS
                      classes over a real N-queue HostMultiQueue)
  StateBackend     <- Resource Subsystem (MTT/page accounting + the
                      decode-state layout: dense KV slabs, the paged KV
                      pool, MLA latent pages, or constant-size recurrent
                      state — the paper's QPC, a compact per-connection
                      context, generalized to "whatever a slot needs")
  ParkingTransport <- Transport Subsystem (host-tier park/restore moves
                      with BusModel timing, the VoQ overflow path)
  Sampler          <- a Semantics-tier handler (sPIN's model): per-token
                      selection runs ON DEVICE inside the decode span,
                      swappable without forking the pipeline (§3.7)
  Frontend         <- the client-facing side of the Transport tier:
                      continuous arrivals while the engine steps,
                      per-token streaming, SLO-graded admission (§3.8)

Implementations register by name (`register_scheduler`,
`register_state_backend`, `register_sampler`, `register_frontend`) so
launchers, benchmarks, and third-party code select parts with a string —
adding a scheduling policy, state layout, sampling strategy, or serving
front end is a plug-in, not an engine edit. serve/schedulers.py,
serve/state_backends.py, serve/samplers.py, serve/parking.py and
serve/frontend.py hold the built-ins; `make_engine` wires a full engine
from an `EngineConfig` and `make_frontend` a front end over it.

`KVBackend` / `register_kv_backend` / `make_kv_backend` remain as
aliases of the renamed `StateBackend` surface for older call sites.
"""
from __future__ import annotations

import inspect
import time
from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Protocol, Tuple, Type, runtime_checkable)

import numpy as np

from repro.core.resource import BusModel


@dataclass
class SamplingParams:
    """Per-request token-selection parameters (DESIGN.md §3.7).

    The defaults are exact greedy: `temperature <= 0` short-circuits to
    argmax of the raw logits, byte-identical to the pre-sampler engine.
    `top_k <= 0` and `top_p >= 1` disable their filters. `seed` is the
    replayable stream identity (folded into the key modulo 2^32): a
    request's KEY stream is a pure function of `(seed, req_id)` and its
    position in the emitted stream — independent of batching, span
    bucketing, prefill chunking, and park/unpark timing — so the token
    stream replays exactly wherever the logits are bit-equal (always
    true for batching/span/park variation; chunked vs monolithic
    prefill is logit-equal only to the 1e-4 pinned tolerance, so a draw
    sitting exactly on a categorical boundary could in principle flip).
    """
    temperature: float = 0.0
    top_k: int = 0                # 0 = full vocab
    top_p: float = 1.0
    seed: int = 0
    logprobs: bool = False        # record chosen-token logprobs


@dataclass
class Request:
    req_id: int
    prompt: np.ndarray
    max_new_tokens: int = 32
    qos: int = 0                  # QoS class; 0 = highest priority
    arrived_at: float = 0.0
    tokens_out: List[int] = field(default_factory=list)
    finished_at: Optional[float] = None
    sampling: SamplingParams = field(default_factory=SamplingParams)
    logprobs_out: List[float] = field(default_factory=list)
    # lifecycle stamps on the engine's clock, each set once: when the
    # request first got a slot (`_admit`) and when its first token was
    # emitted (`_emit`); a preempt-restart or an unpark moves neither
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    # streaming hooks (DESIGN.md §3.8): the engine invokes `on_tokens`
    # with the freshly appended token batch at each host-sync point (one
    # per prefill completion, one per decode span — never more), and
    # `on_done` exactly once when the request completes. A preempt-
    # restart replays the stream from index 0; the Frontend handle
    # dedupes by emitted index so client streams stay byte-identical to
    # `tokens_out`.
    on_tokens: Optional[Callable[["Request", List[int]], None]] = \
        field(default=None, repr=False, compare=False)
    on_done: Optional[Callable[["Request"], None]] = \
        field(default=None, repr=False, compare=False)


@dataclass
class EngineConfig:
    slots: int = 4
    cache_len: int = 256
    page_size: int = 16
    n_pages: int = 256            # device page budget (admission control)
    prefix_cache_entries: int = 32
    prefill_chunk: int = 0        # tokens per prefill chunk; 0 = monolithic
    prefill_budget: int = 0       # prefill tokens per engine step, spent
                                  # in whole chunks (min. one chunk/step);
                                  # 0 derives it from prefill_chunk
    decode_span: int = 8          # decode steps fused into one jitted
                                  # lax.scan between host syncs (1 =
                                  # per-step decode; DESIGN.md §3.6)
    eos_token: int = 0
    host_offload: bool = True     # VoQ overflow tier
    kv_layout: str = "dense"      # StateBackend name: "dense" | "paged"
                                  # | "latent" (MLA) | "recurrent"
    scheduler: str = "fcfs"       # Scheduler name: "fcfs" | "priority" | ...
    sampler: str = "greedy"       # Sampler name: "greedy" | "stochastic"
    frontend: str = "local"       # Frontend name (DESIGN.md §3.8)
    qos_classes: int = 4          # queues a multi-class scheduler exposes
    queue_capacity: int = 1 << 12
    bus: BusModel = field(default_factory=BusModel)
    # the ONE time source: arrival stamps, eviction tie-breaks, bus-timed
    # park/restore readiness and SLO accounting all read it, so tests and
    # benchmarks swap in a deterministic virtual clock (frontend.VirtualClock)
    clock: Callable[[], float] = field(
        default=time.perf_counter,  # jz: allow[JZ003] the injection point itself
        repr=False, compare=False)
    # -- front-end admission control (DESIGN.md §3.8) -----------------
    admit_capacity: int = 64      # bounded front-end wait pool (all classes)
    feed_depth: int = 0           # engine-scheduler backlog the frontend
                                  # keeps fed; 0 derives it from `slots`
    slo_ttft: Tuple[float, ...] = ()   # per-class TTFT budgets, clock units
                                       # (shorter tuple broadcasts its last
                                       # entry; () or <= 0 = no budget)
    slo_tpot: Tuple[float, ...] = ()   # per-class per-token budgets
    degrade_max_new: int = 0      # > 0: under pressure, non-top classes
                                  # are admitted with max_new_tokens
                                  # clamped to this instead of shed


class ParkMeta(NamedTuple):
    """Restore metadata a StateBackend attaches to parked slot state."""
    length: int
    position: int
    slot: int
    n_pages: int                  # 0 for layouts without page indirection


def _opt_float(x) -> Optional[float]:
    return None if x is None else float(x)


def request_to_state(req: Request) -> dict:
    """JSON-able snapshot of a Request (DESIGN.md §9).

    Streaming hooks are intentionally dropped: they are process-local
    callables that `Frontend.reattach` re-wires after a restore. The PR 5
    determinism anchors — `sampling.seed` and `len(tokens_out)` (the
    emitted index the PRNG key derivation folds in) — are carried
    verbatim, so a restored request re-derives its key stream exactly.
    """
    s = req.sampling
    return {
        "req_id": int(req.req_id),
        "prompt": [int(t) for t in np.asarray(req.prompt).reshape(-1)],
        "max_new_tokens": int(req.max_new_tokens),
        "qos": int(req.qos),
        "arrived_at": float(req.arrived_at),
        "tokens_out": [int(t) for t in req.tokens_out],
        "finished_at": _opt_float(req.finished_at),
        "sampling": [float(s.temperature), int(s.top_k), float(s.top_p),
                     int(s.seed), bool(s.logprobs)],
        "logprobs_out": [float(x) for x in req.logprobs_out],
        "admitted_at": _opt_float(req.admitted_at),
        "first_token_at": _opt_float(req.first_token_at),
    }


def request_from_state(d: dict) -> Request:
    temp, top_k, top_p, seed, logprobs = d["sampling"]
    return Request(
        req_id=int(d["req_id"]),
        prompt=np.asarray(d["prompt"], dtype=np.int32),
        max_new_tokens=int(d["max_new_tokens"]),
        qos=int(d["qos"]),
        arrived_at=float(d["arrived_at"]),
        tokens_out=[int(t) for t in d["tokens_out"]],
        finished_at=_opt_float(d["finished_at"]),
        sampling=SamplingParams(float(temp), int(top_k), float(top_p),
                                int(seed), bool(logprobs)),
        logprobs_out=[float(x) for x in d["logprobs_out"]],
        admitted_at=_opt_float(d.get("admitted_at")),
        first_token_at=_opt_float(d.get("first_token_at")))


# --------------------------------------------------------------------------
# protocols
# --------------------------------------------------------------------------

@runtime_checkable
class Scheduler(Protocol):
    """Queue Subsystem: admission order over QoS class queues.

    The engine rings the doorbell with `submit`, pops the next WQE with
    `next`, and returns work it could not place with `requeue` — which
    MUST preserve the request's original QoS class (a requeued request
    is not a new arrival).
    """
    n_classes: int

    def class_of(self, req: Request) -> int: ...
    def submit(self, req: Request) -> bool: ...
    def next(self) -> Optional[Request]: ...
    def requeue(self, req: Request) -> bool: ...
    # crash recovery (DESIGN.md §9): `export` returns the queued work
    # non-destructively as (per-class request lists, JSON-able aux state
    # such as a round-robin cursor); `import_` loads that into a fresh
    # scheduler, preserving pop order exactly.
    def export(self) -> Tuple[List[List[Request]], dict]: ...
    def import_(self, queues: List[List[Request]], aux: dict) -> None: ...
    @property
    def pending(self) -> int: ...
    @property
    def space(self) -> int: ...   # free submit capacity (backpressure
    #                               signal — a caller that checks it never
    #                               has to learn about fullness by raising)


@runtime_checkable
class StateBackend(Protocol):
    """Resource Subsystem: a slot's decode-state layout + accounting.

    Generalizes the KV cache to "whatever state a slot's architecture
    decodes from": dense KV slabs, paged KV behind an MTT, MLA latent
    pages (`[kv_lora_rank + qk_rope_dim]` per token), or constant-size
    recurrent carries (RWKV/Mamba `[H, hd, hd]`-style state). Owns the
    PagePool (the MTT) and every layout-specific state operation; the
    engine never branches on the layout. `append` is alloc-on-append
    capacity growth (also used to reserve the admission `footprint`);
    `sync` re-exports indirection tables into the decode state when they
    changed and is a no-op otherwise.

    Capability flags route engine behavior instead of config sniffing:
    `needs_growth` gates span reservation/pool growth/preemption,
    `supports_chunked_prefill` gates streaming prefill, and
    `supports_prefix_share` gates the block prefix cache (a recurrent
    carry folds the whole prefix into one tensor, so it declines).
    """
    needs_growth: bool            # True if capacity can run out mid-decode
    supports_chunked_prefill: bool  # slot state extends a chunk at a time
    supports_prefix_share: bool   # per-token blocks can back a PrefixCache
    pool: Any                     # PagePool (admission accounting)

    def init_state(self) -> dict: ...
    def footprint(self, req: Request) -> int: ...
    # admission: None if `req` can ever be resident under this layout,
    # else a human-readable reason (the engine raises it on submit)
    def admission_error(self, req: Request) -> Optional[str]: ...
    def append(self, req_id: int, n_tokens: int) -> bool: ...
    # decode spans: claim page headroom for a whole span up front —
    # alloc-on-append cannot fire inside the jitted scan, so the engine
    # reserves `n_tokens` total capacity before dispatch and shrinks a
    # slot's span budget to what the pool actually granted
    def reserve_span(self, req_id: int, n_tokens: int) -> bool: ...
    def held(self, req_id: int) -> int: ...
    def prefill_into_slot(self, state: dict, slot: int, req_id: int,
                          caches, length: int) -> dict: ...
    # chunked prefill: stage a slot's KV as a batch-1 dense tree, extend
    # it one chunk at a time, write the chunk's pages/rows back
    def slot_caches(self, state: dict, slot: int, req_id: int) -> Any: ...
    def store_chunk(self, state: dict, slot: int, req_id: int, caches,
                    start: int, n_tokens: int) -> dict: ...
    # longest-prefix block sharing: install cached payloads into a slot,
    # export a prefilled slot's blocks, pin/unpin cache-held payloads
    def share_prefix(self, state: dict, slot: int, req_id: int,
                     payloads: List[Any], n_tokens: int) -> dict: ...
    def block_payload(self, state: dict, slot: int, req_id: int,
                      block: int) -> Any: ...
    def cache_retain(self, payload: Any) -> None: ...
    def cache_release(self, payload: Any) -> None: ...
    def park(self, state: dict, slot: int,
             req_id: int) -> Tuple[Any, ParkMeta]: ...
    def unpark(self, state: dict, slot: int, req: Request, caches,
               meta: ParkMeta) -> Tuple[bool, dict]: ...
    def release(self, req_id: int) -> None: ...
    def mark_dirty(self) -> None: ...
    def sync(self, state: dict,
             slot_req_ids: List[Optional[int]]) -> dict: ...
    # crash recovery (DESIGN.md §9): `export_state` captures the full
    # resource tier — pool bookkeeping plus the device KV contents —
    # as host arrays and JSON-able scalars; `import_state` rebuilds a
    # fresh decode state from that snapshot. `snapshot_payload` /
    # `restore_payload` are the layout's codec for opaque block payloads
    # (prefix-cache entries: page ids for paged, host KV trees for dense).
    def export_state(self, state: dict) -> dict: ...
    def import_state(self, snap: dict) -> dict: ...
    def snapshot_payload(self, payload: Any) -> Any: ...
    def restore_payload(self, data: Any) -> Any: ...


# Back-compat alias: PRs 1-9 called this protocol `KVBackend`. The
# rename is pure — same members, same registry object — so older
# implementations and annotations keep working unmodified.
KVBackend = StateBackend


@runtime_checkable
class Sampler(Protocol):
    """Sampling Subsystem: on-device token selection (DESIGN.md §3.7).

    `sample(logits [B,V], keys [B,2] | None, params)` picks one token
    per row and MUST be jax-traceable with no host state: the engine
    calls it inside the jitted decode span and the jitted prefill
    first-token selector, so a sampler can never add host syncs to the
    fast path. `slot_params(req)` extracts the per-request parameters
    as a fixed-arity tuple of numpy scalars (constant dtypes; `req is
    None` must yield defaults for empty slots) — the engine stacks them
    into per-slot arrays and passes them through as `params`. When
    `needs_rng` is set, `keys` are per-slot threefry keys derived from
    `(seed, req_id, token_index)` (kernels/sampling.derive_keys), so
    sampled streams replay deterministically through batching, span
    bucketing, park/unpark and preempt-restart.
    """
    needs_rng: bool

    def slot_params(self, req: Optional[Request]) -> Tuple[Any, ...]: ...
    def sample(self, logits, keys, params): ...


@runtime_checkable
class Frontend(Protocol):
    """Serving Front End: the client-facing side of the Transport tier
    (DESIGN.md §3.8).

    `submit` accepts a request at ANY time — including between engine
    steps of an in-flight run (continuous arrivals) — applies SLO-graded
    admission control over bounded per-class wait queues, and returns a
    handle that streams tokens and resolves to an explicit terminal
    outcome (completed | rejected | shed — never a silent drop). `step`
    pumps one engine step: expire SLO-blown waiters, feed the engine's
    scheduler up to `feed_depth`, run `engine.step()`, resolve
    completions. `run` drives a timed arrival trace to drain.
    """

    def submit(self, req: Request,
               on_token: Optional[Callable] = None) -> Any: ...
    def step(self) -> None: ...
    def run(self, arrivals=None, max_steps: int = 100_000,
            drain: bool = True) -> List[Any]: ...
    # crash recovery (DESIGN.md §9): rebind live streaming handles to a
    # restored engine — re-wire callbacks for requests the snapshot
    # carried, resubmit the ones it lost (handles dedupe by emitted
    # index, so client streams stay byte-identical either way).
    def reattach(self, engine) -> None: ...
    @property
    def live(self) -> bool: ...


@runtime_checkable
class ParkingTransport(Protocol):
    """Transport Subsystem: the host-tier move/restore channel.

    `begin` starts an eviction transfer (completion time modeled by the
    bus), `ready` lists transfers whose data is back-restorable, `peek`
    reads a parked entry, `complete` retires it after a successful
    unpark. `in_flight` counts parked entries (the engine's drain
    condition).
    """

    def begin(self, req_id: int, caches, meta: ParkMeta) -> None: ...
    def ready(self, now: Optional[float] = None) -> List[int]: ...
    def peek(self, req_id: int) -> Tuple[Any, ParkMeta]: ...
    def complete(self, req_id: int) -> None: ...
    # crash recovery (DESIGN.md §9): parked payloads are engine state too
    # — a crash between park and unpark must not lose the host-tier copy.
    def export_state(self) -> dict: ...
    def import_state(self, snap: dict) -> None: ...
    @property
    def in_flight(self) -> int: ...


# --------------------------------------------------------------------------
# registries — new subsystems plug in by name
# --------------------------------------------------------------------------

SCHEDULERS: Dict[str, Type] = {}
STATE_BACKENDS: Dict[str, Type] = {}
KV_BACKENDS = STATE_BACKENDS    # back-compat alias (same dict object)
SAMPLERS: Dict[str, Type] = {}
FRONTENDS: Dict[str, Type] = {}


def _positional_shape(fn) -> Optional[Tuple[int, int]]:
    """(min, max) positional arity after self/cls; max = -1 for *args.
    None when the callable has no introspectable signature."""
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return None
    params = list(sig.parameters.values())
    pos = [p for p in params
           if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]
    if pos and pos[0].name in ("self", "cls"):
        pos = pos[1:]
    required = sum(1 for p in pos if p.default is p.empty)
    if any(p.kind == p.VAR_POSITIONAL for p in params):
        return (required, -1)
    return (required, len(pos))


def _conformance_errors(cls: Type, proto: Type) -> List[str]:
    """Structural check of `cls` against `proto`'s declared members.

    The registration-time mirror of jzlint rule JZ005 (DESIGN.md §8):
    methods and properties the Protocol body declares must exist on the
    class with call-compatible positional arity. Annotation-only data
    attrs (`n_classes`, `pool`, ...) are exempt — implementations set
    those per-instance in `__init__`.
    """
    errors: List[str] = []
    for pname, member in sorted(vars(proto).items()):
        if pname.startswith("_"):
            continue
        if isinstance(member, property):
            if not hasattr(cls, pname):
                errors.append(f"missing property `{pname}`")
        elif inspect.isfunction(member):
            impl = getattr(cls, pname, None)
            if impl is None:
                errors.append(f"missing method `{pname}`")
            elif not callable(impl):
                errors.append(f"`{pname}` must be callable, got "
                              f"{type(impl).__name__}")
            else:
                want = _positional_shape(member)
                have = _positional_shape(impl)
                if want is None or have is None:
                    continue
                if have[0] > want[0]:
                    errors.append(
                        f"`{pname}` requires {have[0]} positional "
                        f"arg(s) but the protocol passes as few as "
                        f"{want[0]}")
                elif have[1] != -1 and have[1] < want[1]:
                    errors.append(
                        f"`{pname}` accepts at most {have[1]} "
                        f"positional arg(s) but the protocol declares "
                        f"{want[1]}")
    return errors


def _checked_register(kind: str, proto: Type, registry: Dict[str, Type]
                      ) -> Callable[[str], Callable[[Type], Type]]:
    def register(name: str) -> Callable[[Type], Type]:
        def deco(cls: Type) -> Type:
            errors = _conformance_errors(cls, proto)
            if errors:
                raise TypeError(
                    f"cannot register {kind} {name!r}: class "
                    f"`{cls.__name__}` does not satisfy "
                    f"`{proto.__name__}`: " + "; ".join(errors))
            cls.name = name
            registry[name] = cls
            return cls
        return deco
    return register


register_scheduler = _checked_register("scheduler", Scheduler, SCHEDULERS)
register_state_backend = _checked_register(
    "state backend", StateBackend, STATE_BACKENDS)
register_kv_backend = register_state_backend  # back-compat alias
register_sampler = _checked_register("sampler", Sampler, SAMPLERS)
register_frontend = _checked_register("frontend", Frontend, FRONTENDS)


def make_scheduler(name: str, n_classes: int = 4,
                   capacity: int = 1 << 12) -> Scheduler:
    from repro.serve import schedulers  # noqa: F401  (registers built-ins)
    if name not in SCHEDULERS:
        raise ValueError(f"unknown scheduler {name!r}; "
                         f"registered: {sorted(SCHEDULERS)}")
    return SCHEDULERS[name](n_classes=n_classes, capacity=capacity)


def make_state_backend(name: str, cfg, ecfg: EngineConfig) -> StateBackend:
    from repro.serve import state_backends  # noqa: F401 (registers built-ins)
    if name not in STATE_BACKENDS:
        raise ValueError(f"unknown kv layout {name!r}; "
                         f"registered: {sorted(STATE_BACKENDS)}")
    return STATE_BACKENDS[name](cfg, ecfg)


make_kv_backend = make_state_backend  # back-compat alias


def make_sampler(name: str) -> Sampler:
    from repro.serve import samplers  # noqa: F401  (registers built-ins)
    if name not in SAMPLERS:
        raise ValueError(f"unknown sampler {name!r}; "
                         f"registered: {sorted(SAMPLERS)}")
    return SAMPLERS[name]()


def make_frontend(name: str, engine, **kw) -> Frontend:
    from repro.serve import frontend  # noqa: F401  (registers built-ins)
    if name not in FRONTENDS:
        raise ValueError(f"unknown frontend {name!r}; "
                         f"registered: {sorted(FRONTENDS)}")
    return FRONTENDS[name](engine, **kw)


def slo_budget(cls: int, budgets: Tuple[float, ...]) -> Optional[float]:
    """Per-class SLO budget lookup: a shorter tuple broadcasts its last
    entry to the remaining (lower) classes; `()` or a non-positive entry
    means no budget for that class."""
    if not budgets:
        return None
    b = budgets[cls] if cls < len(budgets) else budgets[-1]
    return float(b) if b > 0 else None


def make_engine(cfg, params, ecfg: EngineConfig, policy=None,
                scheduler: Optional[Scheduler] = None,
                kv_backend: Optional[KVBackend] = None,
                transport: Optional[ParkingTransport] = None,
                sampler: Optional[Sampler] = None):
    """Build a ServingEngine with parts resolved by name from `ecfg`
    (or injected directly for third-party subsystems)."""
    from repro.serve.engine import ServingEngine
    from repro.sharding.policy import NULL_POLICY
    return ServingEngine(cfg, params, ecfg,
                         policy=policy if policy is not None else NULL_POLICY,
                         scheduler=scheduler, kv_backend=kv_backend,
                         transport=transport, sampler=sampler)


def default_page_budget(slots: int, cache_len: int, page_size: int,
                        slack_slots: int = 1) -> int:
    """Device page budget backing `slots` worst-case sequences.

    One full dense reservation per slot plus `slack_slots` slots' worth
    of headroom so an unpark re-allocation never deadlocks against a
    fully-committed pool.
    """
    per_slot = -(-cache_len // page_size)
    return (slots + slack_slots) * per_slot
