"""What the engine leaves for a profiler and a benchmark to read: a host
span around each part of an engine step, stable names on its device
programs with the sampler under its own scope, and the lifecycle stamps
of each request (DESIGN.md §3.8)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program_trace
from repro.configs.registry import SMOKE_CONFIGS
from repro.models import lm
from repro.serve.api import (EngineConfig, Request, SamplingParams,
                             make_engine, make_frontend, request_from_state,
                             request_to_state)
from repro.serve.engine import span_program
from repro.serve.frontend import VirtualClock
from repro.sharding.policy import NULL_POLICY

SPANS = ("serve.step", "serve.admit", "serve.prefill", "serve.kv.stage",
         "serve.kv.store", "serve.grow", "serve.reserve", "serve.kv.sync",
         "serve.dispatch", "serve.host_sync", "serve.emit")


@pytest.fixture(scope="module")
def tiny():
    cfg = SMOKE_CONFIGS["qwen3-8b"].scaled(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=256, dtype="float32")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _ecfg(**kw):
    base = dict(slots=2, cache_len=64, n_pages=32, page_size=8,
                kv_layout="paged", prefill_chunk=8, sampler="stochastic",
                eos_token=-1, decode_span=4)
    return EngineConfig(**{**base, **kw})


def _req(i, n, max_new=6, seed=0):
    prompt = np.random.default_rng(seed + i).integers(
        1, 256, size=n).astype(np.int32)
    return Request(i, prompt, max_new_tokens=max_new,
                   sampling=SamplingParams(temperature=0.7, top_p=0.9,
                                           seed=i))


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

def test_engine_spans_land_in_the_profiler_trace(tiny, tmp_path):
    """A paged engine with chunked prefill, traced: every serve.* span
    shows up, and the sync, the dispatch and the emission loop nest
    inside an engine step."""
    cfg, params = tiny
    eng = make_engine(cfg, params, _ecfg())
    for i in range(3):
        eng.submit(_req(i, 20))
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run_until_done()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    host = program_trace.load(str(path))["host"]
    assert set(SPANS) <= {n for n, _, _ in host}
    steps = program_trace.host_spans({"host": host}, "serve.step")
    for inner in ("serve.host_sync", "serve.dispatch", "serve.emit"):
        for a, b in program_trace.host_spans({"host": host}, inner):
            assert any(s <= a and b <= e for s, e in steps), inner


# ---------------------------------------------------------------------------
# program names and the sampler's scope
# ---------------------------------------------------------------------------

def _module_name(lowered) -> str:
    return re.match(r"module @(\S+)", lowered.as_text()).group(1)


def test_device_programs_carry_stable_names(tiny):
    cfg, params = tiny
    ecfg = _ecfg()
    eng = make_engine(cfg, params, ecfg)
    B, L, V = ecfg.slots, ecfg.cache_len, cfg.vocab_size
    i32 = jnp.int32
    vec = lambda dt, n=B: jax.ShapeDtypeStruct((n,), dt)  # noqa: E731
    sp = tuple(vec(jnp.asarray(x).dtype)
               for x in eng.sampler.slot_params(None))
    state = jax.eval_shape(lambda: lm.init_paged_serve_state(
        cfg, B, ecfg.n_pages, ecfg.page_size, 8))
    span = span_program(cfg, NULL_POLICY, ecfg, eng.sampler, 4, False)
    low = span.lower(params, vec(i32), state, vec(jnp.bool_), vec(i32),
                     sp, (vec(i32),) * 3)
    assert _module_name(low) == "jit_serve_decode_span"
    # the sampler's ops keep the scope in their metadata, so a trace
    # can put their device time down to the sampler
    assert re.search(r'op_name="jit\(serve_decode_span\)/[^"]*/sampler/',
                     low.compile().as_text())

    caches = jax.eval_shape(
        lambda: lm.init_serve_state(cfg, 1, L, filled=False)["caches"])
    chunk = eng._prefill_chunk.lower(
        params, jax.ShapeDtypeStruct((1, ecfg.prefill_chunk), i32), caches,
        jnp.int32(0), jnp.int32(ecfg.prefill_chunk))
    assert _module_name(chunk) == "jit_serve_prefill_chunk"
    sel = eng._select_fn.lower(
        jax.ShapeDtypeStruct((1, V), jnp.float32),
        tuple(vec(x.dtype, 1) for x in sp), (vec(i32, 1),) * 3)
    assert _module_name(sel) == "jit_serve_select"
    full = eng._prefill.lower(params, jax.ShapeDtypeStruct((1, 12), i32))
    assert _module_name(full) == "jit_serve_prefill"


# ---------------------------------------------------------------------------
# request stamps
# ---------------------------------------------------------------------------

def _stamps(r):
    return (r.arrived_at, r.admitted_at, r.first_token_at)


def test_stamps_are_ordered_on_the_injected_clock(tiny):
    """Three requests on two slots under a virtual clock: each is queued,
    admitted, answered and finished in that order, and the third waits
    in the queue for a slot."""
    cfg, params = tiny
    clock = VirtualClock()
    eng = make_engine(cfg, params, _ecfg(clock=clock))
    fe = make_frontend("local", eng, step_dt=1.0)
    hs = [fe.submit(_req(i, 20)) for i in range(3)]
    fe.run()
    for h in hs:
        r = h.req
        assert h.ok
        assert r.arrived_at <= r.admitted_at <= r.first_token_at \
            <= r.finished_at
        assert h.first_token_at == r.first_token_at
        assert h.ttft == r.first_token_at - h.submitted_at
    assert hs[2].req.admitted_at > hs[2].req.arrived_at
    # a 20-token prompt takes three 8-token chunks: its first token comes
    # steps after its slot
    assert hs[0].req.first_token_at - hs[0].req.admitted_at >= 2.0


def test_stamps_survive_snapshot_restore_and_restart(tiny):
    cfg, params = tiny
    clock = VirtualClock()
    eng = make_engine(cfg, params, _ecfg(clock=clock))
    fe = make_frontend("local", eng, step_dt=1.0)
    hs = [fe.submit(_req(i, 10, max_new=12)) for i in range(3)]
    for _ in range(4):
        fe.step()
    live = eng.live_requests()
    assert any(r.first_token_at is not None for r in live.values())
    snap = eng.snapshot()
    fresh = make_engine(cfg, params, _ecfg(clock=VirtualClock()))
    fresh.restore(snap)
    back = fresh.live_requests()
    assert {k: _stamps(r) for k, r in back.items()} == \
        {k: _stamps(r) for k, r in live.items()}
    for r in live.values():
        assert _stamps(request_from_state(request_to_state(r))) == \
            _stamps(r)

    # a preempt-restart requeues the request and replays its stream; its
    # stamps and its handle's TTFT stay those of the first time
    slot = next(i for i, r in enumerate(eng.slot_req)
                if r is not None and r.first_token_at is not None)
    req = eng.slot_req[slot]
    h = next(h for h in hs if h.req is req)
    before, ttft = _stamps(req), h.ttft
    eng._preempt_restart(slot)
    fe.run()
    assert eng.stats["preempt_restarts"] == 1
    assert h.ok and _stamps(req) == before and h.ttft == ttft
    assert req.first_token_at <= req.finished_at


def test_handle_keeps_its_first_token_across_a_crash(tiny):
    """A snapshot taken before a request's first token, then the token
    streams, then the process dies: the restored request has no stamp,
    and the reattached handle keeps the time its client saw the token."""
    cfg, params = tiny
    clock = VirtualClock()
    eng = make_engine(cfg, params, _ecfg(clock=clock))
    fe = make_frontend("local", eng, step_dt=1.0)
    h = fe.submit(_req(0, 20, max_new=4))
    fe.step()                                # admitted, first chunk
    snap = eng.snapshot()
    while not h.streamed:
        fe.step()
    ttft = h.ttft
    fresh = make_engine(cfg, params, _ecfg(clock=clock))
    fresh.restore(snap)
    assert fresh.live_requests()[0].first_token_at is None
    fe.reattach(fresh)
    fe.run()
    assert h.ok and h.ttft == ttft
