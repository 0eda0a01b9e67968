"""Decode spans (DESIGN.md §3.6): N decode steps fused into one jitted
lax.scan with on-device stop masks and page-headroom reservation.

The load-bearing contract is token identity: for any span, in both KV
layouts, under page pressure, parking and mid-span termination, the
emitted streams must be byte-identical to per-step decode
(decode_span=1) — the span is a host-overhead optimization, never a
semantics change.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import SMOKE_CONFIGS
from repro.core.resource import PagePool
from repro.kernels.paged_attention import (live_table_width,
                                           paged_decode_attention)
from repro.models import lm
from repro.serve.engine import EngineConfig, Request, ServingEngine
from repro.sharding.policy import NULL_POLICY


@pytest.fixture(scope="module")
def tiny():
    cfg = SMOKE_CONFIGS["qwen3-8b"].scaled(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=256, dtype="float32")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompt(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(
        1, vocab, size=n).astype(np.int32)


def _mk(cfg, params, span, **kw):
    e = dict(slots=3, cache_len=96, n_pages=64, page_size=8, eos_token=-1,
             decode_span=span)
    e.update(kw)
    return ServingEngine(cfg, params, EngineConfig(**e))


# ---------------------------------------------------------------------------
# model level: decode_span == chained decode_step
# ---------------------------------------------------------------------------

def test_decode_span_matches_chained_decode_steps(tiny):
    """One span of N is the same computation as N decode_steps: same
    tokens emitted, same final counters, same caches."""
    cfg, params = tiny
    L, span = 32, 4
    prompt = _prompt(7, seed=1)
    logits, state = lm.prefill(params, jnp.asarray(prompt[None]), cfg,
                               NULL_POLICY, cache_len=L)
    tok0 = int(jnp.argmax(logits[0]))

    # per-step reference
    ref_state = jax.tree.map(lambda x: x, state)
    act = jnp.asarray([True])
    step = jax.jit(lambda p, t, s, a: lm.decode_step(
        p, t, s, cfg, NULL_POLICY, active=a))
    ref_toks, tok = [], tok0
    for _ in range(span):
        lg, ref_state = step(params, jnp.asarray([tok], jnp.int32),
                             ref_state, act)
        tok = int(jnp.argmax(lg[0]))
        ref_toks.append(tok)

    fn = jax.jit(lambda p, t, s, a, b: lm.decode_span(
        p, t, s, cfg, NULL_POLICY, a, b, span=span, eos_token=-1,
        cache_len=L))
    toks, emit, state = fn(params, jnp.asarray([tok0], jnp.int32), state,
                           act, jnp.asarray([span], jnp.int32))
    assert np.asarray(emit)[:, 0].all()
    assert [int(t) for t in np.asarray(toks)[:, 0]] == ref_toks
    assert int(state["positions"][0]) == int(ref_state["positions"][0])
    leaves = zip(jax.tree.leaves(state["caches"]),
                 jax.tree.leaves(ref_state["caches"]))
    for a, b in leaves:
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_decode_span_budget_freezes_slot_mid_span(tiny):
    """A slot whose budget is below the span freezes exactly at its
    budget: no further emissions, counters and caches halted."""
    cfg, params = tiny
    L = 32
    prompt = _prompt(5, seed=2)
    _, state = lm.prefill(params, jnp.asarray(prompt[None]), cfg,
                          NULL_POLICY, cache_len=L)
    pos0 = int(state["positions"][0])
    fn = jax.jit(lambda p, t, s, a, b: lm.decode_span(
        p, t, s, cfg, NULL_POLICY, a, b, span=8, eos_token=-1,
        cache_len=L))
    toks, emit, state = fn(params, jnp.asarray([3], jnp.int32), state,
                           jnp.asarray([True]), jnp.asarray([3], jnp.int32))
    emit = np.asarray(emit)[:, 0]
    assert emit.tolist() == [True] * 3 + [False] * 5
    assert int(state["positions"][0]) == pos0 + 3


# ---------------------------------------------------------------------------
# engine level: span output identical to per-step, both layouts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_span_engine_matches_per_step_engine(tiny, layout):
    cfg, params = tiny
    reqs = [(i, _prompt(n, seed=40 + i))
            for i, n in enumerate([30, 9, 21, 14])]
    max_new = [12, 7, 5, 16]                # 7 and 5 straddle span=4/8
    outs, syncs = {}, {}
    for span in (1, 4, 8):
        eng = _mk(cfg, params, span, kv_layout=layout)
        for i, p in reqs:
            eng.submit(Request(i, p.copy(), max_new_tokens=max_new[i]))
        done = eng.run_until_done()
        assert len(done) == len(reqs)
        assert all(len(r.tokens_out) == max_new[r.req_id] for r in done)
        outs[span] = {r.req_id: r.tokens_out for r in done}
        # host_syncs also counts the one accounted first-token sync per
        # prefill; the span amortizes the *decode-path* round-trips
        syncs[span] = eng.stats["host_syncs"] - eng.stats["prefills"]
    assert outs[4] == outs[1]
    assert outs[8] == outs[1]
    # host round-trips collapse O(tokens) -> O(tokens/span)
    assert syncs[8] * 4 <= syncs[1]


def test_max_new_tokens_exact_mid_span(tiny):
    """max_new_tokens not a span multiple terminates exactly — the span
    must not overrun past the contract."""
    cfg, params = tiny
    for layout in ("dense", "paged"):
        eng = _mk(cfg, params, 8, kv_layout=layout)
        eng.submit(Request(0, _prompt(10, seed=5), max_new_tokens=5))
        eng.submit(Request(1, _prompt(6, seed=6), max_new_tokens=12))
        done = eng.run_until_done()
        lens = {r.req_id: len(r.tokens_out) for r in done}
        assert lens == {0: 5, 1: 12}


def test_eos_mid_span_terminates_exactly(tiny):
    """EOS emitted mid-span stops that slot on device: the stream ends at
    the first EOS with no overrun tokens, identically to per-step."""
    cfg, params = tiny
    prompt = _prompt(12, seed=7)
    eng = _mk(cfg, params, 1)
    eng.submit(Request(0, prompt.copy(), max_new_tokens=20))
    ref = eng.run_until_done()[0].tokens_out
    # pick an eos value that first appears strictly mid-stream
    eos, cut = None, None
    for j in range(1, len(ref) - 1):
        if ref.index(ref[j]) == j:
            eos, cut = ref[j], j
            break
    assert eos is not None, "reference stream has no usable mid-stream token"
    expect = ref[:cut + 1]
    for span in (1, 8):
        eng = _mk(cfg, params, span, eos_token=eos)
        eng.submit(Request(0, prompt.copy(), max_new_tokens=20))
        done = eng.run_until_done()
        assert done[0].tokens_out == expect, span


def test_cache_len_mid_span_terminates_exactly(tiny):
    """A slot filling cache_len mid-span stops there: one decode token
    per remaining cache row, never a write past the slab/table."""
    cfg, params = tiny
    prompt = _prompt(26, seed=8)
    for layout in ("dense", "paged"):
        eng = _mk(cfg, params, 8, cache_len=32, n_pages=16,
                  kv_layout=layout)
        eng.submit(Request(0, prompt.copy(), max_new_tokens=64))
        done = eng.run_until_done()
        assert len(done[0].tokens_out) == 32 - 26 + 1


# ---------------------------------------------------------------------------
# page-headroom reservation
# ---------------------------------------------------------------------------

def test_page_exhaustion_shrinks_span_and_progresses(tiny):
    """A pool too dry to back full spans shrinks per-slot budgets (via
    reserve_span) instead of stalling or corrupting: everything still
    completes with per-step-identical output."""
    cfg, params = tiny
    # 12-token prompts hold 2 pages (16 token slots): a full span of 8
    # needs a 3rd page per slot, which a 4-page pool cannot grant both —
    # budgets must shrink to the 4 in-page slots left
    reqs = [(i, _prompt(12, seed=50 + i)) for i in range(2)]
    outs = {}
    for span, n_pages in ((1, 64), (8, 4)):
        eng = _mk(cfg, params, span, slots=2, n_pages=n_pages,
                  kv_layout="paged")
        for i, p in reqs:
            eng.submit(Request(i, p.copy(), max_new_tokens=10))
        done = eng.run_until_done()
        assert len(done) == 2
        eng.prefix.clear()
        assert eng.pool.n_free == eng.pool.n_pages
        outs[span] = {r.req_id: r.tokens_out for r in done}
    assert outs[8] == outs[1]
    assert eng.stats["span_shrinks"] > 0      # the tight pool really bit
    assert eng.stats["pages_peak"] <= 4


def test_span_interleaves_with_stall_no_host_tier(tiny):
    """host_offload=False under a dry pool: slots stall in place between
    spans and resume when pages free, outputs still per-step-identical."""
    cfg, params = tiny
    reqs = [(i, _prompt(n, seed=60 + i))
            for i, n in enumerate([20, 14, 18])]
    outs = {}
    for span, n_pages, layout in ((1, 64, "dense"), (8, 9, "paged")):
        eng = _mk(cfg, params, span, n_pages=n_pages, kv_layout=layout,
                  host_offload=False)
        for i, p in reqs:
            eng.submit(Request(i, p.copy(), max_new_tokens=16))
        done = eng.run_until_done()
        assert len(done) == len(reqs)
        eng.prefix.clear()
        assert eng.pool.n_free == eng.pool.n_pages
        outs[span] = {r.req_id: r.tokens_out for r in done}
    assert outs[8] == outs[1]


def test_park_mid_stream_interleaves_with_spans(tiny):
    """Parking a sequence between spans (VoQ move to the host tier) and
    resuming later yields the never-parked stream."""
    cfg, params = tiny
    prompt = _prompt(11, seed=9)
    ref_eng = _mk(cfg, params, 1)
    ref_eng.submit(Request(0, prompt.copy(), max_new_tokens=20))
    ref = ref_eng.run_until_done()[0].tokens_out

    eng = _mk(cfg, params, 4)
    eng.submit(Request(0, prompt.copy(), max_new_tokens=20))
    eng.step()                          # prefill + one 4-token span
    assert len(eng.slot_req[0].tokens_out) == 5
    assert eng._evict_someone(exclude=-1)
    for _ in range(3):
        eng.step()                      # spans run with the slot frozen
    time.sleep(0.001)
    done = eng.run_until_done()
    assert eng.stats["unparked"] == 1
    assert done[0].tokens_out == ref


# ---------------------------------------------------------------------------
# run_until_done exhaustion is loud
# ---------------------------------------------------------------------------

def test_run_until_done_raises_on_stranded_work(tiny):
    cfg, params = tiny
    eng = _mk(cfg, params, 1)
    eng.submit(Request(7, _prompt(8, seed=10), max_new_tokens=50))
    with pytest.raises(RuntimeError, match=r"\[7\]"):
        eng.run_until_done(max_steps=2)
    assert eng.stats["incomplete"] == [7]
    # the same engine can still finish the work afterwards
    done = eng.run_until_done()
    assert len(done) == 1 and len(done[0].tokens_out) == 50


# ---------------------------------------------------------------------------
# bounded page-table export
# ---------------------------------------------------------------------------

def test_live_table_width_buckets():
    assert live_table_width(0, 8) == 1
    assert live_table_width(1, 8) == 1
    assert live_table_width(2, 8) == 2
    assert live_table_width(3, 8) == 4
    assert live_table_width(5, 8) == 8
    assert live_table_width(9, 8) == 8
    assert live_table_width(3, 3) == 3       # cap wins over the bucket


def test_bounded_table_matches_full_width_both_backends():
    """Gathering only the live pow2 bucket of table entries is
    math-identical to the max_pages-wide gather, and the jnp oracle
    still matches the Pallas kernel at the narrowed width."""
    rng = np.random.default_rng(11)
    NP, page, KV, hd, B, H = 16, 4, 2, 8, 2, 4
    kp = jnp.asarray(rng.normal(size=(NP, KV, page, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NP, KV, page, hd)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    pool = PagePool(n_pages=NP, page_size=page)
    pool.alloc(99, 2)                        # non-trivial page ids
    pool.alloc(0, 3)                         # slot 0: 3 live pages
    pool.alloc(1, 1)                         # slot 1: 1 live page
    lengths = jnp.asarray([10, 3], jnp.int32)
    MP_full = 8
    MP_live = live_table_width(3, MP_full)
    assert MP_live < MP_full
    t_full = jnp.asarray(pool.table_matrix([0, 1], MP_full))
    t_live = jnp.asarray(pool.table_matrix([0, 1], MP_live))

    full = paged_decode_attention(q, kp, vp, t_full, lengths, backend="jnp")
    live = paged_decode_attention(q, kp, vp, t_live, lengths, backend="jnp")
    np.testing.assert_allclose(np.asarray(live), np.asarray(full),
                               atol=1e-6)
    pallas_live = paged_decode_attention(q, kp, vp, t_live, lengths,
                                         backend="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(pallas_live), np.asarray(live),
                               atol=2e-2)


def test_engine_counts_the_kernels_table_walk(tiny):
    """attn_pages_table grows by span steps x slots x exported width,
    attn_pages_live by span steps x pages the decoding slots hold."""
    cfg, params = tiny
    eng = _mk(cfg, params, 4, slots=3, cache_len=96, n_pages=64,
              kv_layout="paged")
    eng.submit(Request(0, _prompt(9, seed=1), max_new_tokens=4))
    eng.submit(Request(1, _prompt(30, seed=2), max_new_tokens=4))
    eng.run_until_done()
    # one span: both slots owe 3 tokens, so it runs 4 steps; slot 0
    # reserves positions to 9+3 (2 pages of 8), slot 1 to 30+3 (5
    # pages); the table is exported at width 8 (the pow2 over 5) for
    # 3 slots, the third free
    assert eng.stats["decode_spans"] == 1
    assert eng.stats["attn_pages_table"] == 4 * 3 * 8
    assert eng.stats["attn_pages_live"] == 4 * (2 + 5)
    eng.submit(Request(2, _prompt(17, seed=3), max_new_tokens=2))
    eng.run_until_done()
    # one step for one token at position 17: 3 pages, width 4
    assert eng.stats["attn_pages_table"] == 96 + 1 * 3 * 4
    assert eng.stats["attn_pages_live"] == 28 + 1 * 3


def test_engine_exports_bucketed_tables(tiny):
    """PagedKV.sync exports the MTT at the live pow2 width, and the
    width tracks growth across spans."""
    cfg, params = tiny
    eng = _mk(cfg, params, 4, slots=2, cache_len=96, n_pages=32,
              kv_layout="paged")
    eng.submit(Request(0, _prompt(9, seed=12), max_new_tokens=30))
    eng.step()
    w0 = eng.state["page_table"].shape[1]
    max_pages = 96 // 8
    assert w0 < max_pages                    # 2 live pages -> narrow table
    assert w0 == live_table_width(eng.kv.held(0), max_pages)
    done = eng.run_until_done()
    assert len(done[0].tokens_out) == 30
