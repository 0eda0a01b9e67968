"""Paged KV path: dense equivalence, page reuse, ring wraparound.

The load-bearing assertion is paged-vs-dense *logit* equivalence: the
shared-pool layout (DESIGN.md §3) must be a pure memory-layout change,
invisible to the math.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import SMOKE_CONFIGS
from repro.core.multiqueue import HostMultiQueue, mq_init, mq_pop, mq_push
from repro.core.resource import PagePool
from repro.kernels.paged_attention import paged_append
from repro.models import lm
from repro.models import transformer as tf
from repro.serve.engine import EngineConfig, Request, ServingEngine
from repro.sharding.policy import NULL_POLICY


@pytest.fixture(scope="module")
def tiny():
    cfg = SMOKE_CONFIGS["qwen3-8b"].scaled(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=256, dtype="float32")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ---------------------------------------------------------------------------
# paged == dense (logits, fp32)
# ---------------------------------------------------------------------------

def test_paged_decode_matches_dense_logits(tiny):
    """Same prompt, same steps: paged and dense states yield identical
    logits (atol 1e-4 fp32) even with non-contiguous, unordered pages."""
    cfg, params = tiny
    B, L, ps = 2, 64, 8
    MP = L // ps
    prompt = np.arange(1, 12, dtype=np.int32)
    logits, st = lm.prefill(params, jnp.asarray(prompt[None]), cfg,
                            NULL_POLICY, cache_len=L)

    dense = lm.init_serve_state(cfg, B, L, filled=False)
    from repro.serve.engine import _slot_insert
    dense["caches"] = _slot_insert(dense["caches"], st["caches"], 0)
    dense["lengths"] = dense["lengths"].at[0].set(len(prompt))
    dense["positions"] = dense["positions"].at[0].set(len(prompt))

    pool = PagePool(n_pages=32, page_size=ps)
    pool.alloc(999, 3)                       # force non-trivial page ids
    npg = -(-(len(prompt) + 1) // ps)
    page_ids = pool.alloc(0, npg)
    paged = lm.init_paged_serve_state(cfg, B, 32, ps, MP,
                                      dtype=jnp.float32)
    chunks = tf.dense_to_pages(st["caches"], npg, ps)
    paged["caches"] = tf.scatter_pages(paged["caches"], chunks, page_ids)
    paged["page_table"] = jnp.asarray(pool.table_matrix([0, None], MP))
    paged["lengths"] = paged["lengths"].at[0].set(len(prompt))
    paged["positions"] = paged["positions"].at[0].set(len(prompt))

    step = jax.jit(lambda p, t, s, a: lm.decode_step(
        p, t, s, cfg, NULL_POLICY, active=a))
    tok = int(jnp.argmax(logits[0]))
    act = jnp.asarray([True, False])
    for _ in range(6):
        toks = jnp.asarray([tok, 0], jnp.int32)
        ld, dense = step(params, toks, dense, act)
        lp, paged = step(params, toks, paged, act)
        np.testing.assert_allclose(np.asarray(ld[0]), np.asarray(lp[0]),
                                   atol=1e-4)
        pos = int(paged["positions"][0])
        if pool.ensure_capacity(0, pos + 1):          # alloc-on-append
            paged["page_table"] = jnp.asarray(
                pool.table_matrix([0, None], MP))
        tok = int(jnp.argmax(ld[0]))


def test_paged_engine_matches_dense_engine(tiny):
    """Whole-engine equivalence under page pressure: tight paged budget
    forces alloc-on-append + park/unpark, outputs stay identical."""
    cfg, params = tiny
    rng = np.random.default_rng(1)
    reqs = [(i, rng.integers(1, cfg.vocab_size, size=int(n)).astype(np.int32))
            for i, n in enumerate([30, 18, 26, 9])]
    outs = {}
    for layout, n_pages in (("dense", 64), ("paged", 14)):
        eng = ServingEngine(cfg, params, EngineConfig(
            slots=3, cache_len=64, n_pages=n_pages, page_size=8,
            eos_token=-1, kv_layout=layout))
        for i, p in reqs:
            eng.submit(Request(i, p.copy(), max_new_tokens=12))
        done = eng.run_until_done()
        assert len(done) == len(reqs)
        # only prefix-cache-pinned blocks may remain; dropping them must
        # return the pool to fully free (refcounts balance)
        eng.prefix.clear()
        assert eng.pool.n_free == eng.pool.n_pages
        outs[layout] = {r.req_id: r.tokens_out for r in done}
    assert outs["paged"] == outs["dense"]


def test_paged_engine_parks_under_pressure(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(2)
    eng = ServingEngine(cfg, params, EngineConfig(
        slots=4, cache_len=64, n_pages=12, page_size=8, eos_token=-1,
        kv_layout="paged"))
    for i in range(5):
        p = rng.integers(1, cfg.vocab_size, size=int(rng.integers(16, 40)))
        eng.submit(Request(i, p.astype(np.int32), max_new_tokens=16))
    done = eng.run_until_done()
    assert len(done) == 5
    assert eng.stats["page_allocs"] > 0          # alloc-on-append happened
    assert eng.stats["pages_peak"] <= 12         # budget honored


def test_pages_peak_tracks_backend_internal_allocs(tiny):
    """`stats["pages_peak"]` mirrors PagePool.peak, the pool's OWN
    high-water mark: an alloc that spikes and reclaims entirely between
    engine observation points (here a third-party-style share_prefix +
    alloc-on-append + release against the backend directly) must still
    register. The old engine-side re-sampling under-reports this."""
    cfg, params = tiny
    ps = 8
    eng = ServingEngine(cfg, params, EngineConfig(
        slots=2, cache_len=96, n_pages=32, page_size=ps, eos_token=-1,
        kv_layout="paged"))
    # 2 full donated blocks + 1 tail token (the cache's leave-one-token
    # rule would otherwise hold back the last block)
    prompt = np.arange(1, 18, dtype=np.int32)
    eng.submit(Request(0, prompt.copy(), max_new_tokens=4))
    eng.run_until_done()
    engine_peak = eng.stats["pages_peak"]

    # backend-internal traffic the engine loop never samples: join the
    # cached prefix by reference, grow well past the engine-run peak,
    # then reclaim before the engine looks again
    matched, payloads = eng.prefix.match(prompt)
    assert matched == 16
    eng.state = eng.kv.share_prefix(eng.state, 0, 777, payloads, matched)
    assert eng.kv.append(777, matched + 10 * ps)  # +10 fresh pages
    true_peak = eng.pool.n_used
    assert true_peak > engine_peak
    eng.kv.release(777)                           # spike fully reclaimed
    assert eng.pool.n_used < true_peak

    eng.step()                                    # idle refresh of the mirror
    assert eng.pool.peak >= true_peak
    assert eng.stats["pages_peak"] == eng.pool.peak


def test_paged_no_host_tier_never_corrupts(tiny):
    """host_offload=False + dry pool: slots must stall in place or
    preempt-restart, never write through a zero page-table row into page
    0 (which another sequence owns). Outputs must still match dense."""
    cfg, params = tiny
    rng = np.random.default_rng(3)
    reqs = [(i, rng.integers(1, cfg.vocab_size, size=int(n)).astype(np.int32))
            for i, n in enumerate([20, 14, 18])]
    outs = {}
    for layout, n_pages, offload in (("dense", 64, True), ("paged", 9, False)):
        eng = ServingEngine(cfg, params, EngineConfig(
            slots=3, cache_len=64, n_pages=n_pages, page_size=8,
            eos_token=-1, kv_layout=layout, host_offload=offload))
        for i, p in reqs:
            eng.submit(Request(i, p.copy(), max_new_tokens=16))
        done = eng.run_until_done()
        assert len(done) == len(reqs)
        eng.prefix.clear()
        assert eng.pool.n_free == eng.pool.n_pages
        outs[layout] = {r.req_id: r.tokens_out for r in done}
    assert outs["paged"] == outs["dense"]


def test_overlong_prompt_rejected_at_submit(tiny):
    """A prompt with len+1 > cache_len can never scatter into max_pages
    pages (or fit a dense slab): submit must reject it up front."""
    cfg, params = tiny
    for layout in ("dense", "paged"):
        eng = ServingEngine(cfg, params, EngineConfig(
            slots=2, cache_len=64, n_pages=32, page_size=8, eos_token=-1,
            kv_layout=layout))
        with pytest.raises(ValueError):
            eng.submit(Request(0, np.arange(1, 65, dtype=np.int32)))
        # the boundary case (len+1 == cache_len) is fine
        eng.submit(Request(1, np.arange(1, 64, dtype=np.int32),
                           max_new_tokens=2))
        done = eng.run_until_done()
        assert len(done) == 1


def test_infeasible_footprint_rejected_at_submit(tiny):
    """A single request needing more pages than the whole pool would
    park/preempt-cycle forever: submit must fail fast instead."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params, EngineConfig(
        slots=1, cache_len=64, n_pages=4, page_size=8, eos_token=-1,
        kv_layout="paged"))
    with pytest.raises(ValueError):            # needs 48 tokens > 32 pool
        eng.submit(Request(0, np.arange(1, 17, dtype=np.int32),
                           max_new_tokens=32))
    eng.submit(Request(1, np.arange(1, 17, dtype=np.int32),
                       max_new_tokens=8))      # 24 tokens: fits
    assert len(eng.run_until_done()) == 1


def test_paged_state_rejects_non_attention():
    cfg = SMOKE_CONFIGS["rwkv6-1.6b"]
    with pytest.raises(ValueError):
        lm.init_paged_serve_state(cfg, 2, 16, 8, 4)


# ---------------------------------------------------------------------------
# paged_append semantics
# ---------------------------------------------------------------------------

def test_paged_append_drops_parked_writes():
    NP, ps, KV, hd, B = 4, 4, 2, 8, 3
    kp = jnp.zeros((NP, KV, ps, hd))
    vp = jnp.zeros((NP, KV, ps, hd))
    k_new = jnp.ones((B, KV, hd))
    v_new = 2 * jnp.ones((B, KV, hd))
    table = jnp.asarray([[1, 0], [2, 0], [3, 0]], jnp.int32)
    positions = jnp.asarray([0, 1, 2], jnp.int32)
    active = jnp.asarray([True, False, True])
    kp2, vp2 = paged_append(kp, vp, k_new, v_new, table, positions,
                            active=active)
    assert float(kp2[1, 0, 0, 0]) == 1.0     # slot 0 wrote page 1, off 0
    assert float(kp2[2, 0, 1, 0]) == 0.0     # slot 1 parked: dropped
    assert float(kp2[3, 1, 2, 0]) == 1.0     # slot 2 wrote page 3, off 2
    assert float(vp2[3, 1, 2, 0]) == 2.0     # every KV head of that row
    assert float(jnp.sum(jnp.abs(kp2))) == pytest.approx(
        2 * KV * hd)                          # nothing else touched


# ---------------------------------------------------------------------------
# PagePool wraparound / reuse
# ---------------------------------------------------------------------------

def test_page_pool_reuse_after_release():
    pool = PagePool(n_pages=6, page_size=4)
    a = pool.alloc(1, 3)
    b = pool.alloc(2, 3)
    assert pool.n_free == 0
    assert pool.alloc(3, 1) is None              # exhausted
    pool.release(1)
    c = pool.alloc(3, 3)
    assert sorted(c) == sorted(a)                # freed pages recycled
    assert set(c).isdisjoint(b)                  # never an owned page
    pool.release(2)
    pool.release(3)
    assert pool.n_free == 6
    # many alloc/release cycles never leak or duplicate
    for i in range(50):
        pages = pool.alloc(i, 1 + i % 6)
        assert pages is not None
        assert len(set(pages)) == len(pages)
        pool.release(i)
    assert pool.n_free == 6


def test_page_table_export():
    pool = PagePool(n_pages=8, page_size=4)
    pool.alloc(7, 2)
    pool.alloc(9, 3)
    m = pool.table_matrix([9, None, 7], max_pages=4)
    assert m.shape == (3, 4)
    assert list(m[0][:3]) == pool.pages_of(9)
    assert list(m[1]) == [0, 0, 0, 0]
    assert list(m[2][:2]) == pool.pages_of(7)
    assert m.dtype == np.int32


# ---------------------------------------------------------------------------
# MultiQueue ring wraparound at capacity boundaries
# ---------------------------------------------------------------------------

def test_host_multiqueue_slot_recycling():
    """Push/pop far beyond capacity: slots recycle, FIFO order holds."""
    mq = HostMultiQueue(2, capacity=4)
    model = {0: [], 1: []}
    seq = 0
    for round_ in range(40):
        q = round_ % 2
        while mq.push(q, seq):
            model[q].append(seq)
            seq += 1
        # drain the *other* queue fully, then one from this queue
        other = 1 - q
        got = mq.drain(other)
        assert got == model[other]
        model[other] = []
        item = mq.pop(q)
        if model[q]:
            assert item == model[q].pop(0)
    assert mq.free_slots + sum(mq.qlen(q) for q in (0, 1)) == 4


def test_mq_state_ring_wraparound():
    """Absolute head/tail counters cross the capacity boundary: the ring
    index (counter % capacity) must keep FIFO order and full/empty checks
    exact."""
    C = 4
    state = mq_init(1, C, (1,))
    q = jnp.int32(0)
    sent = 0
    popped = 0
    for cycle in range(5):                  # tail reaches 5*C > int ring
        for _ in range(C):
            state, ok = mq_push(state, q, jnp.asarray([float(sent)]))
            assert bool(ok)
            sent += 1
        state, ok = mq_push(state, q, jnp.asarray([99.0]))
        assert not bool(ok)                 # full: push rejected
        for _ in range(C):
            state, item, ok = mq_pop(state, q)
            assert bool(ok) and float(item[0]) == float(popped)
            popped += 1
        state, _, ok = mq_pop(state, q)
        assert not bool(ok)                 # empty: pop rejected
    assert int(state.tail[0]) == 5 * C      # counters are absolute
    assert int(state.head[0]) == 5 * C


def test_mq_state_partial_wrap():
    """Interleaved push/pop so head/tail straddle a capacity multiple."""
    C = 3
    state = mq_init(1, C, (1,))
    q = jnp.int32(0)
    expect = []
    nxt = 0.0
    for _ in range(2):
        state, ok = mq_push(state, q, jnp.asarray([nxt]))
        expect.append(nxt)
        nxt += 1
    for step in range(10):                  # net occupancy stays at 2
        state, ok = mq_push(state, q, jnp.asarray([nxt]))
        assert bool(ok)
        expect.append(nxt)
        nxt += 1
        state, item, ok = mq_pop(state, q)
        assert bool(ok) and float(item[0]) == expect.pop(0)
    assert [float(x) for x in np.asarray(
        [state.buf[0, int(state.head[0] + i) % C, 0]
         for i in range(2)])] == expect
