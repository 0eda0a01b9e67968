"""Serving front end (DESIGN.md §3.8): continuous arrivals on a virtual
clock, per-token streaming byte-identity, SLO-graded admission."""
import jax
import numpy as np
import pytest

from repro.configs.registry import SMOKE_CONFIGS
from repro.models import lm
from repro.serve.api import (EngineConfig, Request, make_engine,
                             make_frontend, register_frontend)
from repro.serve.frontend import LocalFrontend, VirtualClock
from repro.serve.loadgen import TraceSpec, make_trace


@pytest.fixture(scope="module")
def tiny():
    cfg = SMOKE_CONFIGS["qwen3-8b"].scaled(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=256)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _stack(cfg, params, step_dt=1.0, **kw):
    clock = VirtualClock()
    kw.setdefault("slots", 3)
    kw.setdefault("cache_len", 96)
    kw.setdefault("n_pages", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("eos_token", -1)
    eng = make_engine(cfg, params, EngineConfig(clock=clock, **kw))
    fe = make_frontend("local", eng, step_dt=step_dt)
    return clock, eng, fe


def _prompts(cfg, n, lo=6, hi=20, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size,
                         size=int(rng.integers(lo, hi))).astype(np.int32)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# streaming determinism (satellite): callback stream byte-identical to
# tokens_out across decode spans, KV layouts, and prefill modes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["dense", "paged"])
@pytest.mark.parametrize("span,chunk", [(1, 0), (8, 0), (8, 8)])
def test_stream_matches_tokens_out(tiny, layout, span, chunk):
    cfg, params = tiny
    _, eng, fe = _stack(cfg, params, kv_layout=layout, decode_span=span,
                        prefill_chunk=chunk)
    got = {}
    handles = [fe.submit(Request(i, p, max_new_tokens=6),
                         on_token=lambda t, k, i=i:
                         got.setdefault(i, []).append(t))
               for i, p in enumerate(_prompts(cfg, 4))]
    fe.run()
    assert all(h.ok for h in handles)
    for h in handles:
        assert h.streamed == h.req.tokens_out          # byte-identical
        assert got[h.req.req_id] == h.req.tokens_out   # user callback too
        assert len(h.streamed) == 6


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_stream_invariant_to_span_and_chunking(tiny, layout):
    """The streamed sequence itself is the same whether tokens arrived
    one per sync or eight per sync, under monolithic and under chunked
    prefill. Tokens are compared only across bit-identical variations:
    chunked and monolithic prefill agree on logits to the tolerance
    tests/test_chunked_prefill.py pins, not bit for bit, so a near-tie
    may rightly pick a different greedy token between the two modes."""
    cfg, params = tiny
    streams = {}
    for span, chunk in ((1, 0), (8, 0), (1, 8), (8, 8)):
        _, eng, fe = _stack(cfg, params, kv_layout=layout,
                            decode_span=span, prefill_chunk=chunk)
        hs = [fe.submit(Request(i, p, max_new_tokens=6))
              for i, p in enumerate(_prompts(cfg, 3))]
        fe.run()
        streams[(span, chunk)] = [h.streamed for h in hs]
    assert streams[(1, 0)] == streams[(8, 0)]
    assert streams[(1, 8)] == streams[(8, 8)]


def test_stream_survives_park_unpark_midstream(tiny):
    """A park/unpark cycle in the middle of a stream neither drops,
    duplicates, nor reorders client tokens."""
    cfg, params = tiny
    prompt = np.arange(1, 12, dtype=np.int32)

    _, _, ref_fe = _stack(cfg, params, decode_span=1)
    ref = ref_fe.submit(Request(0, prompt, max_new_tokens=6))
    ref_fe.run()

    _, eng, fe = _stack(cfg, params, decode_span=1)
    h = fe.submit(Request(0, prompt, max_new_tokens=6))
    fe.step()                      # admit + first token
    assert eng._evict_someone(exclude=-1)   # force a park mid-stream
    assert eng.stats["parked"] == 1
    fe.run()
    assert eng.stats["unparked"] == 1
    assert h.ok and h.streamed == h.req.tokens_out == ref.streamed


def test_stream_survives_preempt_restart(tiny):
    """Preempt-restart replays the whole stream from index 0; the handle
    dedupes, so the client stream stays exact."""
    cfg, params = tiny
    _, eng, fe = _stack(cfg, params, decode_span=1)
    h = fe.submit(Request(0, np.arange(1, 10, dtype=np.int32),
                          max_new_tokens=5))
    fe.step()
    seen_before = list(h.streamed)
    assert seen_before                       # at least the prefill token
    eng._preempt_restart(int(np.nonzero(eng.active)[0][0]))
    fe.run()
    assert h.ok
    assert h.streamed == h.req.tokens_out
    assert h.streamed[:len(seen_before)] == seen_before
    assert eng.stats["preempt_restarts"] == 1


def test_streaming_adds_zero_host_syncs(tiny):
    cfg, params = tiny
    _, eng, fe = _stack(cfg, params, decode_span=8)
    hs = [fe.submit(Request(i, p, max_new_tokens=9),
                    on_token=lambda t, k: None)
          for i, p in enumerate(_prompts(cfg, 4))]
    fe.run()
    assert all(h.ok for h in hs)
    assert (eng.stats["host_syncs"]
            == eng.stats["prefills"] + eng.stats["decode_spans"])


# ---------------------------------------------------------------------------
# continuous arrivals + injected clock
# ---------------------------------------------------------------------------

def test_submit_while_engine_is_running(tiny):
    cfg, params = tiny
    _, eng, fe = _stack(cfg, params, decode_span=1)
    h0 = fe.submit(Request(0, np.arange(1, 20, dtype=np.int32),
                           max_new_tokens=8))
    for _ in range(3):
        fe.step()                  # engine mid-flight
    assert not h0.done
    h1 = fe.submit(Request(1, np.arange(1, 9, dtype=np.int32),
                           max_new_tokens=4))
    fe.run()
    assert h0.ok and h1.ok
    assert h0.streamed == h0.req.tokens_out
    assert h1.streamed == h1.req.tokens_out


def test_virtual_clock_replays_identically(tiny):
    """Same trace, fresh stacks: outcomes, streams, arrival stamps and
    timing metrics are bit-identical — no wall-clock leaks anywhere on
    the arrival/eviction/SLO path."""
    cfg, params = tiny
    spec = TraceSpec(arrival="bursty", rate=0.7, burst=3.0,
                     qos_weights=(1, 1), seed=3,
                     prompt_lens=((1.0, 6, 14),),
                     output_lens=((1.0, 3, 7),))

    def one_run():
        _, eng, fe = _stack(cfg, params, scheduler="priority",
                            qos_classes=2, admit_capacity=4,
                            slo_ttft=(0.0, 6.0))
        hs = fe.run(make_trace(spec, 10, cfg.vocab_size))
        return [(h.req.req_id, h.outcome, tuple(h.streamed),
                 h.req.arrived_at, h.submitted_at, h.first_token_at,
                 h.finished_at) for h in hs]

    assert one_run() == one_run()


def test_engine_submit_stamps_injected_clock(tiny):
    cfg, params = tiny
    clock, eng, _ = _stack(cfg, params)
    clock.advance(41.5)
    r = Request(0, np.arange(1, 8, dtype=np.int32), max_new_tokens=2)
    eng.submit(r)
    assert r.arrived_at == clock()           # not wall-clock time
    eng.run_until_done()
    assert r.finished_at >= 41.5


# ---------------------------------------------------------------------------
# SLO-graded admission control (satellite: invariants under overload)
# ---------------------------------------------------------------------------

def _flood(fe, cfg, classes, max_new=6, seed=1):
    """Submit one burst of requests (classes[i] -> request i) at t=0."""
    return [fe.submit(Request(i, p, max_new_tokens=max_new, qos=c))
            for i, (p, c) in enumerate(
                zip(_prompts(cfg, len(classes), seed=seed), classes))]


def test_overload_sheds_only_lower_classes(tiny):
    cfg, params = tiny
    _, eng, fe = _stack(cfg, params, slots=1, decode_span=1,
                        scheduler="priority", qos_classes=3,
                        admit_capacity=3, feed_depth=1)
    classes = [2, 2, 2, 1, 2, 0, 1, 2, 0, 2, 1, 0]
    hs = _flood(fe, cfg, classes)
    fe.run()
    # every request reached an explicit terminal outcome — no silent drops
    outcomes = [h.outcome for h in hs]
    assert all(o in ("completed", "rejected", "shed") for o in outcomes)
    assert (fe.stats["completed"] + fe.stats["rejected"]
            + fe.stats["shed_capacity"] + fe.stats["shed_slo"]
            == len(hs))
    # overload really happened and the knife only ever cut downward:
    # every capacity shed displaced a strictly lower class than the
    # arrival that triggered it, and the top class was never shed
    drops = [e for e in fe.shed_log if e["reason"] == "capacity"]
    assert drops, "expected capacity shedding under this overload"
    assert all(e["qos"] > e["trigger_qos"] for e in drops)
    assert all(h.ok for h in hs if h.req.qos == 0)
    for h in hs:
        if h.outcome == "shed":
            assert h.req.req_id not in [r.req_id for r in eng.completed]


def test_arrival_rejected_when_every_waiter_outranks_it(tiny):
    cfg, params = tiny
    _, eng, fe = _stack(cfg, params, slots=1, decode_span=1,
                        scheduler="priority", qos_classes=2,
                        admit_capacity=2, feed_depth=1)
    _flood(fe, cfg, [0, 0, 0])     # 1 fed + 2 waiting class-0 (pool full)
    low = fe.submit(Request(9, np.arange(1, 8, dtype=np.int32),
                            max_new_tokens=4, qos=1))
    assert low.outcome == "rejected"         # nobody below it to displace
    same = fe.submit(Request(10, np.arange(1, 8, dtype=np.int32),
                             max_new_tokens=4, qos=0))
    assert same.outcome == "rejected"        # ties never displace, either
    fe.run()
    assert fe.stats["rejected"] == 2


def test_high_class_displaces_newest_low_waiter(tiny):
    cfg, params = tiny
    _, eng, fe = _stack(cfg, params, slots=1, decode_span=1,
                        scheduler="priority", qos_classes=2,
                        admit_capacity=2, feed_depth=1)
    hs = _flood(fe, cfg, [1, 1, 1])          # 1 fed + 2 waiting class-1
    hi = fe.submit(Request(9, np.arange(1, 8, dtype=np.int32),
                           max_new_tokens=4, qos=0))
    assert hs[2].outcome == "shed"           # newest low waiter tail-drops
    assert hs[1].outcome is None             # older one keeps its place
    fe.run()
    assert hi.ok


def test_slo_ttft_expiry_is_explicit(tiny):
    cfg, params = tiny
    _, eng, fe = _stack(cfg, params, slots=1, decode_span=1,
                        scheduler="priority", qos_classes=2,
                        admit_capacity=16, feed_depth=1,
                        slo_ttft=(0.0, 2.0))
    hs = _flood(fe, cfg, [0, 1, 1, 1, 1], max_new=8)
    fe.run()
    shed = [h for h in hs if h.outcome == "shed"]
    assert shed and all(h.req.qos == 1 for h in shed)
    assert all(h.reason.startswith("slo-ttft") for h in shed)
    assert all(h.ok for h in hs if h.req.qos == 0)
    assert fe.stats["shed_slo"] == len(shed)


def test_degrade_caps_low_class_output(tiny):
    cfg, params = tiny
    _, eng, fe = _stack(cfg, params, slots=1, decode_span=1,
                        scheduler="priority", qos_classes=2,
                        admit_capacity=8, feed_depth=1,
                        degrade_max_new=2)
    hs = _flood(fe, cfg, [0, 1, 1, 1, 1, 1], max_new=8)
    fe.run()
    degraded = [h for h in hs if h.degraded]
    assert degraded and all(h.req.qos == 1 for h in degraded)
    assert all(h.ok and len(h.streamed) <= 2 for h in degraded)
    assert fe.stats["degraded"] == len(degraded)
    # the top class is never degraded
    assert all(not h.degraded and len(h.streamed) == 8
               for h in hs if h.req.qos == 0)


def test_handle_slo_metrics(tiny):
    cfg, params = tiny
    _, eng, fe = _stack(cfg, params, decode_span=1)
    h = fe.submit(Request(0, np.arange(1, 10, dtype=np.int32),
                          max_new_tokens=4))
    fe.run()
    assert h.ok and h.ttft is not None and h.tpot is not None
    assert h.ttft >= 0 and h.tpot > 0
    assert h.meets_slo()                                  # no budgets
    assert h.meets_slo(slo_ttft=(1e9,), slo_tpot=(1e9,))
    assert not h.meets_slo(slo_ttft=(1e-9,))


# ---------------------------------------------------------------------------
# registry: a third-party frontend plugs in by name
# ---------------------------------------------------------------------------

def test_third_party_frontend_registry(tiny):
    cfg, params = tiny

    @register_frontend("test_logging")
    class LoggingFrontend(LocalFrontend):
        def submit(self, req, on_token=None):
            self.log = getattr(self, "log", []) + [req.req_id]
            return super().submit(req, on_token)

    clock = VirtualClock()
    eng = make_engine(cfg, params, EngineConfig(
        slots=2, cache_len=64, n_pages=32, page_size=8, eos_token=-1,
        clock=clock))
    fe = make_frontend("test_logging", eng, step_dt=1.0)
    hs = [fe.submit(Request(i, np.arange(1, 8, dtype=np.int32),
                            max_new_tokens=3)) for i in range(2)]
    fe.run()
    assert fe.log == [0, 1] and all(h.ok for h in hs)
