"""The benchmark's load generator: deterministic per seed, the same
work for every seed, the closed loop's staggered start, and the copied
arithmetic."""
import numpy as np
import pytest

from bench import loadgen

CHAT = loadgen.Traffic(
    arrival="poisson", rate=2.0, lead_seconds=3.0,
    prompt_lens=((0.6, 32, 256), (0.3, 256, 512), (0.1, 512, 768)),
    output_lens=((0.6, 16, 128), (0.3, 128, 256), (0.1, 256, 256)),
    temperature=0.7, top_p=0.9, greedy_every=4)


def _key(plan):
    return [(p.due, p.prompt.tobytes(), p.max_new, p.greedy) for p in plan]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**40 + 3])
def test_plan_is_deterministic_per_seed(seed):
    a = loadgen.plan(CHAT, seed, 10.0, 151936)
    b = loadgen.plan(CHAT, seed, 10.0, 151936)
    assert _key(a) == _key(b)


def test_large_seeds_do_not_collide():
    a = loadgen.plan(CHAT, 2**33, 10.0, 1000)
    b = loadgen.plan(CHAT, 0, 10.0, 1000)
    assert _key(a) != _key(b)


def test_stratified_seeds_share_the_work():
    plans = [loadgen.plan(CHAT, s, 10.0, 151936) for s in (1, 2, 3)]
    for p in plans:
        assert len(p) == round(2.0 * 3.0) + round(2.0 * 10.0)
        assert sum(x.due < 3.0 for x in p) == 6
        assert all(x.due < 13.0 for x in p)
    sizes = [sorted((len(x.prompt), x.max_new) for x in p) for p in plans]
    # the window's requests alone are the same multiset for every seed
    win = [sorted((len(x.prompt), x.max_new) for x in p if x.due >= 3.0)
           for p in plans]
    assert sorted(len(x.prompt) for x in plans[0] if x.due >= 3.0) == \
        sorted(len(x.prompt) for x in plans[1] if x.due >= 3.0)
    assert sorted(x.max_new for x in plans[0] if x.due >= 3.0) == \
        sorted(x.max_new for x in plans[2] if x.due >= 3.0)
    assert win[0] and win[1]
    assert sorted(len(x.prompt) for x in plans[0]) == \
        sorted(len(x.prompt) for x in plans[1])
    assert sorted(x.max_new for x in plans[0]) == \
        sorted(x.max_new for x in plans[2])
    # the order differs
    assert [len(x.prompt) for x in plans[0]] != [len(x.prompt)
                                                 for x in plans[1]]
    assert sizes[0] != [] and _key(plans[0]) != _key(plans[1])


def test_greedy_every_and_temperature():
    p = loadgen.plan(CHAT, 5, 10.0, 100)
    assert [x.greedy for x in p[:8]] == [True, False, False, False] * 2
    greedy = loadgen.Traffic(**{**CHAT.__dict__, "temperature": 0.0})
    assert all(x.greedy for x in loadgen.plan(greedy, 5, 10.0, 100))


def test_mixture_quantiles_cover_the_components():
    mix = ((0.6, 32, 256), (0.3, 256, 512), (0.1, 512, 768))
    vals = [loadgen.mixture_quantile(mix, (i + 0.5) / 1000)
            for i in range(1000)]
    assert min(vals) >= 32 and max(vals) <= 768
    assert all(v <= 256 for v in vals[:600])
    assert all(256 <= v <= 512 for v in vals[600:900])
    assert all(v >= 512 for v in vals[900:])
    assert vals == sorted(vals)
    assert loadgen.mixture_quantile(((1.0, 16, 16),), 0.999) == 16


def test_stratified_times_keep_the_rate():
    rng = np.random.default_rng(0)
    t = loadgen.stratified_times(rng, 4.0, 40, 10.0)
    assert len(t) == 40 and np.all(np.diff(t) > 0)
    assert 0 < t[0] and t[-1] < 10.0


def test_closed_loop_plan():
    t = loadgen.Traffic(arrival="closed", clients=12,
                        prompt_lens=((1.0, 64, 384),),
                        output_lens=((0.5, 384, 768), (0.5, 768, 1536)))
    p = loadgen.plan(t, 11, 30.0, 151936, n_closed=48)
    assert len(p) == 48 and all(np.isnan(x.due) for x in p)
    # every request keeps its drawn total: prompt + output
    for x in p:
        assert 64 <= len(x.prompt) - x.prior <= 384
        assert 384 <= x.max_new + x.prior <= 1536 and x.max_new >= 1
    # the later requests start from nothing; the 12 first ones are
    # staggered over the whole life of a request
    assert all(x.prior == 0 for x in p[12:])
    frac = sorted(x.prior / (x.prior + x.max_new) for x in p[:12])
    assert frac[0] < 1 / 12 and frac[-1] > 10 / 12
    assert all(abs(f - (i + 0.5) / 12) < 1 / 12 for i, f in enumerate(frac))
    # the first block and the rest are each half short, half long
    assert sum(x.prior + x.max_new < 768 for x in p[:12]) == 6
    assert sum(x.max_new < 768 for x in p[12:]) == 18


def test_stagger_is_the_same_work_for_every_seed():
    t = loadgen.Traffic(arrival="closed", clients=12,
                        prompt_lens=((1.0, 64, 384),),
                        output_lens=((0.5, 384, 768), (0.5, 768, 1536)))
    plans = [loadgen.plan(t, s, 30.0, 1000, n_closed=24) for s in (1, 2)]
    for part in (slice(0, 12), slice(12, 24)):
        for size in (lambda x: len(x.prompt) - x.prior,
                     lambda x: x.max_new + x.prior):
            a, b = (sorted(map(size, p[part])) for p in plans)
            assert a == b
    assert sorted(x.prior / (x.prior + x.max_new) // (1 / 12)
                  for x in plans[0][:12]) == list(range(12))
    fixed = loadgen.Traffic(**{**t.__dict__, "schedule_seed": 3})
    a, b = (loadgen.plan(fixed, s, 30.0, 1000, n_closed=24) for s in (1, 2))
    assert [(len(x.prompt), x.max_new, x.prior) for x in a] == \
        [(len(x.prompt), x.max_new, x.prior) for x in b]


def test_copied_poisson_and_bursty_arithmetic():
    rng = np.random.default_rng(3)
    t = loadgen.stratified_times(rng, 5.0, 2000, 400.0)
    assert abs(np.mean(np.diff(t)) - 0.2) < 0.02
    rng = np.random.default_rng(3)
    b = loadgen.bursty_times(rng, 5.0, 2000, burst=8.0)
    assert len(b) == 2000 and np.all(np.diff(b) >= 0)
    assert abs(2000 / b[-1] - 5.0) < 1.0
    with pytest.raises(ValueError):
        loadgen.plan(loadgen.Traffic(**{**CHAT.__dict__,
                                        "arrival": "uniform"}), 1, 1.0, 9)


def test_traffic_files_parse():
    import json
    from bench.spec import BENCH_DIR
    for f in sorted((BENCH_DIR / "traffic").glob("*.json")):
        t = loadgen.traffic_from_dict(json.loads(f.read_text()))
        assert t.arrival in ("poisson", "bursty", "closed")
        longest = max(hi for _, _, hi in t.prompt_lens)
        assert longest + loadgen.max_output(t) <= \
            json.loads(f.read_text())["engine"]["cache_len"]


def test_bursty_keeps_its_clumps():
    t = loadgen.Traffic(**{**CHAT.__dict__, "arrival": "bursty",
                           "burst": 8.0, "lead_seconds": 0.0})
    p = loadgen.plan(t, 3, 60.0, 100)
    gaps = np.diff([x.due for x in p])
    assert np.mean(gaps < 0.01) > 0.5          # most arrivals in clumps
    assert all(x.due < 60.0 for x in p)


def test_schedule_seed_fixes_sizes_and_times():
    t = loadgen.Traffic(**{**CHAT.__dict__, "schedule_seed": 5})
    a, b = (loadgen.plan(t, s, 10.0, 1000) for s in (1, 2**31 + 9))
    assert [(x.due, len(x.prompt), x.max_new) for x in a] == \
        [(x.due, len(x.prompt), x.max_new) for x in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
