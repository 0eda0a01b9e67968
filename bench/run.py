"""Serving benchmark: one cell of ``BENCHMARK.json`` on the chips of this
machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A cell names a model configuration (``bench/configs/<name>.json``) and a
traffic mix (``bench/traffic/<name>.json``). One run:

1. refuses to run without a TPU (exit 1, no result);
2. keeps JAX's persistent compilation cache at ``<checkout>/.jax_cache``;
3. makes the weights on the device from ``--seed``;
4. sizes the page pool from the compiled decode span's memory analysis;
5. warms every decode-span length and page-table width the cell can
   reach, and the prefill chunk, through the engine itself;
6. drives ``LocalFrontend`` over ``make_engine`` on the real clock for
   ``--seconds`` (open loop: requests due on a schedule, each timed from
   its due time; closed loop: clients that send again when served);
7. checks what the window served against the float32 reference in
   ``reference.py``, after the program's state is freed: the greedy
   requests' tokens by their gap below the reference's best, the
   sampled requests' tokens by their place in the reference's top-p
   nucleus at the request's temperature (``verdict``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device`` and
``checks`` (each compared number beside its limit), with ``--trace 1``
also ``breakdown``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import deque  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
# libtpu writes its logs under /tmp unless told otherwise; a run keeps to
# its checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import loadgen, spec  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
HEADROOM = 1 << 30            # HBM the decode span must leave free
PROBE_PAGES = 128             # any pool: the sizing replaces it
POOL_ALIGN = 32
DRAIN_WINDOWS = 1.0           # open loop: drain for at most one window
RAMP_CAP_S = 300.0            # closed loop: the clients' first tokens
GIB = float(1 << 30)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# compile accounting
# --------------------------------------------------------------------------

class CompileLog:
    """Backend compiles and persistent-cache hits, with their times,
    through ``jax.monitoring``."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.compiles: List[float] = []
        self.hits: List[float] = []
        self.compile_s = 0.0

    def on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append(self.clock())
            self.compile_s += secs

    def on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits.append(self.clock())

    def between(self, t0, t1) -> int:
        return sum(t0 <= t < t1 for t in self.compiles + self.hits)


# --------------------------------------------------------------------------
# set-up
# --------------------------------------------------------------------------

def enable_cache():
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def engine_config(cell: spec.Cell, n_pages: int):
    """The deployment the traffic file states; every other field keeps
    the program's default."""
    from repro.serve.api import EngineConfig
    return EngineConfig(n_pages=n_pages, **cell.traffic["engine"])


def span_need(cfg, ecfg, width: int, sharding=None) -> int:
    """Bytes the compiled decode span needs on the device (arguments +
    outputs - aliased + temporaries), at the full span and ``width``; more
    than any device holds where the compiler finds it does not fit."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    from repro.serve.api import make_sampler
    from repro.serve.engine import span_program
    from repro.sharding.policy import NULL_POLICY

    B = ecfg.slots
    sampler = make_sampler(ecfg.sampler)
    fn = span_program(cfg, NULL_POLICY, ecfg, sampler, ecfg.decode_span,
                      False)
    state = jax.eval_shape(lambda: lm.init_paged_serve_state(
        cfg, B, ecfg.n_pages, ecfg.page_size, width))
    vec = lambda dt: jax.ShapeDtypeStruct((B,), dt)  # noqa: E731
    sp = tuple(jax.ShapeDtypeStruct((B,), jnp.asarray(x).dtype)
               for x in sampler.slot_params(None))
    rng = ((vec(jnp.int32),) * 3) if sampler.needs_rng else None
    args = (lm.abstract_params(cfg), vec(jnp.int32), state, vec(jnp.bool_),
            vec(jnp.int32), sp, rng)
    if sharding is not None:
        args = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), args)
    try:
        ma = fn.lower(*args).compile().memory_analysis()
    except Exception as e:     # the compiler refuses a span that overflows
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        return 1 << 62
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def size_pool(cfg, cell, hbm_bytes: int, sharding=None) -> tuple:
    """The page pool: as many pages as every slot and one more can fill
    (the program's own default budget), or, where the decode span would
    then leave less than HEADROOM of the device free, the largest
    POOL_ALIGN multiple below that which leaves it. The span's need comes
    from compiled memory analyses, which grow unevenly with the pool, so
    the largest fit is found by bisection.
    Returns (n_pages, bytes the span needs)."""
    import dataclasses
    max_pages = cell.traffic["engine"]["cache_len"] // _page_size(cell)
    base = engine_config(cell, PROBE_PAGES)
    cap = -(-(base.slots + 1) * max_pages // POOL_ALIGN) * POOL_ALIGN

    def need(n):
        return span_need(cfg, dataclasses.replace(base, n_pages=n),
                         max_pages, sharding)

    def fits(n):
        return hbm_bytes - need(n) >= HEADROOM

    if fits(cap):
        return cap, need(cap)
    lo, hi = POOL_ALIGN, cap
    if not fits(lo):
        raise RuntimeError(f"no page pool fits: {POOL_ALIGN} pages need "
                           f"{need(lo) / GIB:.3f} GiB of "
                           f"{hbm_bytes / GIB:.3f}")
    while hi - lo > POOL_ALIGN:
        mid = (lo + hi) // 2 // POOL_ALIGN * POOL_ALIGN
        lo, hi = (mid, hi) if fits(mid) else (lo, mid)
    return lo, need(lo)


def _page_size(cell) -> int:
    from repro.serve.api import EngineConfig
    return cell.traffic["engine"].get("page_size", EngineConfig.page_size)


def warm_up(make, ecfg, traffic: loadgen.Traffic, vocab: int, plan):
    """Run, through a throw-away engine, what the window will compile:

    - one request per decode-span length (1, 2, 4, 8) at every page-table
      width the cell can reach; requests of one width share their
      prompt, so only the first prefills it whole;
    - one prefill-only request per class of prompt length in the plan
      (the number of pages before and after its first token), which
      gives the eager page-staging operations of chunked prefill every
      page count the window's prompts give them.

    Returns the number of requests served."""
    import jax
    from repro.serve.api import Request, SamplingParams
    eng = make()
    ps, span = ecfg.page_size, ecfg.decode_span
    max_pages = ecfg.cache_len // ps
    rng = np.random.default_rng(0)
    sp = SamplingParams(temperature=traffic.temperature, top_p=traffic.top_p)
    spans = [1 << k for k in range(span.bit_length()) if 1 << k <= span]
    rid, w = 0, 1
    with jax.profiler.TraceAnnotation("bench.warmup"):
        while True:
            w = min(w, max_pages)
            # w pages stay live through prefill and a whole span
            plen = max(1, min(ps * w - span - 4, ecfg.cache_len - span - 2))
            prompt = rng.integers(1, vocab, size=plen).astype(np.int32)
            for s in sorted(spans, reverse=True):
                eng.submit(Request(rid, prompt, max_new_tokens=s + 1,
                                   sampling=sp))
                eng.run_until_done()
                rid += 1
            if w >= max_pages:
                break
            w *= 2
        classes = {}
        for p in plan:
            n = len(p.prompt)
            classes.setdefault((-(-n // ps), -(-(n + 1) // ps)), n)
        for n in sorted(classes.values()):
            eng.submit(Request(rid, rng.integers(1, vocab, size=n).astype(
                np.int32), max_new_tokens=1, sampling=sp))
            rid += 1
        eng.run_until_done()
    del eng
    gc.collect()
    return rid


# --------------------------------------------------------------------------
# the window
# --------------------------------------------------------------------------

# Sampler faults that ``limits.py`` plants in the sampled requests: the
# program is handed the parameter that a sampler ignoring it would use,
# and serves those requests as that sampler would.
FAULTS = {"top_p_ignored": {"top_p": 1.0},
          "temperature_ignored": {"temperature": 1.0}}


@dataclass
class Rec:
    plan: loadgen.Planned
    due: float                      # absolute perf_counter time
    counted: bool                   # due inside the window
    fault: str = "sound"            # a key of FAULTS, for a sampled request
    req: object = None
    handle: object = None
    first_at: Optional[float] = None
    last_at: Optional[float] = None
    tokens: List[int] = field(default_factory=list)
    steps: List[int] = field(default_factory=list)  # step index per token

    @property
    def done(self):
        return self.handle is not None and self.handle.done

    @property
    def ok(self):
        return self.handle is not None and self.handle.ok


@dataclass
class Step:
    t0: float
    t1: float
    tokens: int
    stats: dict
    pages_used: int


class Driver:
    def __init__(self, fe, eng, traffic: loadgen.Traffic, seed: int,
                 faults=()):
        self.fe, self.eng, self.t = fe, eng, traffic
        # the sampled requests take turns: sound, then each fault
        self.labels = ("sound",) + tuple(faults)
        self.recs: List[Rec] = []
        self.steps: List[Step] = []
        self._delivered = 0
        self.late: List[float] = []
        self.seed_base = int(np.random.SeedSequence(int(seed))
                             .generate_state(1)[0] & 0x7FFFFFFF)

    def send(self, rec: Rec, now: float):
        import jax
        from repro.serve.api import Request, SamplingParams
        p, t = rec.plan, self.t
        kw = dict(temperature=0.0 if p.greedy else t.temperature,
                  top_p=t.top_p)
        if not p.greedy:
            rec.fault = self.labels[p.index % len(self.labels)]
            kw.update(FAULTS.get(rec.fault, {}))
        sp = SamplingParams(**kw,
                            seed=(self.seed_base + p.index) & 0x7FFFFFFF)
        rec.req = Request(p.index, p.prompt, max_new_tokens=p.max_new,
                          sampling=sp)

        def on_token(tok, idx, rec=rec):
            t_now = time.perf_counter()
            if rec.first_at is None:
                rec.first_at = t_now
            rec.last_at = t_now
            rec.tokens.append(int(tok))
            rec.steps.append(len(self.steps))
            self._delivered += 1

        self.late.append(now - rec.due)
        with jax.profiler.TraceAnnotation("bench.submit"):
            rec.handle = self.fe.submit(rec.req, on_token=on_token)
        self.recs.append(rec)

    def step(self):
        import jax
        n0 = self._delivered
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            self.fe.step()
        t1 = time.perf_counter()
        self.steps.append(Step(t0, t1, self._delivered - n0,
                               dict(self.eng.stats),
                               self.eng.pool.n_used))

    def wait(self, until: float):
        import jax
        with jax.profiler.TraceAnnotation("bench.wait_arrival"):
            dt = until - time.perf_counter()
            if dt > 0:
                time.sleep(min(dt, 0.002))


class Tracer:
    """The profiler around the traced window, started and stopped
    between engine steps so that every step in it is whole."""

    def __init__(self, on: bool):
        self.on, self.ann, self.t0, self.t1 = on, None, None, None
        self.step0 = self.step1 = None

    def start(self, n_steps: int):
        if not self.on or self.t0 is not None:
            return
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        self.ann = jax.profiler.TraceAnnotation("bench.window")
        self.ann.__enter__()
        self.t0, self.step0 = time.perf_counter(), n_steps

    def stop(self, n_steps: int) -> float:
        """Stop tracing (once); returns the seconds the profiler took to
        stop, in which the host drove no engine step."""
        if self.t0 is None or self.t1 is not None:
            return 0.0
        import jax
        self.t1, self.step1 = time.perf_counter(), n_steps
        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return time.perf_counter() - self.t1


def run_open(d: Driver, plan, lead: float, seconds: float, tracer: Tracer):
    base = time.perf_counter()
    w0, w1 = base + lead, base + lead + seconds
    pending = deque(Rec(p, base + p.due, p.due >= lead) for p in plan)
    counted: List[Rec] = [r for r in pending if r.counted]
    give_up = w1 + DRAIN_WINDOWS * seconds
    while True:
        now = time.perf_counter()
        if now >= w0:
            tracer.start(len(d.steps))
        if now >= w1:
            # the drain's time does not pay for writing the trace
            give_up += tracer.stop(len(d.steps))
        while pending and pending[0].due <= now:
            d.send(pending.popleft(), now)
        if now >= w1 and (all(r.done for r in counted) or now >= give_up):
            break
        if d.fe.live:
            d.step()
        elif pending:
            d.wait(pending[0].due)
        elif now < w1:
            d.wait(w1)
        else:
            break
    return w0, w1, now


def run_closed(d: Driver, plan, clients: int, seconds: float,
               tracer: Tracer):
    queue = deque(plan)
    t0 = time.perf_counter()
    live: List[Rec] = []
    for _ in range(clients):
        p = queue.popleft()
        live.append(Rec(p, t0, True))
        d.send(live[-1], t0)
    first = list(live)
    w0 = w1 = None
    while True:
        now = time.perf_counter()
        if w0 is None and all(r.first_at is not None for r in first):
            w0, w1 = now, now + seconds
            tracer.start(len(d.steps))
        if w0 is None and now - t0 > RAMP_CAP_S:
            raise RuntimeError("closed loop: the clients' first tokens did "
                               f"not all come within {RAMP_CAP_S} s")
        if w1 is not None and now >= w1:
            tracer.stop(len(d.steps))
            break
        for i, r in enumerate(live):
            if r.done and queue:
                p = queue.popleft()
                live[i] = Rec(p, now, True)
                d.send(live[i], now)
        if d.fe.live:
            d.step()
        elif w1 is not None:
            d.wait(w1)                 # every client's plan is used up
        else:
            raise RuntimeError("closed loop: nothing live before the window")
    return w0, w1, now


# --------------------------------------------------------------------------
# results
# --------------------------------------------------------------------------

def window_tokens(steps: List[Step], w0: float, w1: float) -> float:
    """Tokens delivered in [w0, w1): each step's tokens are spread evenly
    over the step that produced them, and the share of the step inside
    the window counts. This takes all the work and all the time of the
    window without rounding it to whole host syncs."""
    tot = 0.0
    for s in steps:
        if s.tokens == 0 or s.t1 <= w0 or s.t0 >= w1:
            continue
        dur = max(s.t1 - s.t0, 1e-9)
        tot += s.tokens * (min(s.t1, w1) - max(s.t0, w0)) / dur
    return tot


def latency(recs: List[Rec], give_up: float):
    """(ttft samples, tpot samples) in seconds, for the counted requests.
    A request never served counts its wait until the run gave up."""
    ttft, tpot = [], []
    for r in recs:
        if not r.counted:
            continue
        first = r.first_at if r.first_at is not None else give_up
        ttft.append(first - r.due)
        if r.first_at is not None and len(r.tokens) >= 2 and r.done:
            tpot.append((r.last_at - r.first_at) / (len(r.tokens) - 1))
    return ttft, tpot


def pick_check(recs: List[Rec], seed: int, want_tokens: int,
               max_requests: int, greedy: bool = True,
               fault: str = "sound") -> List[Rec]:
    """The requests whose tokens are checked: greedy ones (or sampled
    ones planted with ``fault``), with tokens served, the one with most
    tokens first, then a draw from the seed until ``want_tokens`` tokens
    or ``max_requests`` requests."""
    pool = [r for r in recs if r.plan.greedy == greedy and len(r.tokens) >= 1
            and (r.ok or not r.done) and (greedy or r.fault == fault)]
    if not pool:
        return []
    pool.sort(key=lambda r: (-len(r.tokens), r.plan.index))
    out, rest = [pool[0]], pool[1:]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed),
                                                        7 if greedy else 8]))
    order = rng.permutation(len(rest))
    n = len(pool[0].tokens)
    for i in order:
        if n >= want_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


# checked numbers that must reach their limit; every other one must stay
# at or below it
AT_LEAST = ("checked_tokens", "sampled_tokens")


def verdict(checks: dict) -> bool:
    """``correct``: every checked number within its limit."""
    return all((v["value"] >= v["limit"]) if k in AT_LEAST
               else (v["value"] <= v["limit"]) for k, v in checks.items())


def check(weights, conf, recs: List[Rec], traffic: dict, seed: int,
          cache_len: int, max_new: int, control: bool = False,
          fault: str = "sound", parts=("greedy", "sampled")) -> dict:
    """The numbers that decide ``correct``, before ``limited`` sets each
    beside its limit:

    - ``worst_gap``: the widest gap of a checked greedy token below the
      reference's best logit (with ``control``, of the control's first
      choice at the same positions), over ``checked_tokens`` tokens;
    - for sampled traffic, over ``sampled_tokens`` tokens of sampled
      requests (those planted with ``fault``): ``outside_nucleus``, the
      most by which a token's preceding reference mass passes ``top_p``
      (0 where every token lies inside the reference's nucleus), and
      ``pit_dev``, how far the mean of their integral transforms in the
      nucleus distribution lies from 1/2.

    ``parts`` names the halves to read."""
    from bench import reference
    chk = traffic.get("check", {})
    pad = (cache_len, max_new)
    out = {}
    if "greedy" in parts:
        out.update(worst_gap=0.0, checked_tokens=0)
        for r in pick_check(recs, seed, int(chk.get("tokens", 400)),
                            int(chk.get("max_requests", 8))):
            g = reference.gaps(weights, conf, r.plan.prompt, r.tokens,
                               control=control, pad_to=pad)
            out["worst_gap"] = max(out["worst_gap"], float(g.max()))
            out["checked_tokens"] += len(g)
    if "sampled" not in parts or traffic.get("temperature", 0.0) <= 0:
        return out
    excess, pit = [], []
    for r in pick_check(recs, seed, int(chk.get("sampled_tokens", 400)),
                        int(chk.get("sampled_requests", 8)), greedy=False,
                        fault=fault):
        e, u = reference.nucleus(weights, conf, r.plan.prompt, r.tokens,
                                 traffic["temperature"],
                                 traffic.get("top_p", 1.0), pad_to=pad)
        excess.append(e)
        pit.append(u)
    excess = np.concatenate(excess) if excess else np.zeros(0)
    pit = np.concatenate(pit) if pit else np.full(1, 0.5)
    out["outside_nucleus"] = float(max(excess.max(initial=0.0), 0.0))
    out["pit_dev"] = float(abs(pit.mean() - 0.5))
    out["sampled_tokens"] = int(len(excess))
    return out


def limited(readings: dict, traffic: dict) -> dict:
    """Each checked number beside its limit (``verdict``'s input)."""
    lim = dict(traffic["limits"], wrong_length=0, checked_tokens=1,
               sampled_tokens=1)
    return {k: {"value": v, "limit": lim[k]} for k, v in readings.items()}


@dataclass
class RunView:
    """What a per-layer metric reader is given."""
    conf: dict
    ecfg: object
    traffic: loadgen.Traffic
    recs: List[Rec]
    steps: List[Step]
    w0: float
    w1: float
    compiles_in_window: int
    peak: dict
    trace: Optional[dict] = None        # trace_reduce.load output
    trace_steps: tuple = (0, 0)         # [first, last) traced step index
    summary: Optional[dict] = None      # trace_reduce.summary output

    def window_steps(self) -> List[Step]:
        return [s for s in self.steps if s.t0 >= self.w0 and s.t1 <= self.w1]

    def traced_tokens(self):
        """(request, token index) of every token that a traced step
        delivered."""
        a, b = self.trace_steps
        for r in self.recs:
            for i, k in enumerate(r.steps):
                if a <= k < b:
                    yield r, i


def read_metrics(entries, view: RunView) -> dict:
    out = {}
    for m in entries:
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        v = mod.read(view)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# --------------------------------------------------------------------------
# main
# --------------------------------------------------------------------------

def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_tpu(chips: int):
    """The device list, or SystemExit(1) when it is not ``chips`` TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"bench: needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s) ({devs[0].device_kind})")
        raise SystemExit(1)
    return devs


@dataclass
class Served:
    """What one window served, and how it was timed."""
    recs: List[Rec]
    steps: List[Step]
    w0: float
    w1: float
    end: float
    counted: List[Rec]
    failed: int
    late: list
    compiles_in_window: int
    tracer: Tracer


class Session:
    """One process's set-up, shared by every window it serves: the
    compile cache and log, the model configuration and the page pool.
    ``devices``, ``peak`` and ``n_pages`` let a test drive it on the CPU;
    the command line always insists on a TPU and sizes the pool from the
    device's memory."""

    def __init__(self, cell: spec.Cell, traffic: loadgen.Traffic,
                 devices=None, peak=None, n_pages=None):
        import jax
        from bench import peaks as peaks_mod
        self.cell, self.traffic = cell, traffic
        self.n_pages = n_pages
        self.devices = devices if devices is not None else require_tpu(
            cell.chips)
        self.dev = self.devices[0]
        self.peak = peak or peaks_mod.peaks(self.dev.device_kind)
        log(f"device: {self.dev.device_kind} ({self.dev.platform}) "
            f"x{len(self.devices)}")
        enable_cache()
        self.clog = CompileLog()
        jax.monitoring.register_event_duration_secs_listener(
            self.clog.on_duration)
        jax.monitoring.register_event_listener(self.clog.on_event)
        self.conf = cell.config
        self.cfg = spec.model_config(self.conf)
        self.ecfg = None
        self.warm = False
        self.w = self.params = None

    def load(self, seed: int):
        """Weights from ``seed``; the pool is sized on the first call."""
        import jax
        from bench import weights as wmod
        self.w = self.params = None        # never two sets on the device
        gc.collect()
        self.w = wmod.make(self.conf, seed)
        self.params = wmod.to_program(self.conf, self.w)
        jax.block_until_ready(self.params)
        if self.ecfg is None:
            param_bytes = wmod.nbytes(self.w)
            n_pages, need = self.n_pages, 0
            if n_pages is None:
                hbm = (self.dev.memory_stats() or {}).get("bytes_limit")
                if not hbm:
                    raise RuntimeError(f"{self.dev.device_kind} reports no "
                                       "memory limit to size the pool by")
                n_pages, need = size_pool(self.cfg, self.cell, hbm)
            self.ecfg = engine_config(self.cell, n_pages)
            c = self.cfg
            log(f"model: {c.name} layers={c.n_layers} d={c.d_model} "
                f"heads={c.n_heads}x{c.head_dim} kv={c.n_kv_heads} "
                f"ff={c.d_ff} vocab={c.vocab_size}; weights "
                f"{param_bytes / GIB:.3f} GiB; pool {n_pages} pages x "
                f"{self.ecfg.page_size}; span needs {need / GIB:.3f} GiB")

    def make_engine(self):
        from repro.serve.api import make_engine
        return make_engine(self.cfg, self.params, self.ecfg)

    def serve(self, seed: int, seconds: float, trace: bool,
              faults=()) -> Served:
        """Warm up (first call), then one window over a fresh engine;
        ``faults`` (keys of FAULTS) are planted in the sampled requests,
        each in its turn with sound ones."""
        from repro.serve.api import make_frontend
        t = self.traffic
        closed = t.arrival == "closed"
        plan = loadgen.plan(t, seed, seconds, self.cfg.vocab_size,
                            n_closed=t.clients * 16 if closed else 0)
        if not self.warm:
            n = warm_up(self.make_engine, self.ecfg, t, self.cfg.vocab_size,
                        plan)
            self.warm = True
            log(f"warm-up: {n} requests")
        eng = self.make_engine()
        fe = make_frontend(self.ecfg.frontend, eng)
        d = Driver(fe, eng, t, seed, faults)
        tracer = Tracer(trace)
        self.setup_s = time.perf_counter() - T_START
        c = self.clog
        log(f"setup: {self.setup_s:.3f} s, {len(c.compiles)} compiles "
            f"({c.compile_s:.1f} s), {len(c.hits)} cache hits")
        if closed:
            w0, w1, end = run_closed(d, plan, t.clients, seconds, tracer)
        else:
            w0, w1, end = run_open(d, plan, t.lead_seconds, seconds, tracer)
        tracer.stop(len(d.steps))
        recs = d.recs
        if closed:
            counted = [r for r in recs
                       if r.first_at is None or r.first_at < w1]
            for r in counted:
                r.counted = True
            failed = sum(1 for r in counted if r.done and not r.ok)
        else:
            counted = [r for r in recs if r.counted]
            failed = sum(1 for r in counted if not r.ok)
        for r in recs:             # the hooks hold the front end and engine
            if r.req is not None:
                r.req.on_tokens = r.req.on_done = None
            if r.handle is not None:
                r.handle.on_token = None
        d.fe = d.eng = None
        return Served(recs, d.steps, w0, w1, end, counted, failed, d.late,
                      c.between(w0, w1), tracer)

    def free(self):
        """Drop the program's state (the page pool goes with the engine)
        so the reference has the memory; the weights stay."""
        self.params = None
        gc.collect()

    def check(self, served: Served, seed: int, control: bool = False,
              fault: str = "sound", parts=("greedy", "sampled")) -> dict:
        return check(self.w, self.conf, served.recs, self.cell.traffic,
                     seed, self.ecfg.cache_len,
                     loadgen.max_output(self.traffic), control=control,
                     fault=fault, parts=parts)


def main(argv=None, devices=None, peak=None, n_pages=None,
         control=False) -> dict:
    """One run. ``control`` puts the float8 control in the program's
    place in the greedy check, which ``correct`` must then refuse."""
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    traffic = loadgen.traffic_from_dict(cell.traffic)
    ses = Session(cell, traffic, devices, peak, n_pages)
    ses.load(args.seed)
    sv = ses.serve(args.seed, args.seconds, bool(args.trace))
    mem = (ses.dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    device = {"platform": ses.dev.platform, "kind": ses.dev.device_kind,
              "count": len(ses.devices), "memory_peak_bytes": int(mem)}

    view = RunView(conf=ses.conf, ecfg=ses.ecfg, traffic=traffic,
                   recs=sv.recs, steps=sv.steps, w0=sv.w0, w1=sv.w1,
                   compiles_in_window=sv.compiles_in_window, peak=ses.peak)
    breakdown = None
    if args.trace:
        from bench import trace_reduce
        paths = sorted(TRACE_DIR.rglob("*.xplane.pb"))
        view.trace = trace_reduce.load(str(paths[-1]))
        view.summary = trace_reduce.summary(view.trace)
        view.trace_steps = (sv.tracer.step0, sv.tracer.step1)
        device["busy_s"] = view.summary["busy_s"]
        device["window_s"] = view.summary["window_s"]
        breakdown = {"device_ops": view.summary["device_ops"],
                     "idle_gaps": view.summary["idle_gaps"]}

    win_tokens = window_tokens(sv.steps, sv.w0, sv.w1)
    ttft, tpot = latency(sv.recs, sv.end)
    e2e = {
        "ttft_p90_ms": (loadgen.quantile(ttft, 0.9) * 1e3) if ttft else None,
        "tpot_p90_ms": (loadgen.quantile(tpot, 0.9) * 1e3) if tpot else None,
        "output_tok_s": win_tokens / (sv.w1 - sv.w0),
        "setup_s": ses.setup_s,
    }
    late = np.asarray(sv.late) if sv.late else np.zeros(1)
    log(f"window: {sv.w1 - sv.w0:.3f} s, {len(sv.counted)} requests "
        f"attempted, {sv.failed} failed, {len(ttft)} ttft and {len(tpot)} "
        f"tpot samples, {win_tokens:.1f} tokens; generator late p50 "
        f"{np.median(late) * 1e3:.3f} ms max {late.max() * 1e3:.3f} ms; "
        f"{len(sv.steps)} engine steps; {sv.compiles_in_window} compiles "
        f"in window")
    if ttft:
        log(f"latency: ttft p50 {np.median(ttft) * 1e3:.1f} ms p90 "
            f"{loadgen.quantile(ttft, .9) * 1e3:.1f} ms; tpot p50 "
            f"{(np.median(tpot) * 1e3) if tpot else float('nan'):.2f} ms "
            f"p90 {e2e['tpot_p90_ms'] or float('nan'):.2f} ms")
    if args.trace:
        metrics = read_metrics(cell.per_layer, view)
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end if e2e.get(m["name"]) is not None}

    wrong_len = sum(1 for r in sv.counted if r.ok
                    and len(r.tokens) != r.plan.max_new)
    ses.free()
    t_chk = time.perf_counter()
    readings = ses.check(sv, args.seed, control=control)
    log(f"check: {readings['checked_tokens']} greedy and "
        f"{readings.get('sampled_tokens', 0)} sampled tokens against the "
        f"float32 reference in {time.perf_counter() - t_chk:.1f} s")
    checks = limited(dict(readings, wrong_length=wrong_len), cell.traffic)
    correct = verdict(checks)
    result = {"correct": bool(correct), "attempted": len(sv.counted),
              "failed": int(sv.failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, v in checks.items():
        log(f"{k}: {v['value']} (limit {v['limit']})")
    return result


if __name__ == "__main__":
    res = main()
    print(json.dumps(res), flush=True)
    os._exit(0)
