"""Smoke run of the served path on one TPU chip.

Serves qwen1.5-4b at its published widths (40 layers, d_model 2560, 20
MHA heads x 128, d_ff 6912, vocab 151936, bf16; random weights from a
seed) through ``make_engine`` with the paged KV layout, chunked prefill
and fused decode spans, and checks what comes out:

- the Pallas paged decode kernel against ``kernels/ref.py`` at the
  served shapes;
- every emitted token of a few requests against a teacher-forced
  model-level reference (``lm.prefill`` + ``lm.decode_step`` on dense
  caches, jnp attention, batch 1) fed the engine's own tokens;
- the compiled decode span holds the kernel (``tpu_custom_call``).

The page pool is sized from the compiled decode span's memory analysis
so that at least ``HEADROOM`` bytes of HBM stay free.

    python chip_smoke.py

Exits non-zero, printing no result, when JAX finds no TPU. The last line
of stdout is one JSON object naming the device. The times it prints are
those of a smoke run, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

MODEL = "qwen1.5-4b"
SLOTS = 8
CACHE_LEN = 1024
PAGE_SIZE = 16
PREFILL_CHUNK = 256          # a page multiple: prefill compiles once
DECODE_SPAN = 8
# prompt + MAX_NEW stays within 512 tokens (32 pages) and every prompt
# spans more than one chunk, so decode only ever sees a 32-wide page
# table; MAX_NEW = 1 prefill token + 7 full spans, so every span runs 8
PROMPT_LENS = (300, 300, 264, 440, 288, 392, 320, 352)
MAX_NEW = 57
N_REFERENCE = 2              # requests checked against the reference
HEADROOM = 1 << 30           # HBM the decode span must leave free
PROBE_PAGES = 128            # pool of the compile that prices a page
POOL_ALIGN = 32
# bf16 inputs, f32 accumulation: the kernel's output is rounded to bf16
# (relative step 2**-8), so 2e-2 on values of order 1
KERNEL_TOL = 2e-2
# teacher-forced check: each emitted token's reference logit must lie
# within this many logit units of that step's top reference logit. The
# random-weight logits have unit scale, so a wrong path (wrong pages,
# wrong positions) misses by several units on most steps
LOGIT_TOL = 0.25
GIB = float(1 << 30)


def _greedy_ecfg(*, slots, cache_len, page_size, n_pages,
                 prefill_chunk=0, decode_span=DECODE_SPAN):
    from repro.serve.api import EngineConfig
    return EngineConfig(slots=slots, cache_len=cache_len,
                        page_size=page_size, n_pages=n_pages,
                        prefill_chunk=prefill_chunk, decode_span=decode_span,
                        kv_layout="paged", sampler="greedy", eos_token=-1)


def span_memory(cfg, *, slots, cache_len, page_size, n_pages, width,
                decode_span=DECODE_SPAN, sharding=None):
    """Compile the engine's decode span (greedy, paged) for these sizes.

    Returns (bytes the program needs on the device, compiled). With
    ``sharding`` every argument is placed by it, which lets the span
    compile for a described device that is not attached.
    """
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    from repro.serve.api import make_sampler
    from repro.serve.engine import span_program
    from repro.sharding.policy import NULL_POLICY

    ecfg = _greedy_ecfg(slots=slots, cache_len=cache_len,
                        page_size=page_size, n_pages=n_pages,
                        decode_span=decode_span)
    fn = span_program(cfg, NULL_POLICY, ecfg, make_sampler("greedy"),
                      decode_span, False)
    state = jax.eval_shape(lambda: lm.init_paged_serve_state(
        cfg, slots, n_pages, page_size, width))
    vec = lambda dt: jax.ShapeDtypeStruct((slots,), dt)  # noqa: E731
    args = (lm.abstract_params(cfg), vec(jnp.int32), state, vec(jnp.bool_),
            vec(jnp.int32))
    if sharding is not None:
        args = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=sharding), args)
    compiled = fn.lower(*args, (), None).compile()
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    return int(need), compiled


def size_pool(cfg, *, hbm_bytes, slots, cache_len, page_size, width,
              decode_span=DECODE_SPAN, sharding=None):
    """The largest page pool (a POOL_ALIGN multiple) whose decode span
    leaves HEADROOM of ``hbm_bytes`` free, read from compiled memory
    analyses.

    A probe compile at PROBE_PAGES prices a page: every byte it needs
    beyond the parameters is charged to the pool. That overcharges the
    few pool-independent temporaries, so the estimate errs small. A
    second compile at the chosen size checks the headroom.
    Returns (n_pages, bytes that span needs, compiled span).
    """
    import jax
    from repro.models import lm
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(lm.abstract_params(cfg)))
    kw = dict(slots=slots, cache_len=cache_len, page_size=page_size,
              width=width, decode_span=decode_span, sharding=sharding)
    probe, _ = span_memory(cfg, n_pages=PROBE_PAGES, **kw)
    per_page = (probe - param_bytes) / PROBE_PAGES
    n_pages = int((hbm_bytes - HEADROOM - param_bytes) // per_page)
    n_pages -= n_pages % POOL_ALIGN
    if n_pages < POOL_ALIGN:
        raise RuntimeError(
            f"no page pool fits: parameters {param_bytes / GIB:.3f} GiB, "
            f"{per_page / 2**20:.2f} MiB per page, HBM "
            f"{hbm_bytes / GIB:.3f} GiB")
    need, compiled = span_memory(cfg, n_pages=n_pages, **kw)
    if hbm_bytes - need < HEADROOM:
        raise AssertionError(
            f"decode span at {n_pages} pages needs {need / GIB:.3f} GiB, "
            f"leaving less than {HEADROOM / GIB:.1f} GiB of "
            f"{hbm_bytes / GIB:.3f} GiB free")
    return n_pages, need, compiled


def check_kernel(*, slots, n_heads, n_kv_heads, head_dim, n_pages,
                 page_size, width, dtype, seed=0, tol=KERNEL_TOL) -> float:
    """The Pallas paged decode kernel against the ref.py oracle (f32 at
    highest matmul precision) on random pools of the served shapes.
    Returns the largest absolute difference; raises past ``tol``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    from repro.kernels.ref import paged_decode_attention_ref

    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    pool = (n_pages, n_kv_heads, page_size, head_dim)
    q = jax.random.normal(ks[0], (slots, n_heads, head_dim)).astype(dtype)
    kp = jax.random.normal(ks[1], pool).astype(dtype)
    vp = jax.random.normal(ks[2], pool).astype(dtype)
    table = jax.random.randint(ks[3], (slots, width), 0, n_pages)
    lengths = jax.random.randint(ks[4], (slots,), 1, width * page_size + 1)
    out = ops.paged_decode_attention(q, kp, vp, table, lengths)
    with jax.default_matmul_precision("highest"):
        ref = paged_decode_attention_ref(
            q.astype(jnp.float32), kp.astype(jnp.float32),
            vp.astype(jnp.float32), table, lengths)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    np.testing.assert_allclose(out, ref, atol=tol, rtol=tol)
    return float(np.abs(out - ref).max())


def teacher_forced_gaps(cfg, params, prompts, outputs, cache_len):
    """For each emitted token: the top reference logit minus the
    reference logit of that token (0 where the token is the reference's
    own argmax). The reference is batch-1 ``lm.prefill`` +
    ``lm.decode_step`` on dense caches, fed the emitted tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import lm
    from repro.sharding.policy import NULL_POLICY

    prefill = jax.jit(lambda p, t: lm.prefill(p, t, cfg, NULL_POLICY,
                                              cache_len=cache_len))
    step = jax.jit(lambda p, t, s: lm.decode_step(p, t, s, cfg,
                                                  NULL_POLICY))
    gaps = []
    for prompt, toks in zip(prompts, outputs):
        logits, state = prefill(params, jnp.asarray(prompt[None]))
        for i, tok in enumerate(toks):
            lg = np.asarray(logits[0], np.float32)
            gaps.append(float(lg.max() - lg[tok]))
            if i + 1 < len(toks):
                logits, state = step(params, jnp.asarray([tok], jnp.int32),
                                     state)
    return gaps


def serve_and_check(cfg, params, *, n_pages, slots, cache_len, page_size,
                    prefill_chunk, decode_span, prompt_lens, max_new,
                    n_reference, logit_tol=LOGIT_TOL, seed=0) -> dict:
    """Serve one request per prompt length through ``make_engine`` (paged,
    greedy, chunked prefill) and check the result: every request
    completes with ``max_new`` tokens, one host sync per prefill and per
    decode span, and the first ``n_reference`` requests' tokens agree
    with the teacher-forced reference within ``logit_tol``."""
    import numpy as np
    from repro.core.timing import Timer
    from repro.serve.api import Request, make_engine

    ecfg = _greedy_ecfg(slots=slots, cache_len=cache_len,
                        page_size=page_size, n_pages=n_pages,
                        prefill_chunk=prefill_chunk, decode_span=decode_span)
    eng = make_engine(cfg, params, ecfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in prompt_lens]
    for i, p in enumerate(prompts):
        eng.submit(Request(i, p, max_new_tokens=max_new))
    timer = Timer()
    done = eng.run_until_done()
    serve_s = timer.elapsed()
    outs = {r.req_id: list(r.tokens_out) for r in done}
    if sorted(outs) != list(range(len(prompts))):
        raise AssertionError(f"completed {sorted(outs)} of {len(prompts)}")
    short = {i: len(t) for i, t in outs.items() if len(t) != max_new}
    if short:
        raise AssertionError(f"token counts {short}, wanted {max_new}")
    stats = dict(eng.stats)
    if stats["host_syncs"] != stats["prefills"] + stats["decode_spans"]:
        raise AssertionError(f"host_syncs {stats['host_syncs']} != "
                             f"prefills {stats['prefills']} + decode_spans "
                             f"{stats['decode_spans']}")
    del eng                                  # its page pool leaves HBM
    gaps = teacher_forced_gaps(cfg, params, prompts[:n_reference],
                               [outs[i] for i in range(n_reference)],
                               cache_len)
    if max(gaps) > logit_tol:
        raise AssertionError(
            f"emitted token {max(gaps):.4f} logits below the reference "
            f"top (tolerance {logit_tol})")
    return {"serve_s": serve_s, "stats": stats,
            "tokens": sum(len(t) for t in outs.values()),
            "n_checked": len(gaps), "worst_gap": max(gaps),
            "argmax_agree": sum(g == 0.0 for g in gaps)}


class _CompileLog:
    """Counts backend compiles (persistent-cache hits included) and their
    seconds through jax.monitoring."""

    def __init__(self):
        self.n, self.secs, self.cache_hits = 0, 0.0, 0

    def on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, prompts and kernel inputs")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU; JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1

    import jax.numpy as jnp
    from repro.configs.registry import CONFIGS
    from repro.core.timing import Timer
    from repro.kernels.paged_attention import live_table_width
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import lm

    total = Timer()
    log = _CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log.on_duration)
    jax.monitoring.register_event_listener(log.on_event)
    cache_dir = enable_compile_cache()
    n_dev = len(jax.devices())
    print(f"device: {dev.device_kind} ({dev.platform}) x{n_dev}")
    print(f"compile cache: {cache_dir}")

    cfg = CONFIGS[MODEL]
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}x{cfg.head_dim} kv_heads={cfg.n_kv_heads} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.dtype}")
    # eager, as the launchers do: a jitted init is one 280-op program
    # that takes minutes to compile, for the same values
    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"params: {param_bytes / GIB:.3f} GiB")

    hbm = dev.memory_stats()["bytes_limit"]
    max_pages = CACHE_LEN // PAGE_SIZE
    width = live_table_width(
        -(-(max(PROMPT_LENS) + MAX_NEW) // PAGE_SIZE), max_pages)
    n_pages, need, compiled = size_pool(
        cfg, hbm_bytes=hbm, slots=SLOTS, cache_len=CACHE_LEN,
        page_size=PAGE_SIZE, width=width)
    pool_bytes = (2 * cfg.n_layers * n_pages * cfg.n_kv_heads * PAGE_SIZE
                  * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize)
    print(f"pool: {n_pages} pages x {PAGE_SIZE} tokens, "
          f"{pool_bytes / GIB:.3f} GiB")
    print(f"decode span (x{DECODE_SPAN}, table width {width}): needs "
          f"{need / GIB:.3f} GiB of {hbm / GIB:.3f} GiB, "
          f"{(hbm - need) / GIB:.3f} GiB free")
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError("decode span HLO has no tpu_custom_call: the "
                             "Pallas paged kernel was bypassed")
    print("decode span HLO: tpu_custom_call present")
    del compiled

    err = check_kernel(slots=SLOTS, n_heads=cfg.n_heads,
                       n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                       n_pages=n_pages, page_size=PAGE_SIZE, width=width,
                       dtype=jnp.dtype(cfg.dtype), seed=args.seed)
    print(f"kernel check: max |pallas - ref| = {err:.6f} "
          f"(tolerance {KERNEL_TOL})")

    res = serve_and_check(
        cfg, params, n_pages=n_pages, slots=SLOTS, cache_len=CACHE_LEN,
        page_size=PAGE_SIZE, prefill_chunk=PREFILL_CHUNK,
        decode_span=DECODE_SPAN, prompt_lens=PROMPT_LENS, max_new=MAX_NEW,
        n_reference=N_REFERENCE, seed=args.seed)
    st = res["stats"]
    print(f"served: {len(PROMPT_LENS)}/{len(PROMPT_LENS)} requests, "
          f"{res['tokens']} tokens generated, {st['decode_spans']} decode "
          f"spans, {st['prefill_chunks']} prefill chunks, pages peak "
          f"{st['pages_peak']}")
    print(f"host_syncs == prefills + decode_spans: {st['host_syncs']} == "
          f"{st['prefills']} + {st['decode_spans']}")
    print(f"reference check: {N_REFERENCE} requests, {res['n_checked']} "
          f"tokens, worst logit gap {res['worst_gap']:.4f} (tolerance "
          f"{LOGIT_TOL}), argmax agreement "
          f"{res['argmax_agree']}/{res['n_checked']}")
    print(f"compiles: {log.n} ({log.secs:.1f} s), persistent cache hits "
          f"{log.cache_hits}")
    print(f"peak_bytes_in_use: {dev.memory_stats()['peak_bytes_in_use']}")
    print(f"wall (smoke, not a benchmark): serving {res['serve_s']:.1f} s, "
          f"whole run {total.elapsed():.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
