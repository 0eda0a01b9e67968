"""The harness end to end on the CPU at a tiny size: a sound run is
correct; a run whose tokens are altered where they are produced, whose
sampler ignores top_p or the temperature, or whose greedy tokens are
judged by the float8 control is not; and the command line refuses a
machine without a TPU."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import reference, run, spec, weights

TINY = {"name": "tiny", "source": "test", "model_type": "qwen3",
        "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
        "torch_dtype": "bfloat16", "attention_bias": True, "qk_norm": True,
        "hidden_act": "silu"}
# The limits are this tiny model's own, set from its readings on the CPU
# (seeds 2**31 + 77, 5 and 99): worst_gap 0-0.008 for the program and
# 0.042-0.144 for the float8 control; outside_nucleus 0 sound and
# 0.055-0.098 with top_p or the temperature ignored; pit_dev 0.004-0.018
# sound and 0.052-0.150 with either fault.
TRAFFIC = {"arrival": "poisson", "rate": 6.0, "clients": 3,
           "lead_seconds": 0.3, "prompt_lens": [[1.0, 8, 16]],
           "output_lens": [[1.0, 24, 48]], "temperature": 0.7, "top_p": 0.9,
           "greedy_every": 2,
           "engine": {"slots": 4, "cache_len": 64, "prefill_chunk": 16,
                      "kv_layout": "paged", "sampler": "stochastic",
                      "eos_token": -1},
           "check": {"tokens": 40, "max_requests": 4,
                     "sampled_tokens": 200, "sampled_requests": 12},
           "limits": {"worst_gap": 0.03, "outside_nucleus": 0.01,
                      "pit_dev": 0.04}}
PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def _run(monkeypatch, traffic=TRAFFIC, seed=2**31 + 77, trace=0,
         control=False):
    bench = spec.load_benchmark()
    cell = spec.Cell("tiny.chat", 1, "tiny", TINY, "tiny", traffic,
                     list(bench["end_to_end"]), list(bench["per_layer"]))
    monkeypatch.setattr(spec, "load_cell", lambda *a, **k: cell)
    # the persistent cache is for the chip; keep the test process's
    # compiles to itself
    monkeypatch.setattr(run, "enable_cache", lambda: None)
    return run.main(["--workload", "tiny.chat", "--seed", str(seed),
                     "--seconds", "1.0", "--trace", str(trace)],
                    devices=jax.devices(), peak=PEAK, n_pages=32,
                    control=control)


def test_sound_run_is_correct(monkeypatch):
    res = _run(monkeypatch)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 4 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms",
                                   "output_tok_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["checked_tokens"]["value"] >= 10
    assert res["checks"]["sampled_tokens"]["value"] >= 20
    assert {"outside_nucleus", "pit_dev"} <= set(res["checks"])


def test_closed_loop_run_is_correct(monkeypatch):
    res = _run(monkeypatch, {**TRAFFIC, "arrival": "closed"}, trace=1)
    assert res["correct"], res["checks"]
    assert "batch_occupancy" in res["metrics"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_trace_stop_is_not_a_failure(monkeypatch):
    """Writing the trace at the window's close stalls the host for longer
    than the drain allows; the requests still in flight are served after
    it, not failed."""
    import time
    stop = jax.profiler.stop_trace

    def slow_stop():
        stop()
        time.sleep(2.5)

    monkeypatch.setattr(jax.profiler, "stop_trace", slow_stop)
    res = _run(monkeypatch, {**TRAFFIC, "rate": 12.0}, trace=1)
    assert res["correct"] and res["failed"] == 0, res


def test_altered_token_is_caught(monkeypatch):
    from repro.serve import engine as eng_mod
    emit = eng_mod.ServingEngine._emit

    def altered(self, req, toks, lps=None):
        toks = list(toks)
        k = 2 - len(req.tokens_out)          # alter each stream's 3rd token
        if 0 <= k < len(toks):
            toks[k] = (toks[k] + 1) % self.cfg.vocab_size
        return emit(self, req, toks, lps)

    monkeypatch.setattr(eng_mod.ServingEngine, "_emit", altered)
    res = _run(monkeypatch)
    assert not res["correct"]
    assert res["checks"]["worst_gap"]["value"] > 0.25


def _ignoring(param):
    """The stochastic sampler with ``param`` dropped where it samples."""
    from repro.kernels import sampling

    def sample(self, logits, keys, params):
        temperature, top_k, top_p = params
        if param == "top_p":
            top_p = jax.numpy.ones_like(top_p)
        else:
            temperature = jax.numpy.where(temperature > 0, 1.0, temperature)
        return sampling.sample_logits(logits, keys, temperature, top_k,
                                      top_p)
    return sample


@pytest.mark.parametrize("param", ["top_p", "temperature"])
def test_sampler_fault_is_caught(monkeypatch, param):
    from repro.serve import samplers
    monkeypatch.setattr(samplers.StochasticSampler, "sample",
                        _ignoring(param))
    res = _run(monkeypatch)
    assert not res["correct"]
    c = res["checks"]
    assert (c["outside_nucleus"]["value"] > c["outside_nucleus"]["limit"]
            or c["pit_dev"]["value"] > c["pit_dev"]["limit"]), c
    assert c["worst_gap"]["value"] <= c["worst_gap"]["limit"]


def test_control_is_not_correct(monkeypatch):
    """The float8 control, put in the program's place in the greedy
    check, comes out of the harness's own verdict as not correct."""
    res = _run(monkeypatch, control=True)
    assert not res["correct"], res["checks"]
    c = res["checks"]["worst_gap"]
    assert c["value"] > c["limit"]


def test_control_reads_above_the_program():
    """The float8 control, on the tokens the bf16 model itself picks,
    reads a wider worst gap than the program's own tokens do."""
    from repro.models import lm
    from repro.sharding.policy import NULL_POLICY
    conf = {**TINY, "num_hidden_layers": 4}
    cfg = spec.model_config(conf)
    w = weights.make(conf, 3)
    params = weights.to_program(conf, w)
    rng = np.random.default_rng(0)
    prefill = jax.jit(lambda p, t: lm.prefill(p, t, cfg, NULL_POLICY,
                                              cache_len=72))
    step = jax.jit(lambda p, t, s: lm.decode_step(p, t, s, cfg,
                                                  NULL_POLICY))
    prog, ctrl = [], []
    for _ in range(4):
        prompt = rng.integers(1, 512, size=48).astype(np.int32)
        logits, state = prefill(params, jax.numpy.asarray(prompt[None]))
        toks = []
        for _ in range(24):                  # the bf16 model's greedy tokens
            toks.append(int(np.argmax(np.asarray(logits[0], np.float32))))
            logits, state = step(params, jax.numpy.asarray(toks[-1:]), state)
        prog.append(reference.gaps(w, conf, prompt, toks).max())
        ctrl.append(reference.gaps(w, conf, prompt, toks,
                                   control=True).max())
    assert max(ctrl) >= 3 * max(max(prog), 1e-3), (prog, ctrl)


def test_command_refuses_a_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, str(spec.BENCH_DIR / "run.py"), "--workload",
         "qwen1.5-4b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
