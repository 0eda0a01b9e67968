"""Compiles for a described TPU v5e chip, which need no chip attached.

The TPU compiler refuses what interpret mode accepts: blocks not aligned
to the (8, 128) tiling, programs that overflow HBM. These tests compile
the served path's Pallas kernel and whole decode span at published
widths for one chip of a described ``v5e:2x2`` topology. Nothing runs,
so they say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
each import every test file.
"""
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from bench import program_trace
from bench.spec import BENCH_DIR
from repro.configs.registry import CONFIGS
from repro.kernels.paged_attention import (live_table_width,
                                           paged_decode_attention)

# HBM the TPU compiler accepts for one program on one v5e chip
V5E_HBM_BYTES = int(15.75 * (1 << 30))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_kernel(one_chip, cfg, slots, n_pages, width,
                    backend="pallas"):
    bf16 = jnp.bfloat16

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = spec((n_pages, cfg.n_kv_heads, chip_smoke.PAGE_SIZE,
                 cfg.head_dim), bf16)
    fn = jax.jit(functools.partial(paged_decode_attention,
                                   backend=backend))
    return fn.lower(spec((slots, cfg.n_heads, cfg.head_dim), bf16), pool,
                    pool, spec((slots, width), jnp.int32),
                    spec((slots,), jnp.int32)).compile()


@pytest.mark.parametrize("width", [16, 32, 64, 128])
@pytest.mark.parametrize("name,slots,n_pages", [
    ("qwen1.5-4b", 8, 256), ("qwen3-8b", 12, 1664)])
def test_paged_kernel_compiles(one_chip, name, slots, n_pages, width):
    """The benchmark cells' deployments, KV=20/G=1 (MHA, chat: 8 slots,
    256 pages) and KV=8/G=4 (GQA, reasoning: 12 slots, 1664 pages), at
    every table width their spans export: the blocks of pages fit VMEM."""
    compiled = _compile_kernel(one_chip, CONFIGS[name], slots, n_pages,
                               width)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", ["nemotron-4-15b", "chameleon-34b",
                                  "moonshot-v1-16b-a3b", "musicgen-large"])
def test_paged_kernel_compiles_for_other_paged_configs(one_chip, name):
    """The other configurations on the paged path (G=6 and 8, KV=16)
    take their block size from the same rule; musicgen's head_dim 64 is
    not lane-aligned, so the "auto" dispatch gives it the gather."""
    cfg = CONFIGS[name]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        compiled = _compile_kernel(one_chip, cfg, 8, 256, 64,
                                   backend="auto")
    kernel = "tpu_custom_call" in compiled.as_text()
    assert kernel == (cfg.head_dim % 128 == 0)


@pytest.fixture(scope="module")
def smoke_span(one_chip):
    """chip_smoke.py's decode span at full qwen1.5-4b width, compiled for
    one chip with its pool sized: (prompt+answer lengths, n_pages, bytes
    needed, the compiled program). ``jax.default_backend`` reads "tpu"
    while it is traced, so the paged kernel's "auto" dispatch takes its
    TPU branch."""
    cfg = CONFIGS[chip_smoke.MODEL]
    ps = chip_smoke.PAGE_SIZE
    lens = [n + chip_smoke.MAX_NEW for n in chip_smoke.PROMPT_LENS]
    width = live_table_width(-(-max(lens) // ps),
                             chip_smoke.CACHE_LEN // ps)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        n_pages, need, compiled = chip_smoke.size_pool(
            cfg, hbm_bytes=V5E_HBM_BYTES, slots=chip_smoke.SLOTS,
            cache_len=chip_smoke.CACHE_LEN, page_size=ps, width=width,
            sharding=one_chip)
    return lens, n_pages, need, compiled


def test_decode_span_fits_one_chip(smoke_span):
    """The pool chip_smoke.py sizes leaves the headroom free, holds every
    smoke request whole, and the span runs the Pallas kernel."""
    lens, n_pages, need, compiled = smoke_span
    ps = chip_smoke.PAGE_SIZE
    assert V5E_HBM_BYTES - need >= chip_smoke.HEADROOM
    assert n_pages >= sum(-(-n // ps) for n in lens)
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_span_names_the_kernel(smoke_span):
    """The Pallas call keeps its name in the compiled span, so a trace
    finds the kernel as ``paged_attention``."""
    text = smoke_span[3].as_text()
    assert re.search(r'%paged_attention[.\d]* = [^\n]*'
                     r'custom_call_target="tpu_custom_call"', text)


@pytest.fixture(scope="module")
def chat_span(one_chip):
    """The decode span of the benchmark's ``qwen1.5-4b.chat`` deployment
    (its traffic file's engine: 8 slots, the stochastic sampler) at full
    width, compiled for one chip with a small pool: (slots, the
    optimized ``HloModuleProto``)."""
    from repro.models import lm
    from repro.serve.api import EngineConfig, make_sampler
    from repro.serve.engine import span_program
    from repro.sharding.policy import NULL_POLICY

    traffic = json.loads((BENCH_DIR / "traffic" / "chat.json").read_text())
    cfg = CONFIGS["qwen1.5-4b"]
    ecfg = EngineConfig(n_pages=64, **traffic["engine"])
    B, width = ecfg.slots, ecfg.cache_len // ecfg.page_size
    sampler = make_sampler(ecfg.sampler)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = jax.eval_shape(lambda: lm.init_paged_serve_state(
        cfg, B, ecfg.n_pages, ecfg.page_size, width))
    vec = lambda dt: spec((B,), dt)  # noqa: E731
    args = jax.tree.map(lambda s: spec(s.shape, s.dtype), (
        lm.abstract_params(cfg), vec(jnp.int32), state, vec(jnp.bool_),
        vec(jnp.int32), tuple(vec(jnp.asarray(x).dtype)
                              for x in sampler.slot_params(None)),
        (vec(jnp.int32),) * 3))
    fn = span_program(cfg, NULL_POLICY, ecfg, sampler, ecfg.decode_span,
                      False)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        compiled = fn.lower(*args).compile()
    proto = compiled.runtime_executable().hlo_modules()[0]
    return B, program_trace._messages()["HloModuleProto"].FromString(
        proto.as_serialized_hlo_module_proto())


def test_sampler_ops_are_charged_to_the_sampler(chat_span):
    """Every op of the TPU-compiled span that works on the whole
    vocabulary's mask or indices (pred/s32, [slots x vocab] or flat) is
    charged to the ``sampler`` scope, by its own op_name or, where the
    compiler made it (the keep-mask scatter becomes a sort and a fusion),
    by ``program_trace.charged_scopes``: ``sampler_share`` reads them."""
    B, module = chat_span
    V = CONFIGS["qwen1.5-4b"].vocab_size
    charged = program_trace.charged_scopes(module)
    fused = {c for comp in module.computations for i in comp.instructions
             if i.opcode == "fusion" for c in i.called_computation_ids}
    PRED, S32 = 1, 4                           # xla PrimitiveType

    def vocab_wide(shape):
        return any(s.element_type in (PRED, S32)
                   and list(s.dimensions) in ([B, V], [B * V])
                   for s in [shape, *shape.tuple_shapes])

    found = [i for comp in module.computations if comp.id not in fused
             for i in comp.instructions
             if i.opcode in ("fusion", "sort") and vocab_wide(i.shape)]
    # the sampler's sort and the sort the keep-mask scatter became
    assert sum(i.opcode == "sort" for i in found) >= 2
    for i in found:
        scope = charged.get(i.name, i.metadata.op_name)
        assert program_trace.in_scope(scope, "serve_decode_span",
                                      "sampler"), (i.name, scope)
