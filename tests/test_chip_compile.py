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
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
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


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """Code that picks its path from ``jax.default_backend()`` (the
    paged kernel's "auto" backend) takes its TPU branch while tracing."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("name", ["qwen1.5-4b", "qwen3-8b"])
def test_paged_kernel_compiles(one_chip, name):
    """KV=20/G=1 (MHA) and KV=8/G=4 (GQA) pools at served sizes."""
    cfg = CONFIGS[name]
    B, page, NP = chip_smoke.SLOTS, chip_smoke.PAGE_SIZE, 256
    MP = chip_smoke.CACHE_LEN // page
    bf16 = jnp.bfloat16

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = spec((NP, cfg.n_kv_heads, page, cfg.head_dim), bf16)
    fn = jax.jit(functools.partial(paged_decode_attention,
                                   backend="pallas"))
    compiled = fn.lower(spec((B, cfg.n_heads, cfg.head_dim), bf16), pool,
                        pool, spec((B, MP), jnp.int32),
                        spec((B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_span_fits_one_chip(one_chip, tpu_dispatch):
    """chip_smoke.py's decode span at full qwen1.5-4b width: the pool it
    sizes leaves the headroom free, holds every smoke request whole, and
    the span runs the Pallas kernel."""
    cfg = CONFIGS[chip_smoke.MODEL]
    ps = chip_smoke.PAGE_SIZE
    lens = [n + chip_smoke.MAX_NEW for n in chip_smoke.PROMPT_LENS]
    width = live_table_width(-(-max(lens) // ps),
                             chip_smoke.CACHE_LEN // ps)
    n_pages, need, compiled = chip_smoke.size_pool(
        cfg, hbm_bytes=V5E_HBM_BYTES, slots=chip_smoke.SLOTS,
        cache_len=chip_smoke.CACHE_LEN, page_size=ps, width=width,
        sharding=one_chip)
    assert V5E_HBM_BYTES - need >= chip_smoke.HEADROOM
    assert n_pages >= sum(-(-n // ps) for n in lens)
    assert "tpu_custom_call" in compiled.as_text()
