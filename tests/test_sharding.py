"""Distribution-layer tests: policy mapping, mesh/null equivalence,
elastic resharding, head padding."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import SMOKE_CONFIGS
from repro.launch.mesh import make_smoke_mesh
from repro.models import lm
from repro.models.transformer import eff_heads
from repro.sharding.policy import NULL_POLICY, make_policy


def test_policy_specs():
    mesh = make_smoke_mesh()
    pol = make_policy(mesh)
    assert pol.spec("batch", None, "ff") == jax.sharding.PartitionSpec(
        ("data",), None, "model")
    # raw mesh-axis fallback (ZeRO-1 placement)
    assert pol.spec("data", "vocab") == jax.sharding.PartitionSpec(
        "data", "model")
    # long-context rules: batch released, kv_seq takes the data axes
    pol2 = make_policy(mesh, shard_kv_seq=True)
    assert pol2.spec("batch") == jax.sharding.PartitionSpec(None)
    assert pol2.spec("kv_seq") == jax.sharding.PartitionSpec(("data",))


@pytest.mark.parametrize("arch", ["qwen3-8b", "moonshot-v1-16b-a3b",
                                  "rwkv6-1.6b", "jamba-v0.1-52b"])
def test_mesh_equals_null_policy(arch):
    """The sharded program computes the same loss as the plain one."""
    cfg = SMOKE_CONFIGS[arch]
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0,
                              cfg.vocab_size)
    l0, _ = jax.jit(lambda p, t: lm.forward_loss(
        p, t, cfg, NULL_POLICY))(params, toks)
    mesh = make_smoke_mesh()
    pol = make_policy(mesh)
    with mesh:
        l1, _ = jax.jit(lambda p, t: lm.forward_loss(
            p, t, cfg, pol))(params, toks)
    assert abs(float(l0) - float(l1)) < 5e-3, (float(l0), float(l1))


def test_eff_heads_padding_rules():
    from repro.configs.registry import get_config
    # kv duplication: 8 kv heads, tp=16 -> 16 (H untouched)
    c = get_config("qwen3-8b")
    assert eff_heads(c, 16) == (32, 16)
    # qwen1.5: 20 heads pad to 32, kv pads with them (MHA)
    c2 = get_config("qwen1.5-4b")
    assert eff_heads(c2, 16) == (32, 32)
    # no-op cases
    assert eff_heads(c, 1) == (32, 8)
    c3 = get_config("moonshot-v1-16b-a3b")
    assert eff_heads(c3, 16) == (16, 16)


def test_elastic_reshard(tmp_path):
    """Checkpoint written under one layout restores under another."""
    from repro.checkpoint import Checkpointer, reshard_tree
    cfg = SMOKE_CONFIGS["qwen1.5-4b"]
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    ck = Checkpointer(str(tmp_path))
    ck.save(1, params, blocking=True)
    mesh = make_smoke_mesh()
    pol = make_policy(mesh)
    restored, _ = ck.restore(params)
    shardings = pol.tree_named(lm.param_specs(cfg))
    placed = reshard_tree(restored, shardings)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(placed)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.slow
def test_multidevice_equivalence_subprocess():
    """8 fake devices (2x4 mesh): loss equals the 1-device value."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.configs.registry import SMOKE_CONFIGS
from repro.models import lm
from repro.sharding.policy import make_policy, NULL_POLICY
cfg = SMOKE_CONFIGS["moonshot-v1-16b-a3b"]
params = lm.init_params(cfg, jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, cfg.vocab_size)
l0, _ = jax.jit(lambda p, t: lm.forward_loss(p, t, cfg, NULL_POLICY))(params, toks)
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
pol = make_policy(mesh)
with mesh:
    l1, _ = jax.jit(lambda p, t: lm.forward_loss(p, t, cfg, pol))(params, toks)
d = abs(float(l0) - float(l1))
assert d < 5e-3, (float(l0), float(l1))
print("OK", float(l0), float(l1))
"""
    r = subprocess.run([sys.executable, "-c", code],
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
