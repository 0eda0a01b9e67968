"""chip_smoke.py's checks at SMOKE size on the CPU, and the persistent
compile cache's location.

The chip run itself needs a TPU (``python chip_smoke.py``); these tests
run the same check functions on the reduced config so a broken path
fails here first.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from repro.configs.registry import SMOKE_CONFIGS
from repro.launch.compile_cache import REPO_CACHE_DIR
from repro.models import lm

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")

# a request mix like chip_smoke's, cut to SMOKE size: prompts span more
# than one prefill chunk, every request decodes whole spans
SMOKE_RUN = dict(n_pages=32, slots=4, cache_len=128, page_size=8,
                 prefill_chunk=32, decode_span=4,
                 prompt_lens=(40, 40, 36, 52), max_new=9, n_reference=2)


@pytest.fixture(scope="module")
def smoke():
    cfg = SMOKE_CONFIGS[chip_smoke.MODEL]
    return cfg, lm.init_params(cfg, jax.random.PRNGKey(0))


def test_serve_and_check_at_smoke_size(smoke):
    cfg, params = smoke
    res = chip_smoke.serve_and_check(cfg, params, **SMOKE_RUN)
    n = len(SMOKE_RUN["prompt_lens"])
    assert res["tokens"] == n * SMOKE_RUN["max_new"]
    assert res["n_checked"] == SMOKE_RUN["n_reference"] * SMOKE_RUN["max_new"]
    assert res["worst_gap"] <= chip_smoke.LOGIT_TOL
    st = res["stats"]
    assert st["prefills"] == n and st["parked"] == 0
    assert st["host_syncs"] == st["prefills"] + st["decode_spans"]


def test_reference_check_catches_wrong_tokens(smoke):
    """The teacher-forced check is not vacuous: tokens that are not the
    model's own choice sit far below the reference's top logit."""
    cfg, params = smoke
    prompt = jnp.arange(1, 30, dtype=jnp.int32)
    gaps = chip_smoke.teacher_forced_gaps(cfg, params, [prompt],
                                          [[7, 7, 7, 7]],
                                          SMOKE_RUN["cache_len"])
    assert max(gaps) > chip_smoke.LOGIT_TOL


def test_kernel_check_at_smoke_size(smoke):
    cfg, _ = smoke
    err = chip_smoke.check_kernel(
        slots=4, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, n_pages=16, page_size=8, width=4,
        dtype=jnp.bfloat16)
    assert err <= chip_smoke.KERNEL_TOL


def test_refuses_without_tpu():
    """On the CPU the script exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def _run(code, env, timeout=600):
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.split()


_WHERE = """
import jax
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)
"""


def test_compile_cache_dir(tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and is left as JAX read it;
    unset, the cache sits at one fixed path in the checkout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    assert _run(_WHERE, env) == [str(REPO_CACHE_DIR)] * 2
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    assert _run(_WHERE, env) == [str(tmp_path)] * 2


_REHEARSAL = """
import jax
import chip_smoke
from repro.configs.registry import SMOKE_CONFIGS
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
enable_compile_cache()
cfg = SMOKE_CONFIGS[chip_smoke.MODEL]
params = lm.init_params(cfg, jax.random.PRNGKey(0))
chip_smoke.serve_and_check(
    cfg, params, n_pages=16, slots=2, cache_len=64, page_size=8,
    prefill_chunk=16, decode_span=4, prompt_lens=(20, 28), max_new=5,
    n_reference=1)
"""


def test_second_run_writes_no_new_cache_entries(tmp_path):
    """Cache keys are stable across processes: a second run of the same
    rehearsal finds every program it compiles and writes nothing."""
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    _run(_REHEARSAL, env)
    first = sorted(os.listdir(cache))
    assert first
    _run(_REHEARSAL, env)
    assert sorted(os.listdir(cache)) == first
