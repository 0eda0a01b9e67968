"""Where JAX keeps its persistent compilation cache.

A whole-model program takes tens of seconds to compile for the chip, and
the serving loop compiles several (prefill chunk, decode span buckets).
JAX can keep compiled programs on disk and find them again in a later
process, but only if the directory is the same every time: a temporary,
per-process or dated path never hits.

  - ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is
    set here.
  - Unset: the cache lives at ``<repo>/.jax_cache`` (listed in
    ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
