"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights itself, so that the reference in
``reference.py`` reads the same arrays the program serves from and
nothing that the program has made. The canonical layout is the model
family's (``bench/families``): one leaf per kind of weight, ``embed``,
``head`` and ``final_norm`` alone and every other leaf stacked over its
first axis (the layers) under ``"layers"``. ``to_program`` re-nests the
same arrays (no copy) into the tree that the program takes.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import families


def device_key(seed: int):
    """A PRNG key from any whole-number seed. ``jax.random.PRNGKey`` keeps
    only the low 32 bits of a seed, so large seeds would collide."""
    lo, hi = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(lo)), int(hi))


def shapes(conf: dict) -> Dict[str, Tuple[int, ...]]:
    """Leaf name -> shape for a configuration file (its family's canonical
    layout)."""
    return families.of(conf).shapes(conf)


_TOP = ("embed", "head", "final_norm")


def _leaf(key, name: str, shape, dtype):
    """One leaf: matrices N(0, 1/fan_in) (the embedding N(0, 1), so the
    logits come out with unit scale); norm scales 1 + N(0, 0.1^2);
    biases N(0, 0.1^2)."""
    if name.endswith("norm") or name in ("norm1", "norm2"):
        return (1.0 + 0.1 * jax.random.normal(key, shape)).astype(dtype)
    if name in ("bq", "bk", "bv"):
        return (0.1 * jax.random.normal(key, shape)).astype(dtype)
    std = 1.0 if name == "embed" else 1.0 / math.sqrt(shape[-2])
    return (std * jax.random.normal(key, shape)).astype(dtype)


def make(conf: dict, seed: int):
    """All weights of a configuration from ``seed``, on the default
    device, in the configuration's dtype. Stacked leaves are made one
    layer at a time inside the call, so no full-size float32 copy is
    ever held."""
    dtype = jnp.dtype(conf.get("torch_dtype", "bfloat16"))
    table = shapes(conf)
    names = sorted(table)

    def build(key):
        out = {"layers": {}}
        for i, name in enumerate(names):
            k = jax.random.fold_in(key, i)
            shape = table[name]
            if name in _TOP:
                out[name] = _leaf(k, name, shape, dtype)
            else:
                keys = jax.random.split(k, shape[0])
                out["layers"][name] = jax.lax.map(
                    lambda kk, n=name, s=shape[1:]: _leaf(kk, n, s, dtype),
                    keys)
        return out

    return jax.jit(build)(device_key(seed))


def to_program(conf: dict, w: dict) -> dict:
    """The same arrays, nested as the program takes them (by the
    configuration's family)."""
    return families.of(conf).to_program(w)


def nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
