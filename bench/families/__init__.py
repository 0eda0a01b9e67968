"""Model families: everything in the benchmark that depends on the
architecture, one module each, found by the configuration file's
``model_type`` (the key of the model's public ``config.json``).

A family module declares ``MODEL_TYPES``, the ``model_type`` values it
serves, and provides (``CONTRACT``):

- ``model_config(conf)``: the program's ``ModelConfig``;
- ``shapes(conf)``: leaf name -> shape of the canonical weights, which
  ``weights.make`` builds from the seed (a leaf other than ``embed``,
  ``head`` and ``final_norm`` is stacked over its first axis and goes
  under ``"layers"``; ``weights._leaf`` seeds a leaf whose name ends in
  ``norm`` (or ``norm1``, ``norm2``) as a norm scale, ``bq``, ``bk`` and
  ``bv`` as biases, every other leaf as a matrix over its axis -2);
- ``to_program(w)``: the program's parameter tree, made of the same
  arrays without a copy;
- ``logits(w, conf, tokens, rows, control)``: the float32 reference's
  logits ``[len(rows), V]`` at ``rows`` of the padded sequence
  ``tokens``, with ``control`` those of the float8 control, both built
  from ``reference.py``'s numerical helpers;
- ``decode_token_flops(conf, context)``, ``prefill_flops(conf,
  prompt_len)`` and ``paged_attn_work(conf, context, itemsize)``: the
  work counts of ``flops.py``, from the configuration and the lengths
  alone.

A new architecture is a new module in this package, and nothing else.
"""
from __future__ import annotations

import importlib
import pkgutil

CONTRACT = ("MODEL_TYPES", "model_config", "shapes", "to_program", "logits",
            "decode_token_flops", "prefill_flops", "paged_attn_work")


def known() -> dict:
    """``model_type`` -> family module, over every module of the package."""
    out = {}
    for info in pkgutil.iter_modules(__path__):
        mod = importlib.import_module(f"{__name__}.{info.name}")
        for t in mod.MODEL_TYPES:
            if t in out:
                raise ValueError(f"model_type {t!r} is declared by both "
                                 f"{out[t].__name__} and {mod.__name__}")
            out[t] = mod
    return out


def of(conf: dict):
    """The family module that serves a configuration file."""
    fams = known()
    t = conf.get("model_type")
    if t not in fams:
        raise ValueError(f"configuration {conf.get('name')!r} has model_type "
                         f"{t!r}, which no family serves; known: "
                         f"{sorted(fams)}")
    return fams[t]
