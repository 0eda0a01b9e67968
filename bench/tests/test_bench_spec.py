"""BENCHMARK.json and the files it names: every cell, configuration,
traffic mix and per-layer metric is found by name, and the configuration
files give the program the model it registers."""
import dataclasses
import importlib
import json

import jax
import pytest

from bench import spec, weights

BENCH = spec.load_benchmark()


def test_every_cell_finds_its_files():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"], bench=BENCH)
        assert cell.config["name"] == w["config"]
        assert "engine" in cell.traffic and "limits" in cell.traffic
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_every_per_layer_metric_has_a_reader():
    for m in BENCH["per_layer"]:
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        assert callable(mod.read)
        moved = [e for e in BENCH["end_to_end"] if e["name"] == m["moves"]]
        assert moved, m
        for cell in m.get("workloads", []):
            assert cell in moved[0].get("workloads", [cell])


def test_config_files_name_their_reductions():
    for c in BENCH["configs"]:
        conf = json.loads((spec.ROOT / c["file"]).read_text())
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        for k in conf["reduced"]:
            assert k in conf.get("published", {})


@pytest.mark.parametrize("name,registry,layers", [
    ("qwen1.5-4b", "qwen1.5-4b", 40), ("qwen3-8b-s18", "qwen3-8b", 18)])
def test_config_matches_the_registry(name, registry, layers):
    from repro.configs.registry import CONFIGS
    c = [c for c in BENCH["configs"] if c["name"] == name][0]
    cfg = spec.model_config(json.loads((spec.ROOT / c["file"]).read_text()))
    want = dataclasses.replace(CONFIGS[registry], n_layers=layers)
    for f in dataclasses.fields(want):
        if f.name not in ("name", "source"):
            assert getattr(cfg, f.name) == getattr(want, f.name), f.name


@pytest.mark.parametrize("name", ["qwen1.5-4b", "qwen3-8b-s18"])
def test_weights_fit_the_program_tree(name):
    from repro.models import lm
    c = [c for c in BENCH["configs"] if c["name"] == name][0]
    conf = json.loads((spec.ROOT / c["file"]).read_text())
    cfg = spec.model_config(conf)
    made = jax.eval_shape(lambda: weights.to_program(
        conf,
        {"layers": {k: jax.ShapeDtypeStruct(v[:], "bfloat16")
                    for k, v in weights.shapes(conf).items()
                    if k not in ("embed", "head", "final_norm")},
         **{k: jax.ShapeDtypeStruct(weights.shapes(conf)[k], "bfloat16")
            for k in ("embed", "head", "final_norm")}}))
    want = lm.abstract_params(cfg)
    assert jax.tree.structure(made) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(made), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_weights_are_made_from_the_seed():
    conf = {"model_type": "qwen3", "hidden_size": 16,
            "num_hidden_layers": 2, "num_attention_heads": 2,
            "num_key_value_heads": 1, "head_dim": 8,
            "intermediate_size": 32, "vocab_size": 64,
            "attention_bias": True, "qk_norm": True}
    a = weights.make(conf, 2**31 + 5)
    b = weights.make(conf, 2**31 + 5)
    c = weights.make(conf, 5)
    assert all((x == y).all() for x, y in zip(jax.tree.leaves(a),
                                              jax.tree.leaves(b)))
    assert not (a["embed"] == c["embed"]).all()
    assert a["layers"]["wq"].dtype == jax.numpy.bfloat16
    assert a["layers"]["wq"].shape == (2, 16, 16)
