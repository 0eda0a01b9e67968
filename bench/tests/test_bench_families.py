"""Model families: each configuration file's ``model_type`` finds the
module that builds, checks and counts its model.

The dense family's fingerprints were taken from the harness before the
families were split out of ``weights.py``, ``reference.py`` and
``flops.py``: the same seed gives bitwise the same weights, the
reference and its control the same logits, and the configurations the
same work counts. A family that exists only as a new file is found and
drives the whole CPU path."""
import hashlib
import importlib
import json
import sys

import jax
import numpy as np
import pytest

from bench import families, flops, reference, spec, weights

FP = {"name": "fp", "source": "test", "model_type": "qwen3",
      "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
      "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
      "torch_dtype": "bfloat16", "attention_bias": True, "qk_norm": True,
      "hidden_act": "silu"}
FP_SEED = 2**31 + 11

# sha256 of each leaf's bytes (first 16 hex digits), from FP_SEED
WEIGHTS = {
    "bk": "21cba5bdb62b8ef5", "bq": "86d1985603320db8",
    "bv": "68aad4afc26e659e", "embed": "24810cadcc266281",
    "final_norm": "b625eaa38b2dd47d", "head": "dbdc786af49b552d",
    "k_norm": "807957c6277c9d82", "norm1": "5116e805d55a7cb5",
    "norm2": "39c0b45280055a99", "q_norm": "79890a45a671d07f",
    "w_down": "4207744cb6ee4472", "w_gate": "2b807fc7fa76a308",
    "w_up": "8f59a0defa54b723", "wk": "cf51ba8286826735",
    "wo": "f538526d5c57c0d1", "wq": "3a49752a1c4acaa8",
    "wv": "66d3e82e1932c832"}

# teacher-forced logits at served tokens 0, 4 and 8, vocabulary ids
# 0, 1, 2, 100 and 255, and each served token's row sum
COLS = [0, 1, 2, 100, 255]
REF = [[1.3990508317947388, 1.2469533681869507, 1.2300806045532227,
        2.629089593887329, -0.24392908811569214],
       [-1.0990955829620361, 0.3480224013328552, -0.6985270380973816,
        -0.6753150224685669, -0.27688437700271606],
       [-1.0212434530258179, 0.6260785460472107, -1.1940852403640747,
        0.9223038554191589, -0.33190134167671204]]
CTL = [[1.437487244606018, 1.2767301797866821, 1.0607526302337646,
        2.5453593730926514, -0.22732405364513397],
       [-1.0903911590576172, 0.26779404282569885, -0.7471267580986023,
        -0.692607045173645, -0.2175961136817932],
       [-1.043999433517456, 0.6396411657333374, -1.2493265867233276,
        0.902903139591217, -0.3270382583141327]]
REF_SUMS = [-4.2404465675354, -5.597709655761719, 8.135833740234375,
            6.023097991943359, 3.156337022781372, -1.4302020072937012,
            -21.135623931884766, 5.802150726318359, 15.205302238464355]
CTL_SUMS = [-4.085822105407715, -4.79604959487915, 6.85101842880249,
            4.846508026123047, 4.685525894165039, -2.5788774490356445,
            -21.124204635620117, 4.676549911499023, 15.858682632446289]
GAPS = [2.659647226333618, 2.396496295928955, 2.1697616577148438,
        3.261396646499634, 0.9457880258560181, 2.388551712036133,
        3.1228597164154053, 2.1141300201416016, 1.8211698532104492]
CONTROL_GAPS = [0, 0, 0, 0, 0, 0.08930325508117676, 0, 0, 0]

# decode_token_flops at contexts 1, 1000, 4096; prefill_flops at prompts
# 1, 256, 3968; paged_attn_work at contexts 1, 1000, 4096
COUNTS = {
    "qwen1.5-4b": (
        [7122206720, 7531397120, 8799518720],
        [7122206720, 1638286622720, 28398706360320],
        [(409600, 819200), (409600000, 410009600),
         (1677721600, 1678131200)]),
    "qwen3-8b-s18": (
        [8190722048, 8485339136, 9398386688],
        [8190722048, 1789062545408, 29884333162496],
        [(294912, 368640), (294912000, 74022912),
         (1207959552, 302284800)])}


def _served():
    rng = np.random.default_rng(3)
    return rng.integers(1, 256, 40), rng.integers(1, 256, 9)


def _config_file(name):
    c = [c for c in spec.load_benchmark()["configs"] if c["name"] == name]
    return json.loads((spec.ROOT / c[0]["file"]).read_text())


def test_dense_weights_are_bitwise_the_same():
    w = weights.make(FP, FP_SEED)
    leaves = {**{k: w[k] for k in ("embed", "head", "final_norm")},
              **w["layers"]}
    got = {k: hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16]
           for k, v in leaves.items()}
    assert got == WEIGHTS


def test_dense_reference_and_control_logits_are_the_same():
    w = weights.make(FP, FP_SEED)
    prompt, served = _served()
    ref, ctl, _, n = reference._teacher_forced(w, FP, prompt, served, None,
                                               control=True)
    ref, ctl = np.asarray(ref)[:n], np.asarray(ctl)[:n]
    for got, want, sums in ((ref, REF, REF_SUMS), (ctl, CTL, CTL_SUMS)):
        np.testing.assert_allclose(got[[0, 4, 8]][:, COLS], want,
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(got.sum(-1), sums, rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(reference.gaps(w, FP, prompt, served), GAPS,
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        reference.gaps(w, FP, prompt, served, control=True), CONTROL_GAPS,
        rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_work_counts_are_the_same(name):
    conf = _config_file(name)
    decode, prefill, attn = COUNTS[name]
    assert [flops.decode_token_flops(conf, c)
            for c in (1, 1000, 4096)] == decode
    assert [flops.prefill_flops(conf, p) for p in (1, 256, 3968)] == prefill
    assert [flops.paged_attn_work(conf, c)
            for c in (1, 1000, 4096)] == attn


@pytest.mark.parametrize("name,model_type", [("qwen1.5-4b", "qwen2"),
                                             ("qwen3-8b-s18", "qwen3")])
def test_config_files_name_their_family(name, model_type):
    conf = _config_file(name)
    assert conf["model_type"] == model_type
    assert families.of(conf).__name__ == "bench.families.dense"


def test_every_family_provides_the_contract():
    fams = families.known()
    assert {"qwen2", "qwen3"} <= set(fams)
    for mod in set(fams.values()):
        for name in families.CONTRACT:
            assert hasattr(mod, name), (mod.__name__, name)
        assert all(callable(getattr(mod, n)) for n in families.CONTRACT
                   if n != "MODEL_TYPES")
        assert mod.MODEL_TYPES and all(isinstance(t, str)
                                       for t in mod.MODEL_TYPES)


def test_unknown_model_type_names_the_known_ones(tmp_path):
    conf = {**FP, "model_type": "mamba9"}
    with pytest.raises(ValueError, match=r"mamba9.*qwen2.*qwen3"):
        families.of(conf)
    bench = _toy_bench(tmp_path, conf)
    with pytest.raises(ValueError, match=r"mamba9.*qwen2.*qwen3"):
        spec.load_cell("toy.chat", root=tmp_path, bench=bench)
    with pytest.raises(ValueError, match="None"):
        families.of({k: v for k, v in FP.items() if k != "model_type"})


def _toy_bench(root, conf):
    (root / "toy.json").write_text(json.dumps(conf))
    return {"configs": [{"name": "toy", "file": "toy.json"}],
            "workloads": [{"name": "toy.chat", "config": "toy",
                           "traffic": "chat", "chips": 1}],
            "end_to_end": [{"name": "setup_s"}], "per_layer": []}


@pytest.fixture
def toy_family(tmp_path, monkeypatch):
    """A family that exists only as a new file: the dense module, copied
    into a directory of its own under another ``model_type``."""
    src = importlib.import_module("bench.families.dense").__file__
    text = open(src).read().replace('MODEL_TYPES = ("qwen2", "qwen3")',
                                    'MODEL_TYPES = ("toy_lm",)')
    assert 'MODEL_TYPES = ("toy_lm",)' in text
    pkg = tmp_path / "fams"
    pkg.mkdir()
    (pkg / "toy_lm.py").write_text(text)
    monkeypatch.setattr(families, "__path__",
                        [*families.__path__, str(pkg)])
    importlib.invalidate_caches()
    yield "bench.families.toy_lm"
    sys.modules.pop("bench.families.toy_lm", None)


def test_a_new_family_is_found_by_its_file(toy_family, tmp_path):
    from repro.models import lm
    conf = {**FP, "model_type": "toy_lm"}
    cell = spec.load_cell("toy.chat", root=tmp_path,
                          bench=_toy_bench(tmp_path, conf))
    assert families.of(cell.config) is sys.modules[toy_family]
    cfg = spec.model_config(cell.config)
    w = weights.make(cell.config, FP_SEED)
    params = weights.to_program(cell.config, w)
    want = lm.abstract_params(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(want)
    # the copy computes what the dense family does
    prompt, served = _served()
    np.testing.assert_allclose(
        reference.gaps(w, cell.config, prompt, served), GAPS,
        rtol=1e-6, atol=1e-6)
    assert flops.decode_token_flops(cell.config, 50) == \
        flops.decode_token_flops(FP, 50)
    assert flops.prefill_flops(cell.config, 30) == flops.prefill_flops(FP, 30)
    assert flops.paged_attn_work(cell.config, 7) == \
        flops.paged_attn_work(FP, 7)


def test_a_model_type_has_one_family(toy_family, tmp_path):
    (tmp_path / "fams" / "toy_twin.py").write_text(
        'MODEL_TYPES = ("toy_lm",)\n')
    importlib.invalidate_caches()
    try:
        with pytest.raises(ValueError, match="toy_lm.*both"):
            families.known()
    finally:
        sys.modules.pop("bench.families.toy_twin", None)
