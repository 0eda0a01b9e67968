"""Operations and bytes behind step_mfu and paged_attn_roofline, on
hand-computed shapes."""
import pytest

from bench import flops, peaks
from bench.families import dense

TOY = {"model_type": "qwen3", "hidden_size": 8, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
       "intermediate_size": 16, "vocab_size": 10}


def test_layer_matmul_params():
    # per layer: wq 8*8, wk 8*4, wv 8*4, wo 8*8, 3 * 8*16
    assert dense.layer_matmul_params(TOY) == 2 * (64 + 32 + 32 + 64 + 384)


def test_decode_token_flops():
    # 2 * 1152 matmul params + 2 * 8 * 10 head + 4 * 2 * 4 * 2 * 5
    assert flops.decode_token_flops(TOY, 5) == 2304 + 160 + 320


def test_prefill_flops():
    # 3 tokens: matmuls 3 * 2304, head once, attention 4*2*4*2 * (1+2+3)
    assert flops.prefill_flops(TOY, 3) == 3 * 2304 + 160 + 64 * 6


def test_paged_attn_work():
    ops, nbytes = flops.paged_attn_work(TOY, 7)
    assert ops == 4 * 2 * 4 * 2 * 7
    # per layer: K and V rows 2 * 2 heads * 2 * 7, q and out 2 * 4 * 2
    assert nbytes == 2 * 2 * (2 * 2 * 2 * 7 + 2 * 4 * 2)


def test_qwen15_4b_parameters():
    conf = {"hidden_size": 2560, "num_hidden_layers": 40,
            "num_attention_heads": 20, "num_key_value_heads": 20,
            "intermediate_size": 6912, "vocab_size": 151936}
    # 40 * (4 * 2560^2 + 3 * 2560 * 6912) = 3.171e9
    assert dense.layer_matmul_params(conf) == 3_171_942_400


def test_roofline_takes_the_larger_bound():
    pk = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(1000, 50, pk) == 10.0
    assert flops.roofline_seconds(100, 50, pk) == 5.0


def test_peaks_table():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
