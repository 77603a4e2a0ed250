"""Operation and byte counts from shapes, and the table of peaks."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import counts  # noqa: E402

SMOKE = {"hidden_size": 48, "num_attention_heads": 6, "num_key_value_heads": 2,
         "head_dim": 8, "intermediate_size": 112, "num_hidden_layers": 3,
         "vocab_size": 173, "qkv_bias": True, "tie_word_embeddings": True}


def test_parameter_count_at_smoke_size():
    per_layer = (48 * 8 * (12 + 4)          # q, k, v, o
                 + 3 * 48 * 112             # gated MLP
                 + 2 * 48                   # two norms
                 + 8 * (6 + 4))             # q, k, v biases
    assert counts.param_count(SMOKE) == 173 * 48 + 3 * per_layer + 48


def test_published_sizes():
    qwen = dict(hidden_size=1536, num_attention_heads=12, num_key_value_heads=2,
                head_dim=128, intermediate_size=8960, num_hidden_layers=28,
                vocab_size=151936, qkv_bias=True, tie_word_embeddings=True)
    smol = dict(hidden_size=576, num_attention_heads=9, num_key_value_heads=3,
                head_dim=64, intermediate_size=1536, num_hidden_layers=30,
                vocab_size=49152, qkv_bias=False, tie_word_embeddings=True)
    assert counts.param_count(smol) == 134_515_008       # "135M"
    assert counts.param_count(qwen) == 1_543_714_304     # 1.5B with tied head
    assert counts.kv_bytes_per_token(qwen, 2) == 28_672


def test_decode_and_prefill_flops():
    m = 3 * (48 * 8 * 16 + 3 * 48 * 112)                  # matmul weights
    head = 48 * 173
    att = 4 * 3 * 6 * 8                                    # per query, per key
    assert counts.decode_flops(SMOKE, 2, 12) == 2 * (2 * (m + head)) + att * 12
    # 4 new tokens after 10 held ones attend to 11, 12, 13, 14 keys
    assert counts.prefill_flops(SMOKE, 4, past=10) == 2 * m * 4 + att * 50
    assert counts.train_flops(SMOKE, 10) == 6 * counts.param_count(SMOKE) * 10


def test_decode_bytes_and_pages():
    w = counts.param_count(SMOKE) * 2
    assert counts.decode_bytes(SMOKE, 1, 12, 2) == w + 12 * 2 * 3 * 2 * 8 * 2
    assert counts.leaf_pages([(10, 100), (3,)], 1024) == 4 + 1
    assert counts.page_copy_bytes(5, 1024) == 10 * 1024


def test_peaks_lookup():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
