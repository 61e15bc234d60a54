"""Work counts and peaks against hand counts."""
from __future__ import annotations

import json

import pytest

import counts
import harness


def hp(name):
    return json.loads((harness.HERE / "configs" / f"{name}.json").read_text())


# Mixtral-8x7B's published shapes (hf mistralai/Mixtral-8x7B-v0.1), cut to
# 4 layers: the counts of a sparse-expert configuration
MIXTRAL_4L = {"model_type": "mixtral", "hidden_size": 4096,
              "intermediate_size": 14336, "num_hidden_layers": 4,
              "num_attention_heads": 32, "num_key_value_heads": 8,
              "head_dim": 128, "vocab_size": 32000, "num_local_experts": 8,
              "num_experts_per_tok": 2, "tie_word_embeddings": False,
              "sliding_window": 4096, "torch_dtype": "bfloat16"}


def test_qwen3_hand_counts():
    q = hp("qwen3-0.6b")
    # per layer: q 1024x2048, k and v 1024x1024, o 2048x1024, MLP 3x1024x3072
    layer = 1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024 + 3 * 1024 * 3072
    assert layer == 15_728_640
    head = 1024 * 151936
    assert counts.matmul_params_per_token(q) == 28 * layer + head
    # a token at context 1000: 2 per weight, + QK^T and PV per layer
    assert counts.flops_per_token(q, 1000) == \
        2 * (28 * layer + head) + 28 * 4 * 16 * 128 * 1000
    # K and V of one position, 28 layers x 8 heads x 128, bf16
    assert counts.kv_bytes_per_token(q) == 2 * 28 * 8 * 128 * 2 == 114_688
    norms = 2 * 1024 + 2 * 128
    w = (28 * (layer + norms) + head + 1024) * 2     # tied: head is embed
    assert counts.step_weight_bytes(q, 16) == w
    step = counts.step_work(q, [100, 2048])
    assert step["bytes"] == w + (100 + 2048) * 114_688
    assert step["flops"] == counts.flops_per_token(q, 100) + \
        counts.flops_per_token(q, 2048)


def test_mixtral_hand_counts_and_the_two_expert_share():
    m = MIXTRAL_4L
    attn = 4096 * (32 + 16) * 128 + 32 * 128 * 4096
    assert attn == 41_943_040
    expert = 3 * 4096 * 14336
    assert expert == 176_160_768
    per_tok = 4 * (attn + 2 * expert + 4096 * 8) + 4096 * 32000
    assert counts.matmul_params_per_token(m) == per_tok
    # window 4096 caps the keys a token reads
    assert counts.attended(m, 5000) == 4096
    assert counts.flops_per_token(m, 5000) == 2 * per_tok + 4 * 4 * 32 * \
        128 * 4096
    # one token reads 2 experts; 4 tokens or more read all 8
    norms = 2 * 4096
    one = (4 * (attn + 2 * expert + 4096 * 8 + norms) + 4096 * 32000
           + 4096 + 4096) * 2
    assert counts.step_weight_bytes(m, 1) == one
    full = counts.step_weight_bytes(m, 32)
    assert full - counts.step_weight_bytes(m, 4) == 28 * 4096 * 2
    # the share of one of four chips: 2 experts of each of 4 layers, bf16
    assert 4 * 2 * expert * 2 == 2_818_572_288
    assert (full - (4 * (attn + 4096 * 8 + norms) + 4096 * 32000 + 4096
                    + 32 * 4096) * 2) / 4 == 2_818_572_288


def test_least_seconds_names_the_bound():
    peak = counts.peaks("TPU v5 lite")
    assert peak["bf16_flops"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    t = counts.least_seconds(197e12, 819e9 / 2, peak, 1)
    assert t["seconds"] == 1.0 and t["bound"] == "compute"
    t = counts.least_seconds(1e9, 819e9 * 2, peak, 4)
    assert t["seconds"] == 0.5 and t["bound"] == "memory"


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
