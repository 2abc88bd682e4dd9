"""`benchmark/flops_granite.py` against values worked by hand (the
totals at the cell's size, every term at a small one), and
the configuration's file against the catalog's row."""

import json
import os

import pytest

from benchmark import flops_granite as fg

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAFFIC = {"batch_per_chip": 1, "seq": 8192}
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        return json.load(f)


def test_published_widths_and_the_two_cuts(config):
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["intermediate_size"],
            config["shared_intermediate_size"], config["mamba_n_heads"],
            config["mamba_d_head"], config["mamba_d_state"],
            config["mamba_n_groups"], config["mamba_d_conv"],
            config["mamba_expand"], config["mamba_chunk_size"]) == (
        2048, 32, 8, 8192, 8192, 64, 64, 128, 1, 4, 2, 256)
    assert (config["embedding_multiplier"], config["residual_multiplier"],
            config["attention_multiplier"], config["logits_scaling"],
            config["rms_norm_eps"], config["tie_word_embeddings"],
            config["position_embedding_type"], config["mamba_conv_bias"],
            config["mamba_proj_bias"], config["attention_bias"],
            config["num_local_experts"]) == (
        12, 0.22, 0.015625, 8, 1e-5, True, "nope", True, False, False, 0)
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "vocab_size": 100352}
    assert (config["num_hidden_layers"], config["vocab_size"]) == (
        10, 100352 // 4)
    # layer_types kept whole: four periods; the first one runs
    assert config["layer_types"] == PERIOD * 4
    assert fg.layer_kinds(config) == PERIOD
    assert (fg.mamba_layers(config), fg.attention_layers(config)) == (9, 1)


def test_the_parameter_count_is_worked_by_hand(config):
    """A Mamba layer 76,182,976, the attention layer 60,821,504, one
    period 746,468,288; with a quarter of the tied table 797,850,560 =
    12.77 GB at 16 B a parameter."""
    in_proj = 2048 * (2 * 4096 + 2 * 128 + 64)
    assert in_proj == 17_432_576
    swiglu = 3 * 2048 * 8192
    mamba = (in_proj + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * 2048
             + swiglu + 2 * 2048)
    assert mamba == 76_182_976
    attention = 2 * 2048 ** 2 + 2 * 2048 * 512 + swiglu + 2 * 2048
    assert attention == 60_821_504
    total = 9 * mamba + attention + 25088 * 2048 + 2048
    assert total == config["parameters"] == 797_850_560
    assert round(16 * total / 1e9, 2) == 12.77
    # the matmul parameters leave out the conv, the vectors, the norms and
    # the lookup: the head is the table's transpose
    assert fg.mamba_matmul_params(config) == in_proj + 4096 * 2048
    assert fg.attention_matmul_params(config) == 2 * 2048 ** 2 + \
        2 * 2048 * 512
    assert fg.matmul_params_per_token(config) == (
        9 * (in_proj + 4096 * 2048) + 2 * 2048 ** 2 + 2 * 2048 * 512
        + 10 * swiglu + 2048 * 25088)


def test_the_ssd_count_at_the_cell():
    """~0.70 TFLOP and ~3.8 GB a step: 4.6 ms at v5e's peaks, bound by
    the bytes."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        config = json.load(f)
    macs = 128 * 128 + 128 * 4096 + 2 * 128 * 4096
    assert fg.ssd_forward_macs_per_token(config) == macs == 1_589_248
    need = fg.ssd_train_step(config, TRAFFIC, 1)
    assert need["flops"] == 3 * 2 * macs * 8192 * 9 == 703_032_459_264
    row = 2 * 4096 * 2 + 2 * 128 * 2 + 64 * 4
    assert need["bytes"] == 3 * row * 8192 * 9 == 3_793_747_968
    assert need["bytes"] / 819e9 > need["flops"] / 197e12
    assert need["bytes"] / 819e9 == pytest.approx(4.632e-3, rel=1e-3)


def test_the_flash_count_at_the_cell(config):
    """One attention layer at T 8192: 32 heads of 64 over 33,558,528
    visible pairs, 12 FLOPs a pair a head a dim: 4.19 ms at the bf16
    peak, bound by the FLOPs (0.25 GB of q, k, v, o and their
    cotangents: 0.31 ms)."""
    need = fg.flash_train_step(config, TRAFFIC, 1)
    assert need["flops"] == 12 * 32 * 33_558_528 * 64 == 824_734_384_128
    assert need["bytes"] == 6 * 8192 * 64 * 2 * (32 + 8) == 251_658_240
    assert need["flops"] / 197e12 == pytest.approx(4.187e-3, rel=1e-3)
    assert need["flops"] / 197e12 > need["bytes"] / 819e9


def test_every_term_at_a_small_size():
    small = {"hidden_size": 8, "num_attention_heads": 2,
             "num_key_value_heads": 1, "shared_intermediate_size": 12,
             "mamba_n_heads": 4, "mamba_d_head": 4, "mamba_d_state": 2,
             "mamba_n_groups": 1, "mamba_chunk_size": 4,
             "num_hidden_layers": 3, "vocab_size": 10, "dtype": "float32",
             "layer_types": ["mamba", "attention", "mamba", "attention"]}
    traffic = {"batch_per_chip": 2, "seq": 6}
    # in_proj 8 x (16 + 16 + 4 + 4) = 320, out_proj 16 x 8 = 128
    assert fg.mamba_matmul_params(small) == 448
    # q, o 8 x 8 each; k, v 8 x 4 each
    assert fg.attention_matmul_params(small) == 192
    assert fg.matmul_params_per_token(small) == (
        2 * 448 + 192 + 3 * 3 * 8 * 12 + 8 * 10)
    # causal pairs of 6: 21; 12 a pair a head a dim, 2 heads of 4
    assert fg.attention_flops(small, 2, 6) == 12 * 2 * 2 * 21 * 4
    # (4/2) 2 + (4/2) 16 + 2 x 2 x 16 a token
    assert fg.ssd_forward_macs_per_token(small) == 4 + 32 + 64
    need = fg.ssd_train_step(small, traffic, 1)
    assert need["flops"] == 3 * 2 * 100 * 12 * 2
    assert need["bytes"] == 3 * (2 * 16 * 4 + 2 * 2 * 4 + 4 * 4) * 12 * 2
    # the one attention layer of the three that run; f32 rows of 4
    flash = fg.flash_train_step(small, traffic, 1)
    assert flash["flops"] == fg.attention_flops(small, 2, 6)
    assert flash["bytes"] == 6 * (2 * 6 * 4 * 4) * (2 + 1)
    assert fg.train_step(small, traffic, 2) == (
        6 * fg.matmul_params_per_token(small) * 24
        + fg.attention_flops(small, 4, 6)
        + 3 * 2 * 100 * 24 * 2)
