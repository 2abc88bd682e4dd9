"""`benchmark/flops_afmoe.py` against values worked by hand (ISSUE 34's
arithmetic), and the configuration's file against the catalog's row."""

import json
import os

import pytest

from benchmark import flops, flops_afmoe

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAFFIC = {"batch_per_chip": 1, "seq": 8192}
SLIDING, FULL = "sliding_attention", "full_attention"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-mini.json")) as f:
        return json.load(f)


def test_published_widths_and_the_three_cuts(config):
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["sliding_window"], config["intermediate_size"],
            config["moe_intermediate_size"], config["router_width"],
            config["num_experts_per_tok"], config["num_shared_experts"],
            config["num_dense_layers"]) == (
        2048, 32, 4, 128, 2048, 6144, 1024, 128, 8, 1, 2)
    assert (config["route_scale"], config["rope_theta"],
            config["rms_norm_eps"], config["load_balance_coeff"],
            config["tie_word_embeddings"], config["mup_enabled"],
            config["route_norm"], config["score_func"]) == (
        2.826, 10000, 1e-5, 0.001, False, True, True, "sigmoid")
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 32,
                                   "num_experts": 128,
                                   "vocab_size": 200192}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (8, 8, 200192 // 8)
    # layer_types kept whole, 3 sliding : 1 full; the first 8 run
    assert config["layer_types"] == [SLIDING, SLIDING, SLIDING, FULL] * 8
    assert flops_afmoe.layer_kinds(config) == [
        SLIDING, SLIDING, SLIDING, FULL] * 2
    assert (flops_afmoe.sliding_layers(config),
            flops_afmoe.full_layers(config),
            flops_afmoe.expert_layers(config)) == (6, 2, 6)
    # every parameter: attention with its gate and two QK-norm scales,
    # four norm scales a block, two dense and six expert layers (the
    # router whole, its bias, the shared expert, 8 held experts),
    # embedding and head over the slice, the final norm
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128
    assert attention == 27_263_232
    dense = attention + 4 * 2048 + 3 * 2048 * 6144
    assert dense == 65_020_160
    expert = (attention + 4 * 2048 + 2048 * 128 + 128
              + (1 + 8) * 3 * 2048 * 1024)
    assert expert == 84_156_800
    total = 2 * dense + 6 * expert + 2 * 25024 * 2048 + 2048
    assert total == config["parameters"] == 737_481_472
    assert 16 * total == pytest.approx(11.80e9, rel=0.001)


def test_matmul_parameters_a_token(config):
    assert flops_afmoe.attention_params(config) == 27_262_976
    assert flops_afmoe.expert_params(config) == 6_291_456
    # 8 of 128 held, top-8: half an expert a token here
    assert flops_afmoe.held_share(config) == 0.5
    assert flops_afmoe.matmul_params_per_token(config) == (
        8 * 27_262_976 + 2 * 3 * 2048 * 6144
        + 6 * (6_291_456 + 2048 * 128 + 0.5 * 6_291_456)
        + 2048 * 25024) == 403_046_400


def test_visible_pairs_of_both_kinds_of_layer():
    # ISSUE 34's counts at T 8192
    assert flops_afmoe.visible_pairs(8192, None) == 33_558_528
    assert flops_afmoe.visible_pairs(8192, 2048) == 14_681_088
    assert flops_afmoe.visible_pairs(8192, 2048) == sum(
        min(i + 1, 2048) for i in range(8192))
    # a window wider than the sequence is no window
    assert flops_afmoe.visible_pairs(64, 2048) == 64 * 65 // 2
    assert flops.visible_pairs(8192, True) == 33_558_528


def test_train_step_is_27_4_tflop(config):
    full = flops_afmoe.attention_flops(config, 1, 8192, None)
    local = flops_afmoe.attention_flops(config, 1, 8192, 2048)
    assert full == flops.flash_attention_flops(1, 8192, 32, 128, True,
                                               backward=True)
    assert full == pytest.approx(1.649e12, rel=0.001)
    assert local == 12 * 32 * 14_681_088 * 128
    assert local / full == pytest.approx(0.4375, abs=0.0001)
    step = flops_afmoe.train_step(config, TRAFFIC, chips=1)
    assert step == 6 * 403_046_400 * 8192 + 2 * full + 6 * local
    assert step == pytest.approx(27.44e12, rel=0.001)
    # 139 ms at the bf16 peak; attention's pairs are 28% of it
    assert step / 197e12 == pytest.approx(0.1393, rel=0.001)
    assert (2 * full + 6 * local) / step == pytest.approx(0.278, abs=0.001)


def test_kernel_work(config):
    local = flops_afmoe.window_flash_train_step(config, TRAFFIC, 1)
    full = flops_afmoe.global_flash_train_step(config, TRAFFIC, 1)
    assert local["flops"] == 6 * 12 * 32 * 14_681_088 * 128
    assert full["flops"] == 2 * 12 * 32 * 33_558_528 * 128
    # six tensors of 32 heads and six of 4, bf16, a layer
    layer_bytes = 6 * 8192 * 128 * 2 * (32 + 4)
    assert local["bytes"] == 6 * layer_bytes
    assert full["bytes"] == 2 * layer_bytes
    # compute-bound: 22.0 and 16.7 ms of FLOPs against 3.3 and 1.1 of bytes
    assert local["flops"] / 197e12 == pytest.approx(0.02198, rel=0.001)
    assert full["flops"] / 197e12 == pytest.approx(0.01675, rel=0.001)
    assert local["bytes"] / 819e9 == pytest.approx(0.00332, rel=0.01)
    assert full["bytes"] / 819e9 == pytest.approx(0.00111, rel=0.01)


def test_expert_work(config):
    work = flops_afmoe.moe_expert_train_step(config, TRAFFIC, 1)
    # 8192 tokens meet the shared expert and, expected, half a held one
    rows = 8192 * 1.5
    assert work["flops"] == 6 * 3 * 2 * rows * 6_291_456
    assert work["flops"] / 197e12 == pytest.approx(0.01413, rel=0.001)
    assert work["bytes"] == 6 * 3 * 2 * (9 * 6_291_456 + rows * 2 * 2048)
    # and it is what the glm module counts when given glm's key names
    from benchmark import flops_glm

    as_glm = {"hidden_size": 2048, "moe_intermediate_size": 1024,
              "num_experts_per_tok": 8, "n_routed_experts": 8,
              "router_width": 128, "n_shared_experts": 1,
              "num_hidden_layers": 8, "first_k_dense_replace": 2,
              "num_nextn_predict_layers": 0}
    assert flops_glm.moe_expert_train_step(as_glm, TRAFFIC, 1) == work
