"""`benchmark/flops_ouro.py` against values worked by hand (ISSUE 32's
arithmetic), and the configuration's file against the catalog's row."""

import json
import os

import pytest

from benchmark import flops, flops_ouro

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAFFIC = {"batch_per_chip": 1, "seq": 4096}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro-2.6b.json")) as f:
        return json.load(f)


def test_published_widths_and_the_one_cut(config):
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["intermediate_size"], config["vocab_size"],
            config["total_ut_steps"], config["early_exit_threshold"]) == (
        2048, 16, 16, 128, 5632, 49152, 4, 1)
    assert (config["rope_theta"], config["rms_norm_eps"],
            config["tie_word_embeddings"]) == (1000000, 1e-6, False)
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 48}
    assert len(config["layer_types"]) == 48  # kept whole, as published
    # every parameter: 8 layers (four norms each), embedding and head,
    # the final norm, the exit gate and its bias
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    total = (config["num_hidden_layers"] * layer + 2 * 49152 * 2048
             + 2048 + 2048 + 1)
    assert total == config["parameters"] == 612_438_017
    assert 16 * total == pytest.approx(9.80e9, rel=0.001)


def test_matmul_parameters_a_token(config):
    assert flops_ouro.layer_applications(config) == 32
    assert flops_ouro.layer_matmul_params(config) == 51_380_224
    # 32 applications and four heads
    assert flops_ouro.matmul_params_per_token(config) == (
        32 * 51_380_224 + 4 * 2048 * 49152) == 2_046_820_352


def test_train_step_is_56_9_tflop(config):
    # a layer application: 0.421 TFLOP of matmuls forward
    assert 2 * 51_380_224 * 4096 == pytest.approx(0.421e12, rel=0.001)
    pairs = 4096 * 4097 // 2
    # and 0.069 TFLOP of visible-pair attention forward
    assert 4 * 16 * pairs * 128 == pytest.approx(0.0687e12, rel=0.001)
    attention = 32 * 12 * 16 * pairs * 128
    assert attention == 32 * flops.flash_attention_flops(
        1, 4096, 16, 128, True, backward=True)
    step = flops_ouro.train_step(config, TRAFFIC, chips=1)
    assert step == 6 * 2_046_820_352 * 4096 + attention
    assert step == pytest.approx(56.9e12, rel=0.001)
    # 289 ms at the bf16 peak; the four heads are 17% of it
    assert step / 197e12 == pytest.approx(0.2888, rel=0.001)
    assert 4 * 6 * 2048 * 49152 * 4096 / step == pytest.approx(0.174,
                                                               abs=0.001)


def test_kernel_work(config):
    work = flops_ouro.flash_train_step(config, TRAFFIC, 1)
    assert work["flops"] == 32 * 12 * 16 * (4096 * 4097 // 2) * 128
    # q, k, v, o forward; q, k, v, o, do, dq, dk, dv backward; bf16
    assert work["bytes"] == 32 * 12 * (4096 * 16 * 128 * 2)
    # compute-bound: 33.5 ms of FLOPs against 7.9 ms of bytes
    assert work["flops"] / 197e12 == pytest.approx(0.0335, rel=0.01)
    assert work["bytes"] / 819e9 == pytest.approx(0.0079, rel=0.01)
