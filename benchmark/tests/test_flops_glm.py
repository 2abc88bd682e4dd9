"""`benchmark/flops_glm.py` against values worked by hand, from the
table in the configuration's `deployment` arithmetic (ISSUE 27)."""

import json
import os

import pytest

from benchmark import flops, flops_glm

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAFFIC = {"batch_per_chip": 1, "seq": 8192}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "glm-4.7-flash.json")) as f:
        return json.load(f)


def test_matmul_parameters_a_token(config):
    # q_a 2048x768 + q_b 768x5120 + kv_a 2048x576 + kv_b 512x8960
    # + o 5120x2048
    assert flops_glm.mla_params(config) == (
        1_572_864 + 3_932_160 + 1_179_648 + 4_587_520 + 10_485_760
    ) == 21_757_952
    assert flops_glm.expert_params(config) == 3 * 2048 * 1536 == 9_437_184
    # top-4 of 64 with 8 held: half an expert a token
    assert flops_glm.held_share(config) == 0.5
    assert flops_glm.attention_layers(config) == 6
    assert flops_glm.expert_layers(config) == 5
    want = (6 * 21_757_952                      # six attention layers
            + 3 * 2048 * 10240                  # the dense block
            + 5 * (9_437_184 + 2048 * 64 + 9_437_184 // 2)
            + 4096 * 2048                       # eh_proj
            + 2 * 2048 * 19360)                 # the head, twice
    assert want == 352_583_680
    assert flops_glm.matmul_params_per_token(config) == want


def test_train_step_is_29_7_tflop(config):
    # 6 FLOPs a matmul parameter a token: 2.12 GFLOP a token
    matmul = 6 * 352_583_680 * 8192
    # visible pairs at d = 256, 20 heads, six layers, forward + backward
    pairs = 8192 * 8193 // 2
    attention = 6 * 12 * 20 * pairs * 256
    assert attention == 6 * flops.flash_attention_flops(
        1, 8192, 20, 256, True, backward=True)
    step = flops_glm.train_step(config, TRAFFIC, chips=1)
    assert step == matmul + attention
    assert step == pytest.approx(29.7e12, rel=0.001)
    assert attention / 8192 == pytest.approx(1.51e9, rel=0.003)


def test_kernel_work(config):
    flash = flops_glm.mla_flash_train_step(config, TRAFFIC, 1)
    assert flash["flops"] == 6 * 12 * 20 * (8192 * 8193 // 2) * 256
    # q, k, v, o forward; q, k, v, o, do, dq, dk, dv backward; bf16
    assert flash["bytes"] == 6 * 12 * (8192 * 20 * 256 * 2)
    # compute-bound: 62.8 ms of FLOPs against 7.4 ms of bytes
    assert flash["flops"] / 197e12 == pytest.approx(0.0628, rel=0.01)
    assert flash["bytes"] / 819e9 == pytest.approx(0.0074, rel=0.01)
    moe = flops_glm.moe_expert_train_step(config, TRAFFIC, 1)
    # 4096 expected routed rows + 8192 shared rows a layer, three
    # projections of 2048 x 1536, 2 FLOPs, forward + backward, 5 layers
    assert moe["flops"] == 5 * 3 * 2 * (4096 + 8192) * 9_437_184
    # nine experts' weights in bf16 and the rows in and out, 3 passes
    assert moe["bytes"] == 5 * 3 * 2 * (9 * 9_437_184
                                        + (4096 + 8192) * 2 * 2048)
    assert moe["flops"] / 197e12 > moe["bytes"] / 819e9  # compute-bound
