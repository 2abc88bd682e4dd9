"""`benchmark/flops.py` against values worked by hand."""

import json
import os

import pytest

from benchmark import flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def config(name):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{name}.json")) as f:
        return json.load(f)


def test_gpt2_small_flops_per_token():
    c = config("gpt2-small")
    # 12 x (4 x 768^2 + 2 x 768 x 3072) + 768 x 50257
    assert flops.gpt_matmul_params(c) == 123_532_032
    # 6 x 123.53M + 6 x 12 x 768 x 1024 = 797.8 MFLOP a token
    assert flops.gpt_flops_per_token(c, 1024) == 797_815_296
    step = flops.gpt_train_step(
        c, {"batch_per_chip": 8, "seq": 1024}, chips=1)
    assert step == 8192 * 797_815_296


def test_flash_visible_pairs():
    # [8, 12, 1024, 64] causal: 1024 x 1025 / 2 pairs, 4 x pairs x d
    fwd = flops.flash_attention_flops(8, 1024, 12, 64)
    assert fwd == 4 * 8 * 12 * 524_800 * 64 == 12_897_484_800
    both = flops.flash_attention_flops(8, 1024, 12, 64, backward=True)
    assert both == 3 * fwd
    assert flops.flash_attention_flops(1, 4, 1, 1, causal=False) == 64
    work = flops.flash_train_step(
        config("gpt2-small"), {"batch_per_chip": 8, "seq": 1024}, 1)
    assert work["flops"] == 12 * both
    # q, k, v, o forward; q, k, v, o, do, dq, dk, dv backward; bf16
    assert work["bytes"] == 12 * 12 * (8 * 1024 * 12 * 64 * 2)


def test_resnet50_forward_flops():
    c = config("resnet50")
    # the published v1 stem (7x7/2 over 3 channels): 4.09 GMACs with
    # the stride on the 3x3 (v1.5), the figure every ResNet-50 table has
    plain = flops.resnet_forward_flops({**c, "space_to_depth": False})
    assert plain == pytest.approx(2 * 4.09e9, rel=0.005)
    # the space-to-depth stem swaps 118.0M MACs for 154.1M
    s2d = flops.resnet_forward_flops(c)
    assert s2d - plain == 2 * 112 * 112 * 64 * (4 * 4 * 12 - 7 * 7 * 3)
    step = flops.resnet_train_step(c, {"batch_per_chip": 128}, chips=4)
    assert step == 3 * 512 * s2d
