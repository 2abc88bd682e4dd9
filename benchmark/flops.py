"""Operations and bytes a step needs, computed from shapes.

The yardstick's arithmetic: a per-layer metric divides one of these by
a time read from the device trace. Each function takes the
configuration and the traffic as the cell's files give them and names
nothing of the program. A configuration's file names the function that
counts its train step (`"flops": "flops.gpt_train_step"`), a roofline
metric's file the function that counts its kernel (`"work"`).

Conventions: 2 FLOPs a multiply-accumulate; a train step is forward
plus backward (3 x the forward's matmul work); recomputed operations
do not count.
"""

from __future__ import annotations


def gpt_matmul_params(config: dict) -> int:
    """Parameters that take part in a matmul per token: attention
    projections, the MLP and the lm_head. Embedding lookups are not
    matmuls (`kungfu_tpu/benchmarks/lm.py::_train_mfu`, copied)."""
    h, inner = config["n_embd"], config["n_inner"]
    per_layer = 4 * h * h + 2 * h * inner
    return config["n_layer"] * per_layer + h * config["vocab_size"]


def gpt_flops_per_token(config: dict, seq: int) -> int:
    """PaLM appendix B: 6 FLOPs per matmul parameter per token plus
    the causal attention term 6 * L * h * T."""
    return (6 * gpt_matmul_params(config)
            + 6 * config["n_layer"] * config["n_embd"] * seq)


def gpt_train_step(config: dict, traffic: dict, chips: int) -> int:
    seq = traffic["seq"]
    tokens = traffic["batch_per_chip"] * chips * seq
    return tokens * gpt_flops_per_token(config, seq)


def visible_pairs(t: int, causal: bool) -> int:
    return t * (t + 1) // 2 if causal else t * t


def flash_attention_flops(b: int, t: int, h: int, d: int,
                          causal: bool = True,
                          backward: bool = False) -> int:
    """Matmul FLOPs of one attention call over the visible (q, k)
    pairs only (`kungfu_tpu/ops/flash.py::flash_attention_flops`'
    arithmetic, copied): forward QK^T + PV = 4 * pairs * d; the
    backward's four block matmuls add 8 * pairs * d. The score
    recomputation inside the backward kernel does not count."""
    flops = 4 * b * h * visible_pairs(t, causal) * d
    if backward:
        flops += 8 * b * h * visible_pairs(t, causal) * d
    return flops


def flash_attention_bytes(b: int, t: int, h: int, d: int,
                          itemsize: int = 2,
                          backward: bool = False) -> int:
    """Least HBM traffic of one call: forward reads q, k, v and writes
    o; the backward reads q, k, v, o, do and writes dq, dk, dv. The
    f32 row statistics are a 1/d-th of one tensor and left out."""
    tensor = b * t * h * d * itemsize
    return tensor * (4 + (8 if backward else 0))


def flash_train_step(config: dict, traffic: dict, chips: int) -> dict:
    """Flash attention's work in one train step on ONE chip (the
    kernel's time is read per device): every layer's forward and
    backward call."""
    b, t = traffic["batch_per_chip"], traffic["seq"]
    h = config["n_head"]
    d = config["n_embd"] // h
    layers = config["n_layer"]
    return {
        "flops": layers * flash_attention_flops(b, t, h, d, True, True),
        "bytes": layers * flash_attention_bytes(b, t, h, d, 2, True),
    }


def conv_flops(out_hw: int, k: int, cin: int, cout: int) -> int:
    return 2 * out_hw * out_hw * k * k * cin * cout


def resnet_forward_flops(config: dict) -> int:
    """Convolution and dense FLOPs of one image's forward pass, layer
    by layer from the shapes in the configuration's file (bottleneck
    ResNet, v1.5: the stride sits on the 3x3). Batch norm, ReLU and
    pooling are not matmul work and are left out."""
    hw = config["image_size"]
    f0 = config["num_filters"]
    if config["space_to_depth"]:
        # [H/2, W/2, 12] through a 4x4 conv at stride 1
        hw //= 2
        total = conv_flops(hw, 4, 12, f0)
    else:
        hw //= 2
        total = conv_flops(hw, 7, 3, f0)
    hw //= 2  # 3x3 max pool, stride 2
    cin = f0
    expansion = config["bottleneck_expansion"]
    for i, blocks in enumerate(config["stage_sizes"]):
        f = f0 * 2 ** i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            out_hw = hw // stride
            total += conv_flops(hw, 1, cin, f)
            total += conv_flops(out_hw, 3, f, f)
            total += conv_flops(out_hw, 1, f, f * expansion)
            if j == 0:  # projection shortcut
                total += conv_flops(out_hw, 1, cin, f * expansion)
            cin, hw = f * expansion, out_hw
    return total + 2 * cin * config["num_classes"]


def resnet_train_step(config: dict, traffic: dict, chips: int) -> int:
    images = traffic["batch_per_chip"] * chips
    return 3 * images * resnet_forward_flops(config)
