"""Operations and bytes of the `granite-4.0-h-micro` train step on this
chip, from shapes (`flops.py`'s conventions: 2 FLOPs a
multiply-accumulate, a train step is 3 x the forward's work,
recomputation does not count). The configuration's file gives the
published widths, the slice of the vocabulary, and `layer_types`, of
which the first `num_hidden_layers` run: `mamba` layers run the SSD
scan, `attention` layers causal attention over every earlier key.

The SSD's count is the scan's own least work at the published chunk,
whatever implements it: per token and layer, forward, (Q/2) N
multiply-accumulates for C B^T (one group: every head shares it), (Q/2)
P H for the masked product, N P H for the chunks' end states and N P H
for the output from the states; its bytes are x, B, C, the step size
(f32) and y once each way, plus their cotangents.
"""

from __future__ import annotations


def layer_kinds(config: dict) -> list:
    return config["layer_types"][:config["num_hidden_layers"]]


def mamba_layers(config: dict) -> int:
    return layer_kinds(config).count("mamba")


def attention_layers(config: dict) -> int:
    return layer_kinds(config).count("attention")


def head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def d_inner(config: dict) -> int:
    return config["mamba_n_heads"] * config["mamba_d_head"]


def mamba_matmul_params(config: dict) -> int:
    """One mixer's `in_proj` (z, x, B, C, dt) and `out_proj`; the conv
    is depthwise, no matmul."""
    h, inner = config["hidden_size"], d_inner(config)
    in_proj = h * (2 * inner + 2 * config["mamba_n_groups"]
                   * config["mamba_d_state"] + config["mamba_n_heads"])
    return in_proj + inner * h


def attention_matmul_params(config: dict) -> int:
    """q and o over the query heads, k and v over the K/V heads."""
    h, d = config["hidden_size"], head_dim(config)
    return (2 * h * config["num_attention_heads"] * d
            + 2 * h * config["num_key_value_heads"] * d)


def matmul_params_per_token(config: dict) -> int:
    """Parameters a token meets in a matmul on this chip: the mixers,
    every layer's SwiGLU, the tied head over the slice. The embedding
    lookup is no matmul."""
    h = config["hidden_size"]
    return (mamba_layers(config) * mamba_matmul_params(config)
            + attention_layers(config) * attention_matmul_params(config)
            + config["num_hidden_layers"] * 3 * h
            * config["shared_intermediate_size"]
            + h * config["vocab_size"])


def attention_flops(config: dict, b: int, t: int) -> int:
    """One attention layer's calls, forward and backward, over the
    visible (causal) pairs: QK^T + PV forward (4 a pair a head a dim)
    and the backward's four block matmuls (8)."""
    return (12 * b * config["num_attention_heads"] * (t * (t + 1) // 2)
            * head_dim(config))


def attention_bytes(config: dict, b: int, t: int) -> int:
    """Least HBM traffic of one attention layer's calls
    (`flops_afmoe.attention_bytes`' count): the forward reads q, k, v
    and writes o; the backward reads q, k, v, o, dO and writes dq, dk,
    dv: six tensors of the query heads' size, six of the K/V heads'."""
    isz = 2 if config["dtype"] == "bfloat16" else 4
    row = b * t * head_dim(config) * isz
    return 6 * row * (config["num_attention_heads"]
                      + config["num_key_value_heads"])


def flash_train_step(config: dict, traffic: dict, chips: int) -> dict:
    """The attention layers' flash calls of one chip's step."""
    b, t = traffic["batch_per_chip"], traffic["seq"]
    n = attention_layers(config)
    return {"flops": n * attention_flops(config, b, t),
            "bytes": n * attention_bytes(config, b, t)}


def ssd_forward_macs_per_token(config: dict) -> int:
    """One layer's SSD forward, a token (module docstring)."""
    q, n = config["mamba_chunk_size"], config["mamba_d_state"]
    ph = d_inner(config)
    return q // 2 * n + q // 2 * ph + 2 * n * ph


def ssd_train_step(config: dict, traffic: dict, chips: int) -> dict:
    """The SSD scans of one chip's step, forward and backward."""
    tokens = traffic["batch_per_chip"] * traffic["seq"]
    layers = mamba_layers(config)
    n, heads = config["mamba_d_state"], config["mamba_n_heads"]
    isz = 2 if config["dtype"] == "bfloat16" else 4
    # x, y [H P] and B, C [N] in the model's dtype, the step size in f32
    row = 2 * d_inner(config) * isz + 2 * n * isz + 4 * heads
    return {"flops": 3 * 2 * ssd_forward_macs_per_token(config) * tokens
            * layers,
            "bytes": 3 * row * tokens * layers}


def train_step(config: dict, traffic: dict, chips: int) -> int:
    """What `mfu` divides: 6 FLOPs a matmul parameter a token, the
    attention layers' visible pairs and the SSD scans, forward and
    backward."""
    b, t = traffic["batch_per_chip"] * chips, traffic["seq"]
    return (6 * matmul_params_per_token(config) * b * t
            + attention_layers(config) * attention_flops(config, b, t)
            + ssd_train_step(config, {"batch_per_chip": b, "seq": t},
                             1)["flops"])
