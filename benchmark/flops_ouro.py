"""Operations and bytes of the `ouro-2.6b` train step on this chip,
from shapes (`flops.py`'s conventions: 2 FLOPs a multiply-accumulate, a
train step is 3 x the forward's matmul work, recomputation does not
count). The one stack of `num_hidden_layers` blocks runs
`total_ut_steps` times on one set of weights, so a token meets every
layer's parameters once a PASS, and the head once a pass too; the exit
gate's hidden -> 1 product (a millionth of the step) is left out.
"""

from __future__ import annotations

from benchmark.flops import flash_attention_bytes, flash_attention_flops


def layer_applications(config: dict) -> int:
    return config["num_hidden_layers"] * config["total_ut_steps"]


def layer_matmul_params(config: dict) -> int:
    """One block's q, k, v, o and its SwiGLU's gate, up, down."""
    h = config["hidden_size"]
    heads = config["num_attention_heads"] * config["head_dim"]
    return 4 * h * heads + 3 * h * config["intermediate_size"]


def matmul_params_per_token(config: dict) -> int:
    """Parameters a token meets in a matmul in one step: every layer
    application's and one head a pass. The embedding lookup is no
    matmul."""
    return (layer_applications(config) * layer_matmul_params(config)
            + config["total_ut_steps"] * config["hidden_size"]
            * config["vocab_size"])


def train_step(config: dict, traffic: dict, chips: int) -> int:
    """What `mfu` divides: 6 FLOPs a matmul parameter a token plus the
    visible attention pairs of every layer application, forward and
    backward."""
    b, t = traffic["batch_per_chip"] * chips, traffic["seq"]
    attention = layer_applications(config) * flash_attention_flops(
        b, t, config["num_attention_heads"], config["head_dim"], True,
        backward=True)
    return 6 * matmul_params_per_token(config) * b * t + attention


def flash_train_step(config: dict, traffic: dict, chips: int) -> dict:
    """The flash calls of one chip's step: every layer application,
    forward and backward, visible pairs only."""
    b, t = traffic["batch_per_chip"], traffic["seq"]
    h, d = config["num_attention_heads"], config["head_dim"]
    n = layer_applications(config)
    return {"flops": n * flash_attention_flops(b, t, h, d, True, True),
            "bytes": n * flash_attention_bytes(b, t, h, d, 2, True)}
