"""Operations and bytes of the `trinity-mini` train step on this chip,
from shapes (`flops.py`'s conventions: 2 FLOPs a multiply-accumulate, a
train step is 3 x the forward's matmul work, recomputation does not
count, an attention call counts its VISIBLE (query, key) pairs: what
the kernels are asked to do, not what they execute). The
configuration's file gives the published widths, the experts held here
(`num_experts`) of the router's `router_width`, the slice of the
vocabulary, and `layer_types`, of which the first `num_hidden_layers`
run: sliding layers see `sliding_window` keys counting self, full
layers every earlier key.
"""

from __future__ import annotations


def layer_kinds(config: dict) -> list:
    return config["layer_types"][:config["num_hidden_layers"]]


def sliding_layers(config: dict) -> int:
    return layer_kinds(config).count("sliding_attention")


def full_layers(config: dict) -> int:
    return layer_kinds(config).count("full_attention")


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["num_dense_layers"]


def attention_params(config: dict) -> int:
    """One layer's q, output gate and o over the query heads, k and v
    over the K/V heads."""
    h, d = config["hidden_size"], config["head_dim"]
    return (3 * h * config["num_attention_heads"] * d
            + 2 * h * config["num_key_value_heads"] * d)


def expert_params(config: dict) -> int:
    """One expert's SwiGLU: gate, up, down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def held_share(config: dict) -> float:
    """Routed experts a token is expected to reach on this chip under
    uniform routing: top-k times held over the router's width."""
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["router_width"])


def matmul_params_per_token(config: dict) -> float:
    """Parameters a token meets in a matmul on this chip, the routed
    experts at their expected share. The embedding lookup is no
    matmul."""
    h = config["hidden_size"]
    expert_layer = (config["num_shared_experts"] * expert_params(config)
                    + h * config["router_width"]
                    + held_share(config) * expert_params(config))
    return (config["num_hidden_layers"] * attention_params(config)
            + config["num_dense_layers"] * 3 * h
            * config["intermediate_size"]
            + expert_layers(config) * expert_layer
            + h * config["vocab_size"])


def visible_pairs(t: int, sliding_window: int | None) -> int:
    """(query, key) pairs a head sees under the causal mask; with a
    `sliding_window` a query sees that many keys counting itself."""
    if sliding_window is None:
        return t * (t + 1) // 2
    w = min(sliding_window, t)
    return t * w - w * (w - 1) // 2


def attention_flops(config: dict, b: int, t: int,
                    sliding_window: int | None) -> int:
    """One layer's attention, forward and backward, over the visible
    pairs: QK^T + PV forward (4 a pair a head a dim) and the
    backward's four block matmuls (8), as `flops.flash_attention_flops`."""
    return (12 * b * config["num_attention_heads"]
            * visible_pairs(t, sliding_window) * config["head_dim"])


def attention_bytes(config: dict, b: int, t: int) -> int:
    """Least HBM traffic of one layer's calls in bf16: the forward
    reads q, k, v and writes o; the backward reads q, k, v, o, dO and
    writes dq, dk, dv: six tensors of the query heads' size, six of
    the K/V heads'."""
    row = b * t * config["head_dim"] * 2
    return 6 * row * (config["num_attention_heads"]
                      + config["num_key_value_heads"])


def train_step(config: dict, traffic: dict, chips: int) -> int:
    """What `mfu` divides: 6 FLOPs a matmul parameter a token plus the
    visible attention pairs of both kinds of layer, forward and
    backward."""
    b, t = traffic["batch_per_chip"] * chips, traffic["seq"]
    attention = (
        sliding_layers(config) * attention_flops(
            config, b, t, config["sliding_window"])
        + full_layers(config) * attention_flops(config, b, t, None))
    return round(6 * matmul_params_per_token(config) * b * t) + attention


def window_flash_train_step(config: dict, traffic: dict,
                            chips: int) -> dict:
    """The sliding layers' flash calls of one chip's step."""
    b, t = traffic["batch_per_chip"], traffic["seq"]
    n = sliding_layers(config)
    return {"flops": n * attention_flops(config, b, t,
                                         config["sliding_window"]),
            "bytes": n * attention_bytes(config, b, t)}


def global_flash_train_step(config: dict, traffic: dict,
                            chips: int) -> dict:
    """The full layers' flash calls of one chip's step."""
    b, t = traffic["batch_per_chip"], traffic["seq"]
    n = full_layers(config)
    return {"flops": n * attention_flops(config, b, t, None),
            "bytes": n * attention_bytes(config, b, t)}


def moe_expert_train_step(config: dict, traffic: dict, chips: int) -> dict:
    """The expert layers' own matmuls on one chip, as
    `flops_glm.moe_expert_train_step` counts them for its
    configuration: the held experts' EXPECTED rows under uniform
    routing (tokens x top-k x held / router width; the step's real rows
    are on the `counters` line) and the shared expert's, three
    projections each, forward and backward. Bytes: the held and shared
    weights in bf16 once for each of the three passes (forward, input
    gradient, weight gradient) and the rows in and out of each."""
    tokens = traffic["batch_per_chip"] * traffic["seq"]
    rows = tokens * (held_share(config) + config["num_shared_experts"])
    weights = ((config["num_experts"] + config["num_shared_experts"])
               * expert_params(config))
    layers = expert_layers(config)
    return {"flops": round(layers * 3 * 2 * rows * expert_params(config)),
            "bytes": round(layers * 3 * 2 * (
                weights + rows * 2 * config["hidden_size"]))}
