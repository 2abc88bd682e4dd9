"""Operations and bytes of the `glm-4.7-flash` train step on this
chip, from shapes (`flops.py`'s conventions: 2 FLOPs a
multiply-accumulate, a train step is 3 x the forward's matmul work,
recomputation does not count). The configuration's file gives the
published widths, the experts held here (`n_routed_experts`) of the
router's `router_width`, the slice of the vocabulary and the depth as
run; the MTP module adds one expert block, `eh_proj` and a second
application of the head.
"""

from __future__ import annotations

from benchmark.flops import flash_attention_bytes, flash_attention_flops


def attention_layers(config: dict) -> int:
    return config["num_hidden_layers"] + config["num_nextn_predict_layers"]


def expert_layers(config: dict) -> int:
    return (config["num_hidden_layers"] - config["first_k_dense_replace"]
            + config["num_nextn_predict_layers"])


def mla_params(config: dict) -> int:
    """One layer's latent attention: q_a, q_b, kv_a, kv_b, o."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v, q_rank, kv_rank = (config["v_head_dim"], config["q_lora_rank"],
                          config["kv_lora_rank"])
    return (h * q_rank + q_rank * heads * (nope + rope)
            + h * (kv_rank + rope) + kv_rank * heads * (nope + v)
            + heads * v * h)


def expert_params(config: dict) -> int:
    """One expert's SwiGLU: gate, up, down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def held_share(config: dict) -> float:
    """Routed experts a token is expected to reach on this chip under
    uniform routing: top-k times held over the router's width."""
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["router_width"])


def matmul_params_per_token(config: dict) -> float:
    """Parameters a token meets in a matmul on this chip, the routed
    experts at their expected share. Embedding lookups are not
    matmuls; the head is applied once by the trunk and once by each
    MTP module."""
    h = config["hidden_size"]
    expert_layer = (config["n_shared_experts"] * expert_params(config)
                    + h * config["router_width"]
                    + held_share(config) * expert_params(config))
    mtp = config["num_nextn_predict_layers"]
    return (attention_layers(config) * mla_params(config)
            + config["first_k_dense_replace"] * 3 * h
            * config["intermediate_size"]
            + expert_layers(config) * expert_layer
            + mtp * 2 * h * h
            + (1 + mtp) * h * config["vocab_size"])


def train_step(config: dict, traffic: dict, chips: int) -> int:
    """What `mfu` divides: 6 FLOPs a matmul parameter a token plus the
    visible attention pairs at the head size the kernels run, forward
    and backward."""
    b, t = traffic["batch_per_chip"] * chips, traffic["seq"]
    attention = attention_layers(config) * flash_attention_flops(
        b, t, config["num_attention_heads"], config["v_head_dim"], True,
        backward=True)
    return round(6 * matmul_params_per_token(config) * b * t) + attention


def mla_flash_train_step(config: dict, traffic: dict, chips: int) -> dict:
    """The flash calls of one chip's step: every attention layer (the
    MTP block's too), forward and backward, visible pairs only."""
    b, t = traffic["batch_per_chip"], traffic["seq"]
    h, d = config["num_attention_heads"], config["v_head_dim"]
    n = attention_layers(config)
    return {"flops": n * flash_attention_flops(b, t, h, d, True, True),
            "bytes": n * flash_attention_bytes(b, t, h, d, 2, True)}


def moe_expert_train_step(config: dict, traffic: dict, chips: int) -> dict:
    """The expert layers' own matmuls on one chip: the held experts'
    EXPECTED rows under uniform routing (tokens x top-k x held / router
    width; the step's real rows are on the `counters` line) and the
    shared expert's, three projections each, forward and backward.
    Bytes: the held and shared weights in bf16 once for each of the
    three passes (forward, input gradient, weight gradient) and the
    rows in and out of each."""
    tokens = traffic["batch_per_chip"] * traffic["seq"]
    rows = tokens * held_share(config) + tokens * config["n_shared_experts"]
    weights = ((config["n_routed_experts"] + config["n_shared_experts"])
               * expert_params(config))
    layers = expert_layers(config)
    return {"flops": round(layers * 3 * 2 * rows * expert_params(config)),
            "bytes": round(layers * 3 * 2 * (
                weights + rows * 2 * config["hidden_size"]))}
