"""The benchmark's own plain reference of the `glm-4.7-flash`
configuration: forward pass, objective and (through `jax.grad`)
gradients in straightforward `jax.numpy`. It decides `correct` in the
cell's `verify` and imports nothing of the program under test
(`kungfu_tpu/models/glm_moe_reference.py` is the program's copy, for its
own tests; `benchmark/tests/test_reference_glm.py` holds the two equal
below this docstring).

float32 and `jax.default_matmul_precision("highest")` by default; no
kernels, no sort-and-group (a dense mask and a loop over the held
experts), no fused head, top-k by repeated argmax. It reads the
parameter tree by its leaf names. It follows the published
`glm4_moe_lite` / DeepSeek-V3 equations as
`benchmark/configs/glm-4.7-flash.json` (`assumed`, `departures`) states
them, the share included: the routed sum runs over the chosen experts
that are held here, and the MTP block runs on all T positions with the
last one's next token a filler.

`cfg` is a plain mapping with the source's key names
(`num_attention_heads`, `q_lora_rank`, `kv_lora_rank`,
`qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`,
`num_experts_per_tok`, `routed_scaling_factor`, `first_k_dense_replace`,
`num_hidden_layers`, `num_nextn_predict_layers`, `rope_theta`,
`rms_norm_eps`) plus `held` (first, count) and `mtp_lambda`; widths
come from the weights' shapes.

Memory, so that T = 8192 at the published widths fits beside the
program's own state: attention by query blocks, the heads' logits by
row blocks, and with `remat=True` each block, each query block and
each expert recomputed in the backward (`jax.checkpoint`: the same
arithmetic, less kept). `dtype=jnp.bfloat16` computes everything in
bf16: that is the reading "one precision below" which the cell's
limits must reject, not a supported mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _maybe_remat(fn, remat):
    return jax.checkpoint(fn) if remat else fn


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale.astype(x.dtype)


def rope(x, theta):
    """Rotary positions on x [T, ..., d]: pair (2i, 2i + 1) is one
    complex number turned by `position * theta^(-2i/d)`."""
    t, d = x.shape[0], x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(d // 2, dtype=jnp.float32) * 2 / d)
    turn = jnp.exp(1j * jnp.arange(t, dtype=jnp.float32)[:, None] * freq)
    turn = turn.reshape((t,) + (1,) * (x.ndim - 2) + (d // 2,))
    z = x[..., 0::2].astype(jnp.float32) + 1j * x[..., 1::2].astype(
        jnp.float32)
    z = z * turn
    out = jnp.stack([z.real, z.imag], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def causal_attention(q, k, v, q_block, remat):
    """q, k [T, h, dk], v [T, h, dv] -> [T, h, dv]; scores of one query
    block at a time."""
    t = q.shape[0]
    q_block = min(q_block, t)
    assert t % q_block == 0, (t, q_block)
    scale = q.shape[-1] ** -0.5
    keys = jnp.arange(t)

    def one(args):
        q_blk, start = args
        s = jnp.einsum("qhd,khd->hqk", q_blk, k) * scale
        rows = start + jnp.arange(q_block)
        s = jnp.where(rows[None, :, None] >= keys[None, None, :], s,
                      -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    blocks = q.reshape((t // q_block, q_block) + q.shape[1:])
    starts = jnp.arange(0, t, q_block)
    out = jax.lax.map(_maybe_remat(one, remat), (blocks, starts))
    return out.reshape((t,) + out.shape[2:])


def mla(p, x, cfg, q_block, remat):
    h = cfg["num_attention_heads"]
    nope = cfg["qk_nope_head_dim"]
    rank = cfg["kv_lora_rank"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    c_q = rms_norm(x @ p["q_a"]["kernel"], p["q_a_norm"]["scale"], eps)
    q = jnp.einsum("tr,rhd->thd", c_q, p["q_b"]["kernel"])
    kv = x @ p["kv_a"]["kernel"]
    c_kv = rms_norm(kv[:, :rank], p["kv_a_norm"]["scale"], eps)
    k_r = rope(kv[:, rank:], theta)
    up = jnp.einsum("tr,rhd->thd", c_kv, p["kv_b"]["kernel"])
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate(
        [up[..., :nope], jnp.repeat(k_r[:, None, :], h, axis=1)], -1)
    o = causal_attention(q, k, up[..., nope:], q_block, remat)
    return jnp.einsum("thd,hdo->to", o, p["o"]["kernel"])


def swiglu(gate, up, down, x):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(x, router, bias, k, scaling):
    """Scores over all experts, the top k of score + bias by repeated
    argmax, weights from the scores alone. Returns the dense [N, E]
    weight of every expert for every token and the counts."""
    scores = jax.nn.sigmoid(x @ router.astype(x.dtype))
    biased = scores + bias.astype(x.dtype)
    chosen = jnp.zeros(scores.shape, bool)
    for _ in range(k):
        best = jnp.argmax(jnp.where(chosen, -jnp.inf, biased), axis=-1)
        chosen = chosen | jax.nn.one_hot(best, scores.shape[1], dtype=bool)
    picked = jnp.where(chosen, scores, 0)
    weights = scaling * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return weights, chosen.sum(axis=0).astype(jnp.int32)


def expert_ffn(p, x, cfg, remat):
    first, count = cfg["held"]
    weights, counts = route(x, p["router"], p["router_bias"],
                            cfg["num_experts_per_tok"],
                            cfg["routed_scaling_factor"])
    s = p["shared"]
    y = swiglu(s["gate"]["kernel"], s["up"]["kernel"], s["down"]["kernel"],
               x)

    def one(y, held):
        gate, up, down, w = held
        return y + w[:, None] * swiglu(gate, up, down, x), None

    mine = weights[:, first:first + count].T  # [count, N]
    y, _ = jax.lax.scan(_maybe_remat(one, remat), y,
                        (p["w_gate"], p["w_up"], p["w_down"], mine))
    return y, counts


def block(p, x, cfg, expert, q_block, remat):
    eps = cfg["rms_norm_eps"]
    x = x + mla(p["MLAttention_0"],
                rms_norm(x, p["attn_norm"]["scale"], eps), cfg, q_block,
                remat)
    y = rms_norm(x, p["ffn_norm"]["scale"], eps)
    if expert:
        y, counts = expert_ffn(p["moe"], y, cfg, remat)
        return x + y, counts
    m = p["mlp"]
    return x + swiglu(m["gate"]["kernel"], m["up"]["kernel"],
                      m["down"]["kernel"], y), None


def cross_entropy(hidden, head, targets, row_block, remat):
    """Mean over rows of logsumexp(h W) - (h W)[target], the logits of
    `row_block` rows at a time."""
    n = hidden.shape[0]
    pad = -n % row_block
    hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
    targets = jnp.pad(targets, (0, pad))

    def one(args):
        h, t = args
        logits = h @ head
        return (jax.nn.logsumexp(logits, axis=-1)
                - jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0])

    per_row = jax.lax.map(
        _maybe_remat(one, remat),
        (hidden.reshape(-1, row_block, hidden.shape[1]),
         targets.reshape(-1, row_block)))
    return jnp.sum(per_row.reshape(-1)[:n]) / n


def reference_loss(params, tokens, cfg, dtype=jnp.float32, q_block=512,
                   row_block=2048, remat=False):
    """(objective, {"ce", "ce_mtp", "counts" [expert layers, E]}) of
    token ids [B, T]; batch rows are independent sequences and are
    averaged. Differentiable in `params`."""
    params = jax.tree_util.tree_map(lambda w: w.astype(dtype), params)
    eps = cfg["rms_norm_eps"]
    layers = cfg["num_hidden_layers"]
    dense = cfg["first_k_dense_replace"]

    def run_block(p, x, expert):
        return block(p, x, cfg, expert, q_block, remat)

    run = (jax.checkpoint(run_block, static_argnums=(2,)) if remat
           else run_block)

    def sequence(ids):
        t = ids.shape[0]
        x = params["embed"]["embedding"][ids]
        counts = []
        for i in range(layers):
            x, c = run(params[f"Block_{i}"], x, i >= dense)
            if c is not None:
                counts.append(c)
        final = params["final_norm"]["scale"]
        head = params["lm_head"]
        ce = cross_entropy(rms_norm(x, final, eps)[:-1], head, ids[1:],
                           row_block, remat)
        out = {"ce": ce}
        loss = ce
        if cfg["num_nextn_predict_layers"]:
            m = params["mtp"]
            nxt = params["embed"]["embedding"][jnp.roll(ids, -1)]
            joined = jnp.concatenate(
                [rms_norm(x, m["h_norm"]["scale"], eps),
                 rms_norm(nxt, m["e_norm"]["scale"], eps)], axis=-1)
            y, c = run(m["block"], joined @ m["eh_proj"]["kernel"], True)
            counts.append(c)
            out["ce_mtp"] = cross_entropy(
                rms_norm(y, final, eps)[:t - 2], head, ids[2:], row_block,
                remat)
            loss = loss + cfg["mtp_lambda"] * out["ce_mtp"]
        out["counts"] = jnp.stack(counts) if counts else None
        return loss, out

    with jax.default_matmul_precision("highest"):
        losses, outs = zip(*[sequence(ids) for ids in tokens])
    mean = lambda xs: sum(xs) / len(xs)  # noqa: E731
    out = {k: mean([o[k] for o in outs]) for k in outs[0]
           if k != "counts"}
    if outs[0]["counts"] is not None:
        out["counts"] = sum(o["counts"] for o in outs)
    return mean(losses), out
