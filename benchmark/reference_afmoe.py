"""The benchmark's plain reference of the `trinity-mini` configuration:
forward pass, loss and (through `jax.grad`) gradients of a decoder LM
with sliding and full attention layers over grouped K/V heads, QK-norm,
a gated attention output, sandwich norms and a shared + routed expert
layer, in straightforward `jax.numpy`, float32 and
`jax.default_matmul_precision("highest")`. It decides `correct` for the
configuration's cells (`adapters/afmoe.py::_verify`) and imports nothing
of the program: below this docstring it is one text with
`kungfu_tpu/models/afmoe_reference.py`, whose docstring holds the
equations, the departures, the memory plan and the meaning of
`dtype=jnp.bfloat16` (`benchmark/tests/test_reference_afmoe.py` keeps
the two alike).
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
    """Rotary positions on x [T, heads, d]: pair (2i, 2i + 1) is one
    complex number turned by `position * theta^(-2i/d)`."""
    t, d = x.shape[0], x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(d // 2, dtype=jnp.float32) * 2 / d)
    turn = jnp.exp(1j * jnp.arange(t, dtype=jnp.float32)[:, None] * freq)
    z = x[..., 0::2].astype(jnp.float32) + 1j * x[..., 1::2].astype(
        jnp.float32)
    z = z * turn[:, None, :]
    out = jnp.stack([z.real, z.imag], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def masked_attention(q, k, v, sliding_window, q_block, remat):
    """q, k, v [T, heads, d] -> [T, heads, d]; the scores of one query
    block at a time. Key j is visible to query i iff `0 <= i - j`, and
    with a `sliding_window` also `i - j < sliding_window`."""
    t = q.shape[0]
    q_block = min(q_block, t)
    assert t % q_block == 0, (t, q_block)
    scale = q.shape[-1] ** -0.5
    keys = jnp.arange(t)

    def one(args):
        q_blk, start = args
        s = jnp.einsum("qhd,khd->hqk", q_blk, k) * scale
        diff = (start + jnp.arange(q_block))[:, None] - keys[None, :]
        seen = diff >= 0
        if sliding_window is not None:
            seen &= diff < sliding_window
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    blocks = q.reshape((t // q_block, q_block) + q.shape[1:])
    starts = jnp.arange(0, t, q_block)
    out = jax.lax.map(_maybe_remat(one, remat), (blocks, starts))
    return out.reshape((t,) + out.shape[2:])


def attention(p, x, cfg, sliding, q_block, remat):
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = cfg["rms_norm_eps"]
    q = rms_norm(jnp.einsum("th,hnd->tnd", x, p["q"]["kernel"]),
                 p["q_norm"]["scale"], eps)
    k = rms_norm(jnp.einsum("th,hnd->tnd", x, p["k"]["kernel"]),
                 p["k_norm"]["scale"], eps)
    v = jnp.einsum("th,hnd->tnd", x, p["v"]["kernel"])
    if sliding:
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    # query head n reads K/V head n // (heads / kv_heads)
    k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))
    o = masked_attention(q, k, v,
                         cfg["sliding_window"] if sliding else None,
                         q_block, remat)
    gate = jax.nn.sigmoid(x @ p["gate"]["kernel"])
    return (o.reshape(o.shape[0], -1) * gate) @ p["o"]["kernel"]


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
                            cfg["num_experts_per_tok"], cfg["route_scale"])
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


def block(p, x, cfg, sliding, expert, q_block, remat):
    eps = cfg["rms_norm_eps"]
    attn = attention(
        p["LocalAttention_0" if sliding else "GlobalAttention_0"],
        rms_norm(x, p["attn_norm"]["scale"], eps), cfg, sliding, q_block,
        remat)
    x = x + rms_norm(attn, p["attn_out_norm"]["scale"], eps)
    m = rms_norm(x, p["ffn_norm"]["scale"], eps)
    if expert:
        y, counts = expert_ffn(p["moe"], m, cfg, remat)
    else:
        f = p["mlp"]
        y, counts = swiglu(f["gate"]["kernel"], f["up"]["kernel"],
                           f["down"]["kernel"], m), None
    return x + rms_norm(y, p["ffn_out_norm"]["scale"], eps), counts


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
    """(loss, {"ce", "counts" [expert layers, E]}) of token ids [B, T];
    batch rows are independent sequences and are averaged.
    Differentiable in `params`."""
    params = jax.tree_util.tree_map(lambda w: w.astype(dtype), params)
    kinds = cfg["layer_types"]
    dense = cfg["num_dense_layers"]

    def run_block(p, x, sliding, expert):
        return block(p, x, cfg, sliding, expert, q_block, remat)

    run = (jax.checkpoint(run_block, static_argnums=(2, 3)) if remat
           else run_block)

    def sequence(ids):
        x = params["embed"]["embedding"][ids]
        if cfg["mup_enabled"]:
            x = x * jnp.asarray(x.shape[-1] ** 0.5, x.dtype)
        counts = []
        for i, kind in enumerate(kinds):
            x, c = run(params[f"Block_{i}"], x,
                       kind == "sliding_attention", i >= dense)
            if c is not None:
                counts.append(c)
        x = rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
        ce = cross_entropy(x[:-1], params["lm_head"], ids[1:], row_block,
                           remat)
        return ce, jnp.stack(counts) if counts else None

    with jax.default_matmul_precision("highest"):
        ces, counts = zip(*[sequence(ids) for ids in tokens])
    ce = sum(ces) / len(ces)
    out = {"ce": ce}
    if counts[0] is not None:
        out["counts"] = sum(counts)
    return ce, out
