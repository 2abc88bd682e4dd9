"""The benchmark's plain reference of the `granite-4.0-h-micro`
configuration: forward pass, loss and (through `jax.grad`) gradients of
a decoder LM mixing Mamba-2 state-space layers, run one position at a
time, with position-less grouped-head attention layers, a SwiGLU, four
multipliers and a tied head, in straightforward `jax.numpy`, float32 and
`jax.default_matmul_precision("highest")`. It decides `correct` for the
configuration's cells (`adapters/granite_hybrid.py::_verify`) and
imports nothing of the program: below this docstring it is one text
with `kungfu_tpu/models/granite_hybrid_reference.py`, whose docstring
holds the memory plan and the meaning of `dtype=jnp.bfloat16`
(`benchmark/tests/test_reference_granite.py` keeps the two alike).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _maybe_remat(fn, remat):
    return jax.checkpoint(fn) if remat else fn


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale.astype(x.dtype)


def causal_conv(x, kernel, bias):
    """x [T, C]: channel c at t is bias_c + sum_k kernel[k, c]
    x[t - K + 1 + k, c], zeros before position 0."""
    k, t = kernel.shape[0], x.shape[0]
    xp = jnp.pad(x, ((k - 1, 0), (0, 0)))
    out = bias.astype(x.dtype)
    for i in range(k):
        out = out + kernel[i].astype(x.dtype) * xp[i:i + t]
    return out


def recurrence(x, dt, a, b, c, d, segment, remat):
    """x [T, H, P], dt [T, H], a [H], b and c [T, N], d [H] -> y
    [T, H, P], one position at a time; segments of `segment` positions
    are checkpoints when `remat`."""
    t, h, p = x.shape
    n = b.shape[-1]

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, jnp.einsum("hpn,n->hp", state, c_t) + d[:, None] * x_t

    def run(state, inputs):
        return jax.lax.scan(step, state, inputs)

    pad = -t % segment
    # padded positions take zero steps: no decay, nothing added
    inputs = tuple(
        jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1)).reshape(
            (-1, segment) + v.shape[1:]) for v in (x, dt, b, c))
    _, y = jax.lax.scan(_maybe_remat(run, remat),
                        jnp.zeros((h, p, n), x.dtype), inputs)
    return y.reshape((-1, h, p))[:t]


def mamba(p, u, cfg, segment, remat):
    heads, head_dim = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n = cfg["mamba_d_state"]
    inner = heads * head_dim
    zxbcdt = u @ p["in_proj"]["kernel"]
    z = zxbcdt[:, :inner]
    xbc = zxbcdt[:, inner:2 * inner + 2 * n]
    dt = zxbcdt[:, 2 * inner + 2 * n:]
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_kernel"], p["conv_bias"]))
    x, b, c = xbc[:, :inner], xbc[:, inner:inner + n], xbc[:, inner + n:]
    delta = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x.reshape(-1, heads, head_dim), delta,
                   -jnp.exp(p["A_log"]), b, c, p["D"], segment, remat)
    y = y.reshape(-1, inner) * jax.nn.silu(z)
    y = rms_norm(y, p["norm"]["scale"], cfg["rms_norm_eps"])
    return y @ p["out_proj"]["kernel"]


def attention(p, u, cfg, q_block, remat):
    """No positions; query head n reads K/V head n // (heads /
    kv_heads); causal softmax of q k^T * attention_multiplier, the
    scores of `q_block` queries at a time."""
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = jnp.einsum("th,hnd->tnd", u, p["q_proj"]["kernel"])
    k = jnp.einsum("th,hnd->tnd", u, p["k_proj"]["kernel"])
    v = jnp.einsum("th,hnd->tnd", u, p["v_proj"]["kernel"])
    k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))
    t = q.shape[0]
    q_block = min(q_block, t)
    assert t % q_block == 0, (t, q_block)
    keys = jnp.arange(t)

    def one(args):
        q_blk, start = args
        s = jnp.einsum("qhd,khd->hqk", q_blk, k) * cfg["attention_multiplier"]
        seen = (start + jnp.arange(q_block))[:, None] >= keys[None, :]
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(_maybe_remat(one, remat),
                      (q.reshape((t // q_block, q_block) + q.shape[1:]),
                       jnp.arange(0, t, q_block)))
    return out.reshape(t, -1) @ p["o_proj"]["kernel"]


def block(p, x, cfg, kind, q_block, segment, remat):
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    u = rms_norm(x, p["input_layernorm"]["scale"], eps)
    if kind == "mamba":
        m = mamba(p["mamba"], u, cfg, segment, remat)
    else:
        m = attention(p["self_attn"], u, cfg, q_block, remat)
    x = x + r * m
    f = p["shared_mlp"]
    u = rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    return x + r * ((jax.nn.silu(u @ f["gate"]["kernel"])
                     * (u @ f["up"]["kernel"])) @ f["down"]["kernel"])


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


def reference_logits(params, ids, cfg, dtype=jnp.float32, q_block=512,
                     segment=256):
    """Logits [T, V] of one sequence of token ids [T]."""
    params = jax.tree_util.tree_map(lambda w: w.astype(dtype), params)
    with jax.default_matmul_precision("highest"):
        return (_trunk(params, ids, cfg, q_block, segment, False)
                @ _head(params, cfg))


def _head(params, cfg):
    table = params["embed_tokens"]["embedding"]
    return table.T / jnp.asarray(cfg["logits_scaling"], table.dtype)


def _trunk(params, ids, cfg, q_block, segment, remat):
    def run_block(p, x, kind):
        return block(p, x, cfg, kind, q_block, segment, remat)

    run = (jax.checkpoint(run_block, static_argnums=(2,)) if remat
           else run_block)
    x = params["embed_tokens"]["embedding"][ids]
    x = x * jnp.asarray(cfg["embedding_multiplier"], x.dtype)
    for i, kind in enumerate(cfg["layer_types"]):
        x = run(params[f"Block_{i}"], x, kind)
    return rms_norm(x, params["norm"]["scale"], cfg["rms_norm_eps"])


def reference_loss(params, tokens, cfg, dtype=jnp.float32, q_block=512,
                   row_block=2048, segment=256, remat=False):
    """(loss, {"ce"}) of token ids [B, T]: the mean next-token CE, batch
    rows independent sequences averaged. Differentiable in `params`."""
    params = jax.tree_util.tree_map(lambda w: w.astype(dtype), params)

    def sequence(ids):
        x = _trunk(params, ids, cfg, q_block, segment, remat)
        return cross_entropy(x[:-1], _head(params, cfg), ids[1:],
                             row_block, remat)

    with jax.default_matmul_precision("highest"):
        ce = sum(sequence(ids) for ids in tokens) / len(tokens)
    return ce, {"ce": ce}
