"""The benchmark's plain reference of the `ouro-2.6b` configuration:
forward pass, objective and (through `jax.grad`) gradients of a looped
decoder LM in straightforward `jax.numpy`, float32 and
`jax.default_matmul_precision("highest")`. It decides `correct` for
the configuration's cells (`adapters/ouro.py::_verify`) and imports
nothing of the program: below this docstring it is one text with
`kungfu_tpu/models/ouro_reference.py`, whose docstring holds the
equations, the memory plan and the meaning of `dtype=jnp.bfloat16`
(`benchmark/tests/test_reference_ouro.py` keeps the two alike).
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


def causal_attention(q, k, v, q_block, remat):
    """q, k, v [T, heads, d] -> [T, heads, d]; the scores of one query
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


def attention(p, x, cfg, q_block, remat):
    theta = cfg["rope_theta"]
    q = rope(jnp.einsum("th,hnd->tnd", x, p["q"]["kernel"]), theta)
    k = rope(jnp.einsum("th,hnd->tnd", x, p["k"]["kernel"]), theta)
    v = jnp.einsum("th,hnd->tnd", x, p["v"]["kernel"])
    o = causal_attention(q, k, v, q_block, remat)
    return jnp.einsum("tnd,ndh->th", o, p["o"]["kernel"])


def swiglu(p, x):
    return (jax.nn.silu(x @ p["gate"]["kernel"])
            * (x @ p["up"]["kernel"])) @ p["down"]["kernel"]


def block(p, x, cfg, q_block, remat):
    """The sandwich: a norm before and a norm after each of the two
    sublayers, the residual round both."""
    eps = cfg["rms_norm_eps"]
    norm = lambda name, y: rms_norm(y, p[name]["scale"], eps)  # noqa: E731
    a = x + norm("attn_out_norm", attention(
        p["RotaryAttention_0"], norm("attn_norm", x), cfg, q_block, remat))
    return a + norm("mlp_out_norm", swiglu(p["mlp"], norm("mlp_norm", a)))


def cross_entropy_rows(hidden, head, targets, row_block, remat):
    """logsumexp(h W) - (h W)[target] of every row, the logits of
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
    return per_row.reshape(-1)[:n]


def exit_distribution(lam):
    """p [R, T] of lambda [R, T]: exit at pass t having stayed through
    every earlier one; the last pass takes the rest whatever its own
    lambda says."""
    stayed = jnp.ones_like(lam[0])
    p = []
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * stayed)
        stayed = stayed * (1 - lam[t])
    return jnp.stack(p + [stayed])


def reference_forward(params, tokens, cfg, dtype=jnp.float32, q_block=512,
                      remat=False):
    """(the normed state after every pass [B, R, T, H], lambda
    [B, R, T]) of token ids [B, T]; batch rows are independent
    sequences."""
    params = jax.tree_util.tree_map(lambda w: w.astype(dtype), params)
    stack, gate = params["stack"], params["exit_gate"]
    eps = cfg["rms_norm_eps"]

    def run_block(p, x):
        return block(p, x, cfg, q_block, remat)

    run = jax.checkpoint(run_block) if remat else run_block

    def sequence(ids):
        x = params["embed"]["embedding"][ids]
        states, lam = [], []
        for _ in range(cfg["total_ut_steps"]):
            for i in range(cfg["num_hidden_layers"]):
                x = run(stack[f"Block_{i}"], x)
            x = rms_norm(x, stack["final_norm"]["scale"], eps)
            states.append(x)
            lam.append(jax.nn.sigmoid(
                (x @ gate["kernel"])[:, 0] + gate["bias"][0]))
        return jnp.stack(states), jnp.stack(lam)

    with jax.default_matmul_precision("highest"):
        states, lam = zip(*[sequence(ids) for ids in tokens])
    return jnp.stack(states), jnp.stack(lam)


def reference_logits(params, tokens, cfg, dtype=jnp.float32, q_block=512):
    """(logits of every pass [B, R, T, V], lambda [B, R, T], p
    [B, R, T])."""
    states, lam = reference_forward(params, tokens, cfg, dtype, q_block)
    with jax.default_matmul_precision("highest"):
        logits = states @ params["lm_head"].astype(dtype)
    return logits, lam, jnp.stack([exit_distribution(x) for x in lam])


def reference_loss(params, tokens, cfg, dtype=jnp.float32, q_block=512,
                   row_block=2048, remat=False):
    """(objective, {"ce" [R], "exit_p" [R], "exit_entropy"}): the
    passes' mean cross-entropies, the mean exit distribution and its
    mean entropy over positions 0..T-2 of every sequence.
    Differentiable in `params`."""
    states, lam = reference_forward(params, tokens, cfg, dtype, q_block,
                                    remat)
    head = params["lm_head"].astype(dtype)
    beta = cfg["entropy_beta"]
    with jax.default_matmul_precision("highest"):
        per_seq = []
        for ids, h, lam_seq in zip(tokens, states, lam):
            ce = jnp.stack([
                cross_entropy_rows(h_t[:-1], head, ids[1:], row_block,
                                   remat) for h_t in h])        # [R, T-1]
            p = exit_distribution(lam_seq[:, :-1])
            entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
            per_seq.append({
                "loss": jnp.mean(jnp.sum(p * ce, axis=0) - beta * entropy),
                "ce": ce.mean(axis=1), "exit_p": p.mean(axis=1),
                "exit_entropy": entropy.mean()})
    out = {k: sum(s[k] for s in per_seq) / len(per_seq)
           for k in per_seq[0]}
    return out.pop("loss"), out
