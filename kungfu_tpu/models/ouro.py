"""A looped decoder LM: ONE stack of blocks applied `total_ut_steps`
times on one set of weights, an exit gate read after every pass, and an
objective that weights the passes' cross-entropies by the exit
distribution the gate gives each position (the `ouro` model type; Zhu
et al., "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741).

What no other model here does: a layer's weights are used more than
once in a step, so its gradient is a sum over the passes; the state the
next pass reads is the state the head reads; the head + CE runs once a
pass, on one kernel.

Equations (the plain reference `models/ouro_reference.py` follows the
same ones; `benchmark/configs/ouro-2.6b.json` lists under `assumed`
what the source's config does not settle). Tokens x_1..x_T, E the
embedding, blocks B_1..B_L, N_f the final RMSNorm, W the untied head, g
the exit gate (hidden -> 1, with a bias), R = `total_ut_steps`:

- block (sandwich norm, four RMSNorms): `a = x + N2(Attn(N1(x)))`,
  `y = a + N4(MLP(N3(a)))`. Attn: q, k, v = x Wq, x Wk, x Wv (no
  biases), heads of `head_dim`, rotary on all of q and k, causal
  `softmax(q k^T / sqrt(head_dim)) v`, then Wo. MLP:
  `Wd(silu(Wg x) * Wu x)`.
- passes: `h^0 = E[x]`; for t = 1..R: `h^t = N_f(B_L(..B_1(h^(t-1))))`:
  the normed state is what the next pass, the head and the gate read.
  `logits^t = h^t W`; `lambda_t = sigmoid(g(h^t))` per position.
- exit distribution per position: `p_t = lambda_t prod_{j<t} (1 -
  lambda_j)` for t < R, `p_R = prod_{j<R} (1 - lambda_j)`: it sums to 1,
  and `lambda_R` enters nothing.
- objective: the mean over positions 0..T-2 of `sum_t p_t CE(logits^t,
  x_{i+1}) - beta H(p)`, `H(p) = -sum_t p_t ln p_t`; gradients flow
  through p into the gate and the trunk.

bf16 matmuls and residual stream (`dtype`), f32 parameters; f32 for norm
statistics, rotary angles, the gate's logit, sigmoid, p, H and the
losses. `RMSNorm`, `rotary`, `SwiGLU` and the bias-less `_dense` are
`models/glm_moe.py`'s, as they are.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..ops.flash import FLASH_LSE, FLASH_OUT, flash_attention, flash_plan
from ..trace.scopes import LOOP_EXIT, LOOP_STACK
from .glm_moe import SwiGLU, _dense, _norm, rotary


@dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    num_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    num_layers: int = 48
    total_ut_steps: int = 4         # R: passes over the one stack
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    entropy_beta: float = 0.1
    dtype: Any = jnp.bfloat16
    attention: str = "local"        # local | flash
    remat: bool = False     # recompute each layer application, but `_KEPT`

    def __post_init__(self):
        if self.attention not in ("local", "flash"):
            raise ValueError(f"attention {self.attention!r}")
        if self.head_dim % 2:
            raise ValueError("rotary pairs need an even head size")
        if self.total_ut_steps < 1:
            raise ValueError("total_ut_steps counts the passes: >= 1")


class RotaryAttention(nn.Module):
    """Causal self-attention, rotary on the whole head. The flash
    kernels are `pallas_call`s directly under this module's name: the
    benchmark's `loop_flash_roofline` selects
    `RotaryAttention_<n>/pallas_call`. Two a layer application, a
    forward and ONE backward, at the published T 4096 as at short T
    (`ops.flash.flash_plan`'s "bwd": `stream_fused` behind the resident
    forward there, the head kernels' own up to T 2048)."""

    config: OuroConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        heads = (c.num_heads, c.head_dim)
        q = rotary(_dense(heads, c, "q")(x), c.rope_theta)
        k = rotary(_dense(heads, c, "k")(x), c.rope_theta)
        v = _dense(heads, c, "v")(x)
        if c.attention == "flash":
            out = flash_attention(q, k, v, causal=True)
        else:
            from ..parallel.sequence import _local_attention

            out = _local_attention(q, k, v, causal=True)
        return _dense(c.hidden_size, c, "o", axis=(-2, -1))(out)


class Block(nn.Module):
    config: OuroConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        x = x + _norm(c, "attn_out_norm")(
            RotaryAttention(c)(_norm(c, "attn_norm")(x)))
        return x + _norm(c, "mlp_out_norm")(
            SwiGLU(c, c.intermediate_size, name="mlp")(
                _norm(c, "mlp_norm")(x)))


# what a recomputed layer application keeps beside its input: the two
# residuals of flash's backward that only its forward kernel can make
# (`models/glm_moe.py::_KEPT`, PR 28), so the kernel's forward runs once
# an application and not twice
_KEPT = (FLASH_OUT, FLASH_LSE)


class Stack(nn.Module):
    """One pass: every block once, then the final norm."""

    config: OuroConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        cls = nn.remat(Block, policy=jax.checkpoint_policies
                       .save_only_these_names(*_KEPT)) if c.remat else Block
        for i in range(c.num_layers):
            x = cls(c, name=f"Block_{i}")(x)
        return _norm(c, "final_norm")(x)


class OuroLM(nn.Module):
    """Token ids [B, T] -> (the normed state after every pass
    [R, B, T, H], the exit gate's logit after every pass [R, B, T],
    f32): `ouro_fused_loss` and `ouro_logits` apply the head."""

    config: OuroConfig = OuroConfig()

    @nn.compact
    def __call__(self, token_ids):
        c = self.config
        # the head's kernel lives here so that `init` makes it; the
        # losses read it from the tree (fused head + CE)
        self.param("lm_head", nn.initializers.lecun_normal(),
                   (c.hidden_size, c.vocab_size), jnp.float32)
        stack = Stack(c, name="stack")
        gate = nn.Dense(1, dtype=jnp.float32, precision=lax.Precision.HIGHEST,
                        kernel_init=nn.initializers.zeros, name="exit_gate")
        x = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                     name="embed")(token_ids)

        # unrolled: ONE module called once a pass is one set of weights,
        # and XLA sums each weight's four gradients. Rolled (`nn.scan`
        # over the passes, the stack as its body) the step compiled 2-3
        # times faster into a quarter of the program, ran 1.5% slower at
        # 8 layers (the same at 6) with 0.8 GB more at the peak, and put
        # two `while` operations round everything else on the device's
        # `XLA Ops` line, which readers that sum operations count again
        # (PERF.md section 6, PR 32)
        states = []
        for _ in range(c.total_ut_steps):
            with jax.named_scope(LOOP_STACK):
                x = stack(x)
            states.append(x)
        states = jnp.stack(states)
        with jax.named_scope(LOOP_EXIT):
            gates = gate(states.astype(jnp.float32))[..., 0]
        return states, gates


def exit_distribution(gate_logits):
    """(p [R, ...], H [...]) of the gate's logits [R, ...], in f32 and
    through logs: `ln p_t = ln sigmoid(z_t) + sum_{j<t} ln sigmoid(-z_j)`
    for t < R, the last pass takes what is left."""
    z = gate_logits.astype(jnp.float32)
    zero = jnp.zeros_like(z[:1])
    stayed = jnp.concatenate(
        [zero, jnp.cumsum(jax.nn.log_sigmoid(-z[:-1]), axis=0)])
    log_p = stayed + jnp.concatenate([jax.nn.log_sigmoid(z[:-1]), zero])
    p = jnp.exp(log_p)
    return p, -jnp.sum(p * log_p, axis=0)


def ouro_forward(model: OuroLM, params, token_ids):
    """(logits of every pass [R, B, T, V] in f32 through the plain
    head, lambda [R, B, T]): for tests and evaluation."""
    hidden, gate_logits = model.apply({"params": params}, token_ids)
    logits = jnp.dot(hidden.astype(jnp.float32),
                     params["lm_head"].astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    return logits, jax.nn.sigmoid(gate_logits)


def ouro_logits(model: OuroLM, params, token_ids):
    """The last pass's logits [B, T, V]: what inference without an
    early exit (`early_exit_threshold` 1) reads."""
    return ouro_forward(model, params, token_ids)[0][-1]


def ouro_fused_loss(model: OuroLM, params, token_ids,
                    interpret: bool | None = None):
    """(objective, metrics): the exit-weighted cross-entropies less
    `entropy_beta` times the exit distribution's entropy, every pass's
    head + CE through `ops.fused_ce_rows.fused_cross_entropy_rows` on
    the one head kernel (no [B, T, V] logits; each position's CE is
    weighted by its own p_t, so the rows stay apart). `metrics`, device
    scalars: `ce` [R] the passes' mean CEs, `exit_p` [R] the mean p_t,
    `exit_entropy`: pass `has_aux=True` to the step builder."""
    from ..ops.fused_ce_rows import fused_cross_entropy_rows

    c = model.config
    hidden, gate_logits = model.apply({"params": params}, token_ids)
    targets = token_ids[:, 1:].reshape(-1)
    ce = jnp.stack([
        fused_cross_entropy_rows(
            h[:, :-1].reshape(-1, c.hidden_size), params["lm_head"],
            targets, interpret=interpret) for h in hidden])     # [R, N]
    with jax.named_scope(LOOP_EXIT):
        p, entropy = exit_distribution(
            gate_logits[:, :, :-1].reshape(c.total_ut_steps, -1))
        loss = jnp.mean(jnp.sum(p * ce, axis=0)
                        - c.entropy_beta * entropy)
        metrics = {"ce": ce.mean(axis=1), "exit_p": p.mean(axis=1),
                   "exit_entropy": entropy.mean()}
    return loss, metrics


def loop_plan(c: OuroConfig, batch: int, seq: int):
    """The loop's static counter (the counterpart of
    `ops.flash.flash_plan`): layer applications and head + CE calls a
    step; what recomputation keeps from forward to backward for each
    application, its input and `_KEPT` (flash's two names only where
    attention runs the kernel; `jax.ad_checkpoint.saved_residuals` is
    what the tests hold it to); and the f32 gradients of the stack's
    weights (blocks and final norm), which every pass shares and the
    backward therefore reads and writes once a pass."""
    isz = jnp.dtype(c.dtype).itemsize
    state = batch * seq * c.hidden_size * isz
    kept = {}
    if c.remat:
        kept["input"] = state
        if c.attention == "flash" and "fwd" in flash_plan(
                seq, c.head_dim, dtype=c.dtype, causal=True):
            rows = batch * seq * c.num_heads
            kept[FLASH_OUT] = rows * c.head_dim * isz
            kept[FLASH_LSE] = rows * 4
    h, d = c.hidden_size, c.num_heads * c.head_dim
    layer = 4 * h * d + 3 * h * c.intermediate_size + 4 * h
    applications = c.num_layers * c.total_ut_steps
    per_application = sum(kept.values())
    return {"layer_applications": applications,
            "head_ce_calls": c.total_ut_steps,
            "kept": tuple(kept),
            "kept_bytes_per_application": per_application,
            "kept_bytes": per_application * applications,
            "shared_grad_bytes": 4 * (c.num_layers * layer + h)}
