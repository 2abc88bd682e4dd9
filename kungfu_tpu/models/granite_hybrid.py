"""A decoder LM that mixes Mamba-2 state-space mixers with grouped-head
attention layers that carry no positions (the `granitemoehybrid` model
type, dense: IBM's Granite 4.0-H family; transformers
`models/granitemoehybrid/modeling_granitemoehybrid.py`, whose Mamba
layer is Bamba's Mamba-2 mixer).

What no other model here does: a layer with recurrent state, run as a
chunked scan (`ops/ssd.py`); two kinds of mixer in one stack, by
`layer_types`; Granite's four multipliers; a head tied to the
embedding. The attention layer is `ops.flash.flash_attention` over
grouped K/V heads at a caller-given scale.

Equations (the plain reference `models/granite_hybrid_reference.py`
follows the same ones; `benchmark/configs/granite-4.0-h-micro.json`
lists under `assumed` what the source's config does not settle):

- input: `h = embedding_multiplier * E[ids]` (12).
- layer l: `h = h + r M_l(N1(h))`, then `h = h + r F(N2(h))`, r the
  `residual_multiplier` (0.22); `M_l` is the Mamba-2 mixer or attention
  by `layer_types[l]`; every norm an RMSNorm (eps 1e-5, f32 statistics).
- F: `W_down(silu(u W_gate) * u W_up)`: the source's `input_linear`
  [hidden, 2 x 8192] is gate then up.
- Mamba-2 mixer, d_inner = H P (64 heads of 64), one group, state N 128:
  `[z | xBC | dt] = u W_in` (widths 4096, 4352, 64); `xBC = silu(causal
  depthwise conv_4(xBC) + b)` (channel c at t sees t-3..t, zeros before
  0); `xBC = [x | B | C]`; `Delta = softplus(dt + dt_bias)` a head;
  `A = -exp(A_log)`; then, a head, `S_t = exp(Delta_t A) S_{t-1} +
  Delta_t x_t B_t^T`, `y_t = S_t C_t + D x_t` (`ops.ssd.ssd`, chunks of
  `mamba_chunk_size`); `o = w * rmsnorm(y * silu(z))` over all 4096
  channels in f32, the gate before the norm; `out = o W_out`. No bias
  but the conv's.
- attention: q, k, v from bias-free projections (32 query heads on 8
  K/V heads of hidden / heads = 64), no positions, causal softmax of
  `q k^T * attention_multiplier` (1/64, not 1/sqrt(64)), then `o`.
- output: `logits = N_f(h) E^T / logits_scaling` (8), tied; the loss is
  the mean next-token CE. The fused head divides the normed state by 8
  before the head, which is exact in bf16.

bf16 matmuls and residual stream (`dtype`), f32 parameters; f32 for norm
statistics (the gated norm's whole product), the conv and its SiLU, the
step size, the decays, their sums and the states between chunks, the
residual adds, softmax statistics (the kernels') and the loss.
`RMSNorm`, `SwiGLU` and the bias-less `_dense` are `models/glm_moe.py`'s.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.flash import (FLASH_LSE, FLASH_OUT, _plain_attention,
                         flash_attention, flash_plan)
from ..ops.ssd import ssd, ssd_plan
from ..trace.scopes import SSM
from .glm_moe import SwiGLU, _dense, _norm

MAMBA, ATTENTION = "mamba", "attention"
F32 = jnp.float32


@dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 8
    # one kind a layer; its length is the depth (published: 40)
    layer_types: Tuple[str, ...] = (
        (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4) * 4
    intermediate_size: int = 8192       # `shared_intermediate_size`
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention: str = "local"        # local | flash
    remat: bool = False     # recompute each block backward, but `_KEPT`

    def __post_init__(self):
        if self.attention not in ("local", "flash"):
            raise ValueError(f"attention {self.attention!r}")
        if self.num_heads % self.num_kv_heads or (
                self.hidden_size % self.num_heads):
            raise ValueError("num_kv_heads must divide num_heads, and "
                             "num_heads hidden_size")
        if (self.mamba_n_heads * self.mamba_d_head
                != self.mamba_expand * self.hidden_size):
            raise ValueError("mamba_n_heads * mamba_d_head must be "
                             "mamba_expand * hidden_size")
        if self.mamba_n_groups != 1:
            raise ValueError("ops/ssd.py reads one group of B and C")
        if set(self.layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(f"layer_types {self.layer_types!r}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state


def causal_conv(x, kernel, bias):
    """Depthwise causal conv of x [B, T, C] in f32: channel c at t is
    `bias_c + sum_k kernel[k, c] x[t - K + 1 + k, c]`, zeros before 0."""
    k, t = kernel.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(F32), ((0, 0), (k - 1, 0), (0, 0)))
    return bias + sum(kernel[i] * xp[:, i:i + t] for i in range(k))


def _uniform(bound):
    return lambda key, shape, dtype=F32: jax.random.uniform(
        key, shape, dtype, -bound, bound)


# The step size and decay rates the Mamba-2 authors train from
# (state-spaces/mamba, `mamba_ssm/modules/mamba2.py`): a head's step
# softplus(dt_bias) log-uniform in [1e-3, 1e-1] (floored at 1e-4) and
# A = exp(A_log) uniform in [1, 16]. The slowest heads then keep ~3/4
# of their state over a chunk of 256 positions, so the state carries
# across chunks as it does in a trained model.
DT_RANGE, DT_FLOOR, A_RANGE = (1e-3, 1e-1), 1e-4, (1.0, 16.0)


def _dt_bias_init(key, shape, dtype=F32):
    lo, hi = DT_RANGE
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        key, shape, dtype, jnp.log(lo), jnp.log(hi))), DT_FLOOR)
    return dt + jnp.log(-jnp.expm1(-dt))     # softplus^-1(dt)


def _a_log_init(key, shape, dtype=F32):
    return jnp.log(jax.random.uniform(key, shape, dtype, *A_RANGE))


class GatedRMSNorm(nn.Module):
    """`w * rmsnorm(y * silu(z))`, all of it in f32."""

    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, y, z):
        scale = self.param("scale", nn.initializers.ones, (y.shape[-1],),
                           F32)
        g = y.astype(F32) * nn.silu(z.astype(F32))
        var = jnp.mean(g * g, axis=-1, keepdims=True)
        return (g * jax.lax.rsqrt(var + self.eps) * scale).astype(self.dtype)


class MambaMixer(nn.Module):
    """The Mamba-2 mixer (module docstring). Its leaves take the
    source's names: `in_proj`, the conv's `conv_kernel` [K, C] and
    `conv_bias`, `dt_bias`, `A_log`, `D`, the gated `norm`, `out_proj`."""

    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, u):
        c = self.config
        b, t, _ = u.shape
        h, p, n = c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state
        zxbcdt = _dense(c.d_inner + c.conv_dim + h, c, "in_proj")(u)
        z, xbc, dt = jnp.split(zxbcdt, [c.d_inner, c.d_inner + c.conv_dim],
                               axis=-1)
        # torch's Conv1d init: U(+-1/sqrt(fan_in)), fan_in = K a channel
        bound = c.mamba_d_conv ** -0.5
        kernel = self.param("conv_kernel", _uniform(bound),
                            (c.mamba_d_conv, c.conv_dim), F32)
        bias = self.param("conv_bias", _uniform(bound), (c.conv_dim,), F32)
        xbc = nn.silu(causal_conv(xbc, kernel, bias)).astype(c.dtype)
        x, B, C = jnp.split(xbc, [c.d_inner, c.d_inner + n], axis=-1)
        dt_bias = self.param("dt_bias", _dt_bias_init, (h,), F32)
        a_log = self.param("A_log", _a_log_init, (h,), F32)
        d = self.param("D", nn.initializers.ones, (h,), F32)
        delta = jax.nn.softplus(dt.astype(F32) + dt_bias)
        y, _ = ssd(x.reshape(b, t, h, p), delta, -jnp.exp(a_log), B, C, d,
                   chunk=c.mamba_chunk_size)
        y = GatedRMSNorm(c.rms_norm_eps, c.dtype, name="norm")(
            y.reshape(b, t, c.d_inner), z)
        return _dense(c.hidden_size, c, "out_proj")(y)


class NoPEAttention(nn.Module):
    """Causal attention over grouped K/V heads with no positions, scaled
    by `attention_multiplier`. The flash kernels are `pallas_call`s
    directly under this module's name."""

    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, u):
        c = self.config
        b, t, _ = u.shape
        d = c.head_dim
        q = _dense((c.num_heads, d), c, "q_proj")(u)
        k = _dense((c.num_kv_heads, d), c, "k_proj")(u)
        v = _dense((c.num_kv_heads, d), c, "v_proj")(u)
        if c.attention == "flash":
            out = flash_attention(q, k, v, causal=True,
                                  scale=c.attention_multiplier)
        else:
            out = _plain_attention(q, k, v, True, c.attention_multiplier)
        return _dense(c.hidden_size, c, "o_proj")(out.reshape(b, t, -1))


class Block(nn.Module):
    """Pre-norm residual block, each sublayer's output times
    `residual_multiplier` before its add; `mixer` picks the kind."""

    config: GraniteHybridConfig
    mixer: str

    @nn.compact
    def __call__(self, x):
        c = self.config

        def add(x, y):
            return (x.astype(F32) + c.residual_multiplier
                    * y.astype(F32)).astype(c.dtype)

        if self.mixer == MAMBA:
            with jax.named_scope(SSM):
                m = MambaMixer(c, name="mamba")(
                    _norm(c, "input_layernorm")(x))
        else:
            m = NoPEAttention(c, name="self_attn")(
                _norm(c, "input_layernorm")(x))
        x = add(x, m)
        return add(x, SwiGLU(c, c.intermediate_size, name="shared_mlp")(
            _norm(c, "post_attention_layernorm")(x)))


# what a recomputed block keeps beside its input: the two residuals of
# flash's backward that only its forward kernel can make
# (`models/glm_moe.py::_KEPT`), so the attention layer's forward kernel
# runs once. A Mamba block keeps its input alone: its SSD keeps nothing
# past its own backward (`ops/ssd.py`).
_KEPT = (FLASH_OUT, FLASH_LSE)


class GraniteHybridLM(nn.Module):
    """Token ids [B, T] -> hidden [B, T, H] after the final norm and
    before the tied head: `granite_fused_loss` and `granite_logits`
    apply it. Explicit block names keep the tree the same with and
    without `remat`."""

    config: GraniteHybridConfig = GraniteHybridConfig()

    @nn.compact
    def __call__(self, token_ids):
        c = self.config
        x = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                     name="embed_tokens")(token_ids)
        x = (x.astype(F32) * c.embedding_multiplier).astype(c.dtype)
        cls = nn.remat(Block, policy=jax.checkpoint_policies
                       .save_only_these_names(*_KEPT)) if c.remat else Block
        for i, kind in enumerate(c.layer_types):
            x = cls(c, kind, name=f"Block_{i}")(x)
        return _norm(c, "norm")(x)


def granite_logits(model: GraniteHybridLM, params, token_ids):
    """Logits [B, T, V] in f32 through the plain tied head: for tests
    and evaluation."""
    hidden = model.apply({"params": params}, token_ids)
    table = params["embed_tokens"]["embedding"].astype(F32)
    return jnp.dot(hidden.astype(F32), table.T,
                   precision=jax.lax.Precision.HIGHEST) / (
        model.config.logits_scaling)


def granite_fused_loss(model: GraniteHybridLM, params, token_ids,
                       interpret: bool | None = None):
    """The mean next-token CE through `ops/fused_ce_rows.py` (no
    [B, T, V] logits; its backward takes a row's target column from the
    row's f32 loss): the head is the embedding table transposed, and
    the normed state is divided by `logits_scaling` before it."""
    from ..ops.fused_ce_rows import fused_cross_entropy_rows

    c = model.config
    hidden = model.apply({"params": params}, token_ids)
    x = (hidden[:, :-1].astype(F32) / c.logits_scaling).astype(hidden.dtype)
    return fused_cross_entropy_rows(
        x.reshape(-1, c.hidden_size), params["embed_tokens"]["embedding"].T,
        token_ids[:, 1:].reshape(-1), interpret=interpret).mean()


def layer_plan(c: GraniteHybridConfig, batch: int, seq: int):
    """The stack's static counter: each layer's mixer, the SSD's plan
    (`ops.ssd.ssd_plan`), and what recomputation keeps from forward to
    backward for each block: its input, and in an attention block
    flash's two names where attention runs the kernel
    (`jax.ad_checkpoint.saved_residuals` is what the tests hold it
    to)."""
    isz = jnp.dtype(c.dtype).itemsize
    state = batch * seq * c.hidden_size * isz
    kept, attention_kept = {}, {}
    if c.remat:
        kept["input"] = state
        if c.attention == "flash" and "fwd" in flash_plan(
                seq, c.head_dim, dtype=c.dtype, causal=True):
            rows = batch * seq * c.num_heads
            attention_kept[FLASH_OUT] = rows * c.head_dim * isz
            attention_kept[FLASH_LSE] = rows * 4
    per_block = sum(kept.values())
    per_attention = per_block + sum(attention_kept.values())
    n_attention = c.layer_types.count(ATTENTION)
    return {
        "layers": c.layer_types,
        "ssd": ssd_plan(batch, seq, c.mamba_n_heads, c.mamba_d_head,
                        c.mamba_d_state, c.mamba_chunk_size, dtype=c.dtype),
        "kept": tuple(kept) + (tuple(attention_kept) if n_attention
                               else ()),
        "kept_bytes_per_block": per_block,
        "kept_bytes_per_attention_block": per_attention,
        "kept_bytes": (per_block * (c.num_layers - n_attention)
                       + per_attention * n_attention)}
