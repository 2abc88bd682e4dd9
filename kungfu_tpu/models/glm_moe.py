"""A latent-attention, sparse-expert decoder LM with a multi-token-
prediction module: the `glm4_moe_lite` / DeepSeek-V3 block family on
the train path.

What `models/gpt.py` is not (ROADMAP M1-M3, M6, M8): rotary positions
(no position table, so no cap on the sequence), RMSNorm, SwiGLU, no
biases; a layer list of two kinds (`first_k_dense_replace` dense
blocks, then expert blocks); multi-head latent attention (MLA) in its
unabsorbed training form; a dropless sigmoid-routed top-k expert layer
beside a shared expert (`parallel/grouped_moe.py`) that is told which
experts it holds; one MTP module after the trunk.

Layer equations (the plain reference `models/glm_moe_reference.py`
follows the same ones; `benchmark/configs/glm-4.7-flash.json` lists
what the source leaves open under `assumed`):

- MLA: `c_q = RMSNorm(x W_qa)`, `q = c_q W_qb` -> heads x (nope +
  rope); `[c_kv | k_r] = x W_kva`, `c_kv = RMSNorm(c_kv)`,
  `[k_nope | v] = c_kv W_kvb` -> heads x (nope + v). Rotary
  (interleaved pairs) on `q_rope` and on the one `k_r` every head
  shares; `k = [k_nope | k_r]`; causal softmax of `q k^T / sqrt(nope
  + rope)`; `o = (P v) W_o`. With nope + rope == v the per-head q, k,
  v go to `ops.flash.flash_attention` as [B, T, heads, d].
- dense block: `down(silu(gate x) * up x)`.
- expert block: `SwiGLU_shared(x) + sum over the chosen AND held e of
  w_e SwiGLU_e(x)`; routing as `grouped_moe.route_sigmoid_topk`.
- the selection bias is no gradient's business: each expert layer adds
  `b . stop_gradient(sign(c - mean c))` minus its own value to the
  loss (zero, with gradient `sign(c - mean c)`, `c` the step's counts
  over all experts), and `glm_moe_optimizer` gives those leaves
  `sgd(gamma)`: `b_e += gamma * sign(mean c - c_e)` inside the normal
  `tx.update` (DeepSeek-V3 report 2.1.2), every other leaf its adamw.
- MTP (depth 1): `h' = [RMSNorm_h(h) | RMSNorm_e(Emb(t_{i+1}))] W_eh`
  with `h` the trunk's last block output before the final norm, one
  expert block on `h'`, the trunk's final norm and head, CE against
  `t_{i+2}`; objective `CE(trunk) + mtp_lambda * CE(MTP)`. The module
  runs on all T positions (the last two carry no target, the last one
  a filler for its next token), so T stays a length the kernels tile.

bf16 matmuls and residual stream (`dtype`), f32 parameters; f32 where
it matters: norm statistics, rotary angles, router scores and the
top-k, softmax statistics (the kernels'), both losses.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops.flash import FLASH_LSE, FLASH_OUT, flash_attention, flash_plan
from ..parallel import grouped_moe as gm
from ..trace.scopes import MLA, MOE_EXPERTS, MOE_ROUTE, MTP

ROUTER_BIAS = "router_bias"  # the leaf `glm_moe_optimizer` sets apart
# `checkpoint_name`s `MLAttention` sets for the blocks' recomputation
# (`_KEPT`): q, k and v as they enter attention, and the `o` projection
MLA_QKV = "kf.mla_qkv"
MLA_O = "kf.mla_o"


@dataclass(frozen=True)
class GlmMoeConfig:
    vocab_size: int = 154880
    hidden_size: int = 2048
    num_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    intermediate_size: int = 10240      # the dense blocks' SwiGLU
    moe_intermediate_size: int = 1536   # every expert's, the shared one's
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.8
    first_k_dense_replace: int = 1
    num_layers: int = 47                # dense + expert blocks of the trunk
    num_nextn_predict_layers: int = 1   # 0 or 1 MTP module
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    # experts [first, first + count) live here; the router is whole
    held: Tuple[int, int] = (0, 64)
    mtp_lambda: float = 0.3
    dtype: Any = jnp.bfloat16
    attention: str = "local"            # local | flash
    remat: bool = False     # recompute each block backward, but `_KEPT`

    def __post_init__(self):
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.n_routed_experts):
            raise ValueError(f"held {self.held} is not a range of the "
                             f"{self.n_routed_experts} experts")
        if self.attention not in ("local", "flash"):
            raise ValueError(f"attention {self.attention!r}")
        if (self.attention == "flash" and self.v_head_dim
                != self.qk_nope_head_dim + self.qk_rope_head_dim):
            raise ValueError(
                "ops/flash.py takes one head size: flash needs "
                "qk_nope_head_dim + qk_rope_head_dim == v_head_dim")
        if self.qk_rope_head_dim % 2:
            raise ValueError("rotary pairs need an even rope size")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("0 or 1 MTP module")


class RMSNorm(nn.Module):
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones,
                           (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        return (x32 * lax.rsqrt(var + self.eps) * scale).astype(self.dtype)


def rotary(x, theta: float):
    """Rotary positions 0..T-1 on x [B, T, ..., d], interleaved pairs
    (2i, 2i + 1), angles and rotation in f32."""
    t, d = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    shape = (1, t) + (1,) * (x.ndim - 3) + (d // 2,)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _norm(c, name):
    return RMSNorm(c.rms_norm_eps, c.dtype, name=name)


def _dense(features, c, name, axis=-1):
    return nn.DenseGeneral(features, axis=axis, use_bias=False,
                           dtype=c.dtype, name=name)


class MLAttention(nn.Module):
    """Multi-head latent attention, unabsorbed. The flash kernels are
    `pallas_call`s directly under this module's name: the benchmark's
    `mla_flash_roofline` selects `MLAttention_<n>/pallas_call`."""

    config: GlmMoeConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        h, nope, rope = c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim
        c_q = _norm(c, "q_a_norm")(_dense(c.q_lora_rank, c, "q_a")(x))
        q = _dense((h, nope + rope), c, "q_b")(c_q)
        kv = _dense(c.kv_lora_rank + rope, c, "kv_a")(x)
        c_kv = _norm(c, "kv_a_norm")(kv[..., :c.kv_lora_rank])
        k_rope = rotary(kv[..., c.kv_lora_rank:], c.rope_theta)
        kv_up = _dense((h, nope + c.v_head_dim), c, "kv_b")(c_kv)
        q = jnp.concatenate(
            [q[..., :nope], rotary(q[..., nope:], c.rope_theta)], axis=-1)
        k = jnp.concatenate(
            [kv_up[..., :nope],
             jnp.broadcast_to(k_rope[:, :, None, :],
                              k_rope.shape[:2] + (h, rope))], axis=-1)
        v = kv_up[..., nope:]
        q, k, v = (checkpoint_name(x, MLA_QKV) for x in (q, k, v))
        if c.attention == "flash":
            out = flash_attention(q, k, v, causal=True)
        else:
            from ..parallel.sequence import _local_attention

            out = _local_attention(q, k, v, causal=True)
        return checkpoint_name(
            _dense(c.hidden_size, c, "o", axis=(-2, -1))(out), MLA_O)


class SwiGLU(nn.Module):
    config: GlmMoeConfig
    width: int

    @nn.compact
    def __call__(self, x):
        c = self.config
        gate = _dense(self.width, c, "gate")(x)
        up = _dense(self.width, c, "up")(x)
        return _dense(c.hidden_size, c, "down")(nn.silu(gate) * up)


class ExpertFFN(nn.Module):
    """Shared expert + this chip's share of the routed ones. Returns
    (y, aux): the bias' zero-valued loss term, the step's counts over
    all experts, and `grouped_moe.held_counters`.

    The tree's one expert layer: `models/afmoe.py` runs it too. Of its
    `config` it reads `n_routed_experts` (the router's width),
    `num_experts_per_tok`, `routed_scaling_factor`, `held`,
    `moe_intermediate_size`, `n_shared_experts`, and `SwiGLU`'s
    `hidden_size` and `dtype`: any config with those fields serves."""

    config: Any

    @nn.compact
    def __call__(self, x):
        c = self.config
        b, t, h = x.shape
        e, f = c.n_routed_experts, c.moe_intermediate_size
        count = c.held[1]
        router = self.param("router", nn.initializers.normal(h ** -0.5),
                            (h, e), jnp.float32)
        bias = self.param(ROUTER_BIAS, nn.initializers.zeros, (e,),
                          jnp.float32)
        stack = lambda name, shape, fan_in: self.param(  # noqa: E731
            name, nn.initializers.normal(fan_in ** -0.5), shape,
            jnp.float32)
        w_gate = stack("w_gate", (count, h, f), h)
        w_up = stack("w_up", (count, h, f), h)
        w_down = stack("w_down", (count, f, h), f)
        flat = x.reshape(b * t, h)
        ladder = gm.row_ladder(b * t, c.num_experts_per_tok, c.held, e)
        with jax.named_scope(MOE_ROUTE):
            routing = gm.route_sigmoid_topk(
                flat, router, bias, c.num_experts_per_tok,
                c.routed_scaling_factor)
            d = gm.plan_dispatch(routing.idx, c.held)
        with jax.named_scope(MOE_EXPERTS):
            shared = SwiGLU(c, f * c.n_shared_experts, name="shared")(x)
            # cast once, outside the routed path: its backward rebuilds
            # the forward and would cast the stacks again
            stacks = [w.astype(flat.dtype) for w in (w_gate, w_up, w_down)]
        # opens kf.moe_route and kf.moe_experts itself, under its switch
        y = gm.routed_experts(flat, routing.weights, d, *stacks,
                              ladder).reshape(b, t, h)
        counts = routing.counts.astype(jnp.float32)
        load_sign = lax.stop_gradient(jnp.sign(counts - counts.mean()))
        pull = jnp.vdot(bias, load_sign)
        aux = {"bias_loss": pull - lax.stop_gradient(pull),
               "counts": routing.counts,
               **gm.held_counters(d, ladder)}
        return shared + y, aux


class Block(nn.Module):
    """Pre-norm residual block; `expert` picks the FFN kind."""

    config: GlmMoeConfig
    expert: bool

    @nn.compact
    def __call__(self, x):
        c = self.config
        with jax.named_scope(MLA):
            x = x + MLAttention(c)(_norm(c, "attn_norm")(x))
        y = _norm(c, "ffn_norm")(x)
        if self.expert:
            y, aux = ExpertFFN(c, name="moe")(y)
        else:
            y, aux = SwiGLU(c, c.intermediate_size, name="mlp")(y), {}
        return x + y, aux


# what a recomputed block keeps beside its input (`remat_plan` has the
# bytes): the two residuals of flash's backward that only its forward
# kernel can produce, so that no kernel runs twice, and what attention
# reads and hands on, so that of the latent projections only the two
# down-projections and their norms run again. Each name paid for its
# bytes on the chip (PERF.md section 6, PR 28).
_KEPT = (FLASH_OUT, FLASH_LSE, MLA_QKV, MLA_O)


def _block(c: GlmMoeConfig, expert: bool, name: str):
    cls = nn.remat(Block, policy=jax.checkpoint_policies
                   .save_only_these_names(*_KEPT)) if c.remat else Block
    return cls(c, expert, name=name)


def remat_plan(c: GlmMoeConfig, batch: int, seq: int):
    """What the blocks' recomputation holds from forward to backward
    beside each block's input, by name and in bytes (the counterpart of
    `ops.flash.flash_plan`; `jax.ad_checkpoint.saved_residuals` is what
    the tests hold it to). Nothing without `remat`; flash's two names
    only where attention runs the kernel (the plain path sets none)."""
    kept = {}
    if c.remat:
        rows, isz = batch * seq * c.num_heads, jnp.dtype(c.dtype).itemsize
        qk = c.qk_nope_head_dim + c.qk_rope_head_dim
        if c.attention == "flash" and "fwd" in flash_plan(
                seq, c.v_head_dim, dtype=c.dtype, causal=True):
            kept = {FLASH_OUT: rows * c.v_head_dim * isz,
                    FLASH_LSE: rows * 4}
        kept[MLA_QKV] = rows * (2 * qk + c.v_head_dim) * isz
        kept[MLA_O] = batch * seq * c.hidden_size * isz
    blocks = c.num_layers + c.num_nextn_predict_layers
    per_block = sum(kept.values())
    return {"names": tuple(kept), "bytes_per_block": per_block,
            "blocks": blocks, "total_bytes": per_block * blocks}


class MTPModule(nn.Module):
    config: GlmMoeConfig

    @nn.compact
    def __call__(self, h, next_embed):
        c = self.config
        joined = jnp.concatenate(
            [_norm(c, "h_norm")(h), _norm(c, "e_norm")(next_embed)], axis=-1)
        x = _dense(c.hidden_size, c, "eh_proj")(joined)
        return _block(c, True, "block")(x)


class GlmMoeLM(nn.Module):
    """Token ids [B, T] -> (trunk hidden [B, T, H], MTP hidden
    [B, T, H] or None, aux), both hidden states after the final
    norm and before the head: `glm_moe_fused_loss` and `glm_moe_logits`
    apply the head. Explicit block names keep the tree the same with
    and without `remat`."""

    config: GlmMoeConfig = GlmMoeConfig()

    @nn.compact
    def __call__(self, token_ids):
        c = self.config
        embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                         name="embed")
        # the head's kernel lives here so that `init` makes it; the
        # losses read it from the tree (fused head + CE)
        self.param("lm_head", nn.initializers.lecun_normal(),
                   (c.hidden_size, c.vocab_size), jnp.float32)
        final_norm = RMSNorm(c.rms_norm_eps, c.dtype, name="final_norm")
        x = embed(token_ids)
        auxes = []
        for i in range(c.num_layers):
            x, aux = _block(c, i >= c.first_k_dense_replace,
                            f"Block_{i}")(x)
            auxes.append(aux)
        mtp_hidden = None
        if c.num_nextn_predict_layers:
            with jax.named_scope(MTP):
                # all T positions, the last with a filler for its next
                # token (the first one, rolled round): T tiles for the
                # kernels where T - 1 does not, the layer is causal, and
                # the loss takes positions 0..T-3 only
                y, aux = MTPModule(c, name="mtp")(
                    x, embed(jnp.roll(token_ids, -1, axis=1)))
            auxes.append(aux)
            mtp_hidden = final_norm(y)
        return final_norm(x), mtp_hidden, _stack_aux(auxes)


def _stack_aux(auxes):
    """The expert layers' aux dicts, stacked layer by layer (trunk
    order, the MTP module's last)."""
    expert = [a for a in auxes if a]
    if not expert:
        return {}
    return {k: jnp.stack([a[k] for a in expert]) for k in expert[0]}


def glm_moe_logits(model: GlmMoeLM, params, token_ids):
    """(trunk logits [B, T, V], MTP logits [B, T, V] or None, aux)
    in f32 through the plain head: for tests and evaluation."""
    hidden, mtp_hidden, aux = model.apply({"params": params}, token_ids)
    head = params["lm_head"].astype(jnp.float32)
    logits = lambda x: None if x is None else jnp.dot(  # noqa: E731
        x.astype(jnp.float32), head, precision=lax.Precision.HIGHEST)
    return logits(hidden), logits(mtp_hidden), aux


def glm_moe_fused_loss(model: GlmMoeLM, params, token_ids,
                       interpret: bool | None = None,
                       residual: bool = True):
    """(objective, metrics): `CE(trunk, t+1) + mtp_lambda * CE(MTP,
    t+2)` through `ops.fused_ce.fused_cross_entropy` twice (the shared
    head, no [B, T, V] logits), plus the expert layers' zero-valued
    bias terms. `metrics` holds both CE terms and the per-layer
    counters as device arrays: pass `has_aux=True` to the step
    builder."""
    from ..ops.fused_ce import fused_cross_entropy

    c = model.config
    hidden, mtp_hidden, aux = model.apply({"params": params}, token_ids)
    head = params["lm_head"]
    no_bias = jnp.zeros((c.vocab_size,), jnp.float32)

    def ce(x, targets):
        return fused_cross_entropy(
            x.reshape(-1, c.hidden_size), head, no_bias,
            targets.reshape(-1), interpret=interpret, residual=residual)

    metrics = {"ce": ce(hidden[:, :-1], token_ids[:, 1:])}
    loss = metrics["ce"]
    if mtp_hidden is not None:
        metrics["ce_mtp"] = ce(mtp_hidden[:, :-2], token_ids[:, 2:])
        loss = loss + c.mtp_lambda * metrics["ce_mtp"]
    if aux:
        loss = loss + aux.pop("bias_loss").sum()
        metrics.update(aux)
    return loss, metrics


def glm_moe_optimizer(tx, gamma: float):
    """`tx` for every leaf but the routers' selection biases, which
    take `sgd(gamma)` on the load sign the loss hands them as their
    gradient: the whole update rides in one `tx.update`."""
    import optax

    def labels(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, _: "bias" if getattr(
                path[-1], "key", None) == ROUTER_BIAS else "weights",
            params)

    return optax.multi_transform(
        {"weights": tx, "bias": optax.sgd(gamma)}, labels)
