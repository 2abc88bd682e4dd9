"""A decoder LM that mixes sliding-window and full attention layers over
grouped K/V heads, gates attention's output, and follows its leading
dense blocks with sparse-expert blocks (the `afmoe` model type: Arcee's
Trinity family; transformers `models/afmoe/modeling_afmoe.py`).

What no other model here does: K/V heads fewer than query heads (32 on
4 as published), which `ops.flash.flash_attention` reads by group with
nothing repeated in HBM; TWO kinds of attention layer in one stack,
whose flash calls plan differently in one step (`flash_plan`); a
sliding window on the train path. The expert layer is the tree's one,
`models/glm_moe.py::ExpertFFN` over `parallel/grouped_moe.py`, at
another router width, top-k and scale.

Equations (the plain reference `models/afmoe_reference.py` follows the
same ones; `benchmark/configs/trinity-mini.json` lists under `assumed`
what the source's config does not settle). Layer l has `layer_types[l]`
in {sliding_attention, full_attention}; l < `num_dense_layers` is dense:

- input: `h = sqrt(hidden) * E[ids]` (`mup_enabled`).
- attention, on `a = N1(x)`: `q = a Wq` -> [T, H, d], `k = a Wk`,
  `v = a Wv` -> [T, H_kv, d], `g = a Wg` -> [T, H d]; no biases. `q =
  Nq(q)`, `k = Nk(k)`: RMSNorm over d, one learned scale [d] each.
  SLIDING layers only: rotary (positions 0..T-1) on all of q and k;
  FULL layers carry no positions at all. Query head h reads K/V head
  h // (H / H_kv). Causal softmax of `q k^T / sqrt(d)`; on sliding
  layers key j is visible to query i iff `0 <= i - j < sliding_window`
  (that many keys counting self: the HF mask). `flash_attention`'s
  `window` counts the keys BEFORE self, so it gets `sliding_window -
  1`. `o = (P v) * sigmoid(g)`; `attn = o Wo`.
- block (four RMSNorms, the residual added after the second and the
  fourth): `x = x + N2(attn)`; `x = x + N4(F(N3(x)))`.
- F, dense: `Wd(silu(Wg m) * Wu m)`; F, expert: `SwiGLU_shared(m) + sum
  over the chosen AND held e of w_e SwiGLU_e(m)`, routing as
  `grouped_moe.route_sigmoid_topk` (sigmoid scores in f32, the top k
  of score + selection bias, weights `route_scale * s_e / sum of the
  chosen s`); the selection bias moves by `glm_moe_optimizer`'s
  `sgd(gamma)` on the load sign and takes no gradient.
- output: `logits = N_f(x) W_head` (untied); the loss is the mean
  next-token CE. No auxiliary loss, no MTP module.

bf16 matmuls and residual stream (`dtype`), f32 parameters; f32 for norm
statistics, rotary angles, the output gate's sigmoid and product, router
scores and the top-k, softmax statistics (the kernels') and the loss.
`RMSNorm`, `rotary`, `SwiGLU`, `ExpertFFN` and the bias-less `_dense`
are `models/glm_moe.py`'s, as they are (rotary pairs (2i, 2i + 1) where
HF's `rotate_half` pairs (i, i + d/2): a permutation of weight columns
relates the two).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.flash import (FLASH_LSE, FLASH_OUT, _plain_attention,
                         flash_attention, flash_plan)
from ..parallel.grouped_moe import MOE_ROUTED
from ..trace.scopes import ATTN_GLOBAL, ATTN_LOCAL
from .glm_moe import ExpertFFN, SwiGLU, _dense, _norm, _stack_aux, rotary

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048      # keys a query sees, self included
    # one kind a layer; its length is the depth
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 8
    num_dense_layers: int = 2
    intermediate_size: int = 6144       # the dense blocks' SwiGLU
    moe_intermediate_size: int = 1024   # every expert's, the shared one's
    n_routed_experts: int = 128         # the router's width
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.826    # `route_scale`
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True        # the embedding times sqrt(hidden)
    # experts [first, first + count) live here; the router is whole
    held: Tuple[int, int] = (0, 128)
    dtype: Any = jnp.bfloat16
    attention: str = "local"        # local | flash
    remat: bool = False     # recompute each block backward, but `_KEPT`

    def __post_init__(self):
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.n_routed_experts):
            raise ValueError(f"held {self.held} is not a range of the "
                             f"{self.n_routed_experts} experts")
        if self.attention not in ("local", "flash"):
            raise ValueError(f"attention {self.attention!r}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_kv_heads must divide num_heads")
        if self.head_dim % 2:
            raise ValueError("rotary pairs need an even head size")
        if self.sliding_window < 1:
            raise ValueError("sliding_window counts self: >= 1")
        if set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types {self.layer_types!r}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)


class _GatedAttention(nn.Module):
    """Causal attention over grouped K/V heads with QK-norm and a
    sigmoid gate on its output; `sliding` (a subclass's) adds rotary
    positions and the window. The flash kernels are `pallas_call`s
    directly under the SUBCLASS's name, so a trace tells the two kinds
    of call apart: the benchmark's `window_flash_roofline` selects
    `LocalAttention_<n>/pallas_call`, `global_flash_roofline`
    `GlobalAttention_<n>/pallas_call`."""

    config: AfmoeConfig
    sliding = False

    @nn.compact
    def __call__(self, x):
        c = self.config
        b, t, _ = x.shape
        q = _norm(c, "q_norm")(_dense((c.num_heads, c.head_dim), c, "q")(x))
        k = _norm(c, "k_norm")(
            _dense((c.num_kv_heads, c.head_dim), c, "k")(x))
        v = _dense((c.num_kv_heads, c.head_dim), c, "v")(x)
        gate = _dense(c.num_heads * c.head_dim, c, "gate")(x)
        window = None
        if self.sliding:
            q, k = rotary(q, c.rope_theta), rotary(k, c.rope_theta)
            # HF's mask keeps `sliding_window` keys counting self;
            # `flash_attention`'s `window` counts those before self
            window = c.sliding_window - 1
        if c.attention == "flash":
            out = flash_attention(q, k, v, causal=True, window=window)
        else:
            out = _plain_attention(q, k, v, True, c.head_dim ** -0.5,
                                   window=window)
        out = (out.reshape(b, t, -1).astype(jnp.float32)
               * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(c.dtype)
        return _dense(c.hidden_size, c, "o")(out)


class LocalAttention(_GatedAttention):
    """A sliding-window layer's: rotary positions and the window."""

    sliding = True


class GlobalAttention(_GatedAttention):
    """A full-attention layer's: no positions, every earlier key."""


class Block(nn.Module):
    """Sandwich-norm residual block: `sliding` picks the attention
    kind, `expert` the FFN kind."""

    config: AfmoeConfig
    sliding: bool
    expert: bool

    @nn.compact
    def __call__(self, x):
        c = self.config
        attention = LocalAttention if self.sliding else GlobalAttention
        with jax.named_scope(ATTN_LOCAL if self.sliding else ATTN_GLOBAL):
            x = x + _norm(c, "attn_out_norm")(
                attention(c)(_norm(c, "attn_norm")(x)))
        y = _norm(c, "ffn_norm")(x)
        if self.expert:
            y, aux = ExpertFFN(c, name="moe")(y)
        else:
            y, aux = SwiGLU(c, c.intermediate_size, name="mlp")(y), {}
        return x + _norm(c, "ffn_out_norm")(y), aux


# what a recomputed block keeps beside its input: the two residuals of
# flash's backward that only its forward kernel can make
# (`models/glm_moe.py::_KEPT`, PR 28), so no kernel of either kind of
# call runs twice, and an expert block's routed output (33.5 MB at T
# 8192): the fourth norm's backward reads it, and without the name the
# recomputed forward runs the whole routed path for it, which the
# routed path's own backward never uses (PERF.md section 6, PR 35). q,
# k, v and `o`'s output are NOT kept: at T 8192 they are 151 MB a block
# and the chip is full (PERF.md section 6, PR 34).
_KEPT = (FLASH_OUT, FLASH_LSE, MOE_ROUTED)


class AfmoeLM(nn.Module):
    """Token ids [B, T] -> (hidden [B, T, H] after the final norm and
    before the head, aux): `afmoe_fused_loss` and `afmoe_logits` apply
    the head. Explicit block names keep the tree the same with and
    without `remat`."""

    config: AfmoeConfig = AfmoeConfig()

    @nn.compact
    def __call__(self, token_ids):
        c = self.config
        # the head's kernel lives here so that `init` makes it; the
        # losses read it from the tree (fused head + CE)
        self.param("lm_head", nn.initializers.lecun_normal(),
                   (c.hidden_size, c.vocab_size), jnp.float32)
        x = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                     name="embed")(token_ids)
        if c.mup_enabled:
            x = (x.astype(jnp.float32) * c.hidden_size ** 0.5).astype(
                c.dtype)
        cls = nn.remat(Block, policy=jax.checkpoint_policies
                       .save_only_these_names(*_KEPT)) if c.remat else Block
        auxes = []
        for i, kind in enumerate(c.layer_types):
            x, aux = cls(c, kind == SLIDING, i >= c.num_dense_layers,
                         name=f"Block_{i}")(x)
            auxes.append(aux)
        return _norm(c, "final_norm")(x), _stack_aux(auxes)


def afmoe_logits(model: AfmoeLM, params, token_ids):
    """(logits [B, T, V] in f32 through the plain head, aux): for tests
    and evaluation."""
    hidden, aux = model.apply({"params": params}, token_ids)
    logits = jnp.dot(hidden.astype(jnp.float32),
                     params["lm_head"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return logits, aux


def afmoe_fused_loss(model: AfmoeLM, params, token_ids,
                     interpret: bool | None = None,
                     residual: bool = True):
    """(objective, metrics): the mean next-token CE through the fused
    head + CE kernels (no [B, T, V] logits; `residual=False`: the
    scheme of `ops/fused_ce.py` that keeps no logits at all), plus the
    expert layers' zero-valued bias terms. `metrics` holds the CE and
    the expert layers' counters as device arrays: pass `has_aux=True`
    to the step builder."""
    from ..ops.fused_ce import fused_cross_entropy
    from ..ops.fused_ce_rows import fused_cross_entropy_rows

    c = model.config
    hidden, aux = model.apply({"params": params}, token_ids)
    x = hidden[:, :-1].reshape(-1, c.hidden_size)
    targets = token_ids[:, 1:].reshape(-1)
    if residual:
        # the mean of the ROWS' form: the same kernels, and its backward
        # takes a row's target column from the row's f32 loss, where
        # `fused_cross_entropy`'s rebuilds it from the bf16 logits and
        # is 2-3 times off on rows the model is sure of (ROADMAP D14):
        # after ~55 steps on this model's ring that alone puts layer
        # 0's gradients 0.6 from the reference's (PERF.md section 6,
        # PR 35)
        ce = fused_cross_entropy_rows(x, params["lm_head"], targets,
                                      interpret=interpret).mean()
    else:
        ce = fused_cross_entropy(
            x, params["lm_head"],
            jnp.zeros((c.vocab_size,), jnp.float32),  # the head has no bias
            targets, interpret=interpret, residual=False)
    metrics = {"ce": ce}
    loss = ce
    if aux:
        loss = loss + aux.pop("bias_loss").sum()
        metrics.update(aux)
    return loss, metrics


def visible_pairs(seq: int, window: int | None) -> int:
    """(query, key) pairs a head sees under the causal mask, `window`
    counting the keys before self as `flash_attention`'s does."""
    if window is None:
        return seq * (seq + 1) // 2
    w = min(window, seq - 1)
    return seq * (w + 1) - w * (w + 1) // 2


def layer_plan(c: AfmoeConfig, batch: int, seq: int):
    """The stack's static counter (the counterpart of
    `ops.flash.flash_plan`): each layer's attention and FFN kind, the
    (query, key) pairs a head sees in each kind of attention layer, the
    `window` the sliding layers hand `flash_attention`, and what
    recomputation keeps from forward to backward for each block, its
    input and `_KEPT` (flash's two names only where attention runs the
    kernel: the plain path sets none; the routed output in the expert
    blocks alone, `kept_bytes_per_expert_block`; `jax.ad_checkpoint.
    saved_residuals` is what the tests hold it to)."""
    isz = jnp.dtype(c.dtype).itemsize
    window = c.sliding_window - 1
    kept = {}
    state = batch * seq * c.hidden_size * isz
    if c.remat:
        kept["input"] = state
        if c.attention == "flash" and all("fwd" in flash_plan(
                seq, c.head_dim, dtype=c.dtype, causal=True, window=w)
                for w in {window if kind == SLIDING else None
                          for kind in c.layer_types}):
            rows = batch * seq * c.num_heads
            kept[FLASH_OUT] = rows * c.head_dim * isz
            kept[FLASH_LSE] = rows * 4
    per_block = sum(kept.values())
    experts = c.num_layers - c.num_dense_layers
    routed = state if c.remat and experts else 0
    if routed:
        kept[MOE_ROUTED] = routed
    return {
        "layers": tuple(
            ("sliding" if kind == SLIDING else "full",
             "expert" if i >= c.num_dense_layers else "dense")
            for i, kind in enumerate(c.layer_types)),
        "window": window,
        "visible_pairs": {"full": visible_pairs(seq, None),
                          "sliding": visible_pairs(seq, window)},
        "kept": tuple(kept),
        "kept_bytes_per_block": per_block,
        "kept_bytes_per_expert_block": per_block + routed,
        "kept_bytes": per_block * c.num_layers + routed * experts}
