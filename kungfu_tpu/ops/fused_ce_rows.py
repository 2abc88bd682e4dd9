"""Head matmul + cross-entropy of EVERY ROW, on `ops/fused_ce.py`'s
kernels: `fused_cross_entropy` returns the mean over rows, and an
objective that weights each row's loss by something the model computes
for that row (`models/ouro.py`: a position's exit probability) needs the
rows apart, value and cotangent both.

    loss_i = logsumexp_v(x_i . W_v) - x_i . W_t_i

No kernel of its own (as `parallel/vocab_ce.py` drives the same ones on
a vocabulary shard): the forward is `_fwd_pallas`, which always wrote
the per-row logsumexp and target logit and whose mean `fused_ce.py`
takes; the backward is the residual scheme's `_residual_d_pallas` at
scale 1, `d = softmax - onehot` in bf16 over the logits residual (its
target column then set from the row's f32 loss, see `_rows_bwd`), and
the row cotangents `r` enter where they cost [N, H] and not [N, V]
elementwise work: `dx = r * (d W^T)`, `dW = (r * x)^T d`. No bias: the
heads that need this have none (the kernels' bias operand carries the
padding of the vocabulary only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..trace.scopes import FUSED_CE
from .fused_ce import (_PAD_BIAS, _fwd_pallas, _fwd_vmem_bytes,
                       _pick_blocks, _residual_d_pallas, _round_up)


def reference_cross_entropy_rows(hidden, kernel, targets):
    """Plain XLA, f32 logits: the fallback where the shapes do not tile
    (H not a multiple of 128) and what the tests hold the kernels to."""
    logits = jnp.dot(hidden, kernel, preferred_element_type=jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return lse - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _rows_padded(x, w, b, t, bn, bv, interpret):
    return _rows_fwd(x, w, b, t, bn, bv, interpret)[0]


@jax.named_scope(FUSED_CE)
def _rows_fwd(x, w, b, t, bn, bv, interpret):
    logits, lse, tl = _fwd_pallas(x, w, b, t, bn, bv, interpret,
                                  residual=True)
    rows = jnp.where(t >= 0, lse - tl, 0.0)          # [n_pad, 1]
    return rows, (x, w, logits, lse, rows, t)


@jax.named_scope(FUSED_CE)
def _rows_bwd(bn, bv, interpret, res, g):
    x, w, logits, lse, rows, t = res
    d, _ = _residual_d_pallas(jnp.ones((1, 1), jnp.float32), logits, lse,
                              t, bn, bv, interpret)
    # the kernel rebuilds every p from the bf16 residual against the f32
    # lse: a relative error of up to |logit| * 2^-9 on each, harmless
    # where p is small and ruinous in the target's column of a row the
    # model is sure of, where d = p - 1 is what is left of two numbers
    # near 1 (a row at CE 0.004 gets a d_target 3.5 times off; PERF.md
    # section 6, PR 32). That entry is exp(-loss) - 1 from the row's own
    # f32 loss: one element a row, chosen over the kernel's as the two
    # matmuls below read d (an elementwise producer XLA fuses into both:
    # no [N, V] array is written again; a scatter into d costs two)
    cols = lax.broadcasted_iota(jnp.int32, d.shape, 1)
    d = jnp.where(cols == t, jnp.expm1(-rows).astype(d.dtype), d)
    g = g.astype(jnp.float32)                        # [n_pad, 1]
    dw = lax.dot_general((x * g).astype(x.dtype), d,
                         (((0,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)
    dx = lax.dot_general(d, w, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32) * g
    return (dx.astype(x.dtype), dw.astype(w.dtype),
            jnp.zeros((1, w.shape[1]), jnp.float32),
            np.zeros(t.shape, jax.dtypes.float0))


_rows_padded.defvjp(_rows_fwd, _rows_bwd)


def fused_cross_entropy_rows(hidden, kernel, targets,
                             interpret: bool | None = None):
    """Softmax cross-entropy of `hidden @ kernel` against integer
    `targets`, one f32 value a row, differentiable in (hidden, kernel).

    hidden [N, H] (the matmuls run bf16 with f32 accumulation), kernel
    [H, V], targets [N]. It keeps a bf16 [N, V] logits residual from
    forward to backward, as `fused_cross_entropy(residual=True)`."""
    n, h = hidden.shape
    v = kernel.shape[1]
    blocks = _pick_blocks(n, h, v, _fwd_vmem_bytes) if h % 128 == 0 else None
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    with jax.named_scope(FUSED_CE):
        if blocks is None:
            return reference_cross_entropy_rows(hidden, kernel, targets)
        bn, bv = blocks
        n_pad, v_pad = _round_up(n, bn), _round_up(v, bv)
        x = jnp.pad(hidden.astype(jnp.bfloat16), ((0, n_pad - n), (0, 0)))
        w = jnp.pad(kernel.astype(jnp.bfloat16), ((0, 0), (0, v_pad - v)))
        b = jnp.pad(jnp.zeros((1, v), jnp.float32),
                    ((0, 0), (0, v_pad - v)), constant_values=_PAD_BIAS)
        t = jnp.pad(lax.stop_gradient(targets).astype(jnp.int32),
                    (0, n_pad - n), constant_values=-1)[:, None]
    return _rows_padded(x, w, b, t, bn, bv, interpret)[:n, 0]
