"""A dropless top-k expert layer that is told which experts it holds.

The third expert layer of the tree (ROADMAP D2) and the one the other
two should fold into: `parallel/expert.py::moe_mlp` and
`models/gpt.py::MoEMLP` are Switch top-1 with a capacity factor and
one-hot dispatch einsums. Here

- the **router is whole**: sigmoid scores over all `E` experts in f32,
  the top `k` of `score + bias` chosen (the bias selects, it does not
  weigh), weights `scaling * s_e / (sum of the chosen s + 1e-20)`;
- the layer **holds** experts `[first, first + count)` (`held`, static)
  and computes the part of the result those give. What the absent
  experts would add is left out: on one chip of an expert-parallel
  group that is this chip's share (model-configs guide, section 4);
  with `held = (0, E)` it is the whole layer, and with the token rows
  exchanged over an `expert` mesh axis the expert-parallel one (not
  run across chips yet: no all-to-all is written here);
- **nothing is dropped**: the (token, held expert) assignments are
  sorted by expert into a row buffer of the true worst case,
  `tokens * min(k, count)` rows (a token's k experts are distinct, so
  no routing can need more), and one grouped matmul a projection
  (`jax.lax.ragged_dot`) runs over the rows the group sizes cover. XLA
  lowers it on the TPU to a Mosaic kernel that walks row tiles by
  group, so the work follows the group sizes and not the buffer
  (PERF.md section 6, PR 27 has the chip's readings). Rows past the
  last group are never read back.

Dispatch and combine are gathers in both directions: each has a
`custom_vjp` whose backward gathers through the inverse permutation,
where autodiff would scatter-add 32k rows.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax


class Routing(NamedTuple):
    idx: jnp.ndarray      # [N, k] int32: the chosen experts, of all E
    weights: jnp.ndarray  # [N, k] f32: differentiable in x and router
    counts: jnp.ndarray   # [E] int32: tokens that chose each expert


class Dispatch(NamedTuple):
    """Where every held assignment sits in the row buffer."""

    row_assign: jnp.ndarray   # [R] int32: flat assignment (n * k + j) of row r
    pos: jnp.ndarray          # [N, k] int32: row of assignment (n, j)
    valid: jnp.ndarray        # [N, k] bool: held here and has a row
    group_sizes: jnp.ndarray  # [count] int32: rows of each held expert
    dropped: jnp.ndarray      # int32: held assignments without a row


def route_sigmoid_topk(x, router, bias, k: int,
                       scaling: float) -> Routing:
    """Sigmoid scores over all experts in f32 (the matmul too: TPU's
    default f32 precision is one bf16 pass), top-k of `score + bias`,
    weights normalised over the chosen scores and scaled."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    _, idx = lax.top_k(scores + lax.stop_gradient(bias), k)
    chosen = jnp.take_along_axis(scores, idx, axis=1)
    weights = scaling * chosen / (
        chosen.sum(axis=1, keepdims=True) + 1e-20)
    counts = jnp.sum(jax.nn.one_hot(idx, scores.shape[1],
                                    dtype=jnp.int32), axis=(0, 1))
    return Routing(idx.astype(jnp.int32), weights, counts)


def buffer_rows(tokens: int, k: int, held: Tuple[int, int]) -> int:
    """Rows that hold every held assignment whatever the routing."""
    return tokens * min(k, held[1])


def plan_dispatch(idx, held: Tuple[int, int]) -> Dispatch:
    """Sort the assignments by held expert (absent experts last) and
    keep the first `buffer_rows` of them. Integer work only."""
    first, count = held
    n, k = idx.shape
    rows = buffer_rows(n, k, held)
    local = idx - first
    is_held = (local >= 0) & (local < count)
    key = jnp.where(is_held, local, count).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    pos = jnp.zeros((n * k,), jnp.int32).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32), unique_indices=True
    ).reshape(n, k)
    valid = is_held & (pos < rows)
    group_sizes = jnp.sum(jax.nn.one_hot(key, count + 1,
                                         dtype=jnp.int32), axis=0)[:count]
    dropped = jnp.sum(is_held) - jnp.sum(valid)
    return Dispatch(order[:rows], pos, valid, group_sizes,
                    dropped.astype(jnp.int32))


def _pick(rows, pos, valid):
    """[N, k, H]: each assignment's row, zero where it has none."""
    picked = rows[jnp.where(valid, pos, 0)]
    return jnp.where(valid[..., None], picked, jnp.zeros((), rows.dtype))


@jax.custom_vjp
def dispatch_rows(x, row_assign, pos, valid):
    """x [N, H] -> rows [R, H]: row r is the token of assignment
    `row_assign[r]`. Backward: each token sums the cotangents of its
    held assignments' rows (a gather, no scatter-add)."""
    return x[row_assign // pos.shape[1]]


def _dispatch_fwd(x, row_assign, pos, valid):
    return dispatch_rows(x, row_assign, pos, valid), (pos, valid)


def _dispatch_bwd(res, g):
    pos, valid = res
    dx = _pick(g, pos, valid).astype(jnp.float32).sum(axis=1)
    return dx.astype(g.dtype), None, None, None


dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine_rows(rows, weights, row_assign, pos, valid):
    """y[n] = sum over the held assignments j of token n of
    `weights[n, j] * rows[pos[n, j]]`, accumulated in f32. Backward:
    row r gets its assignment's weight times its token's cotangent
    (rows past the held ones get zero), each weight the dot of its
    row with the token's cotangent."""
    w = jnp.where(valid, weights, 0.0)
    y = jnp.einsum("nk,nkh->nh", w,
                   _pick(rows, pos, valid).astype(jnp.float32))
    return y.astype(rows.dtype)


def _combine_fwd(rows, weights, row_assign, pos, valid):
    return (combine_rows(rows, weights, row_assign, pos, valid),
            (rows, weights, row_assign, pos, valid))


def _combine_bwd(res, dy):
    rows, weights, row_assign, pos, valid = res
    k = pos.shape[1]
    flat_w = jnp.where(valid, weights, 0.0).reshape(-1)
    d_rows = (flat_w[row_assign][:, None]
              * dy[row_assign // k].astype(jnp.float32))
    d_w = jnp.einsum("nh,nkh->nk", dy.astype(jnp.float32),
                     _pick(rows, pos, valid).astype(jnp.float32))
    return (d_rows.astype(rows.dtype), d_w.astype(weights.dtype),
            None, None, None)


combine_rows.defvjp(_combine_fwd, _combine_bwd)


def grouped_swiglu(rows, w_gate, w_up, w_down, group_sizes):
    """`down(silu(gate x) * up x)` of every row through its group's
    expert: one grouped matmul a projection over [count, ., .] stacks,
    operands in `rows.dtype`, f32 accumulation inside the kernel."""
    dt = rows.dtype

    def grouped(a, w):
        return lax.ragged_dot(a, w.astype(dt), group_sizes,
                              preferred_element_type=dt)

    hidden = jax.nn.silu(grouped(rows, w_gate)) * grouped(rows, w_up)
    return grouped(hidden, w_down)


def held_counters(d: Dispatch, rows: int) -> dict:
    """What a step's routing did to this chip, as device scalars: the
    assignments held, the held experts' largest load over their mean,
    rows of the buffer used (of `rows`), dropped assignments."""
    held = jnp.sum(d.group_sizes)
    mean = jnp.maximum(held, 1).astype(jnp.float32) / d.group_sizes.shape[0]
    return {
        "held_assignments": held,
        "max_load_over_mean": jnp.max(d.group_sizes) / mean,
        "buffer_rows_used": jnp.minimum(held, rows),
        "dropped": d.dropped,
    }
