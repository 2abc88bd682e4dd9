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
  sorted by expert, and the true worst case is `tokens * min(k, count)`
  rows (`buffer_rows`: a token's k experts are distinct, so no routing
  can need more). One grouped matmul a projection
  (`jax.lax.ragged_dot`) runs over the rows the group sizes cover; XLA
  lowers it on the TPU to a Mosaic kernel that walks row tiles by
  group, so ITS work follows the group sizes. Everything else (the
  dispatch and combine gathers, SwiGLU's elementwise passes, the
  cotangents of the rows) is as long as the buffer, and a chip that
  holds `count` of `E` experts uses `count / E` of the worst case on
  average. So the buffer has a **ladder** of static lengths
  (`row_ladder`: twice and four times the expected rows, then
  `buffer_rows`, which always ends it), the routed path is compiled
  once a rung,
  and the device takes, each step and each layer, the smallest rung
  that holds the step's `sum(group_sizes)` (`routed_experts`). The
  top rung IS the worst case, so no routing can lack a row: `dropped`
  is still counted against the worst case, outside the switch, and
  reads 0. A caller that holds every expert has one rung and no
  switch.

What each op reads. Routing is dense over [tokens, k, E] and
[tokens x k, count + 1]: a select and a max for the chosen scores, a
cumulative count for each assignment's row `pos`; no gather, no
scatter, and their transposes as dense. Dispatch gathers the rung's
rows from the tokens. Combine's forward and dispatch's backward are
`_held_sum`: each token sums its held rows (weighed, for combine), slot
by slot, k gathers of [tokens, H] fused into one f32 sum; no
[tokens, k, H] value. Combine's backward gives row r its weight times
its token's cotangent, and each weight the dot of its row with that
cotangent: over the rung in the same pass, read back by slot, or on a
rung past twice the tokens slot by slot (`_held_dots`). Both are
`custom_vjp`s, so autodiff scatters nothing.

Isolated on one TPU v5e (N 8192 tokens, H 2048, bf16 rows, ~4,100 of
them held), combine's forward in ms a call: the [N, k, H] pick with an
f32 einsum, which this replaces, 3.01 / 2.17 at top-8 / top-4 on a
rung of 8,192 rows, 3.05 / 2.00 on 16,384; XLA's scatter-add of the
weighed rows into [N, H] f32 1.08 / 1.77 (by rung); the slot sum 0.92 /
0.48 and 0.87 / 0.43. A Pallas kernel that copies the held rows alone
read 0.77 at 8,192 rows and 1.23 at 16,384 (Mosaic moves no single row
of an (8, 128)-tiled array, so it first copies the rung to f32 [R, 1,
H]); over a layer's forward and backward at top-8 it saved 0.45 ms
against the slot sum, under a percent of the step end to end (PERF.md
section 6), so the slot sum is the one form.

The switch is not differentiated through. JAX's partial evaluation of
`cond` would make the forward switch return the union of every rung's
residuals, each branch filling the other rungs' with zeros (1.2 GB of
zero-fill a layer at 65,536 x 2048). `routed_experts` is ONE
`custom_vjp` with a switch in each half: its residuals are its inputs,
none as long as a rung, and each backward branch rebuilds its rung's
forward (`jax.vjp` of the same branch function) and applies it.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..trace.scopes import MOE_EXPERTS, MOE_ROUTE

# `checkpoint_name` of `routed_experts`' output, [N, H]: a recomputed
# block whose policy keeps it runs no routed forward again (its
# backward rebuilds the rung it needs anyway); inert elsewhere
MOE_ROUTED = "kf.moe_routed"


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
    # scores[n, idx[n, j]] as a select and a max over E: the gather's
    # bits, and a transpose as dense (a select), where a gather's is a
    # scatter-add into [N, E]. A max and not a sum with one nonzero
    # term, which XLA may fold into the sum over j below and so add the
    # k scores in another order
    chosen = jnp.max(jnp.where(idx[:, :, None] == jnp.arange(scores.shape[1]),
                               scores[:, None, :], -jnp.inf), axis=2)
    weights = scaling * chosen / (
        chosen.sum(axis=1, keepdims=True) + 1e-20)
    counts = jnp.sum(jax.nn.one_hot(idx, scores.shape[1],
                                    dtype=jnp.int32), axis=(0, 1))
    return Routing(idx.astype(jnp.int32), weights, counts)


def buffer_rows(tokens: int, k: int, held: Tuple[int, int]) -> int:
    """Rows that hold every held assignment whatever the routing."""
    return tokens * min(k, held[1])


def row_ladder(tokens: int, k: int, held: Tuple[int, int],
               router_width: int) -> Tuple[int, ...]:
    """The static buffer lengths the routed path is compiled at, from
    shapes alone: twice and four times the rows a balanced router sends
    here (`tokens * k * count / router_width`), then the worst case,
    which always ends it. One rung where twice the expected rows reach
    the worst case (every expert held). No rung between four times and
    the worst case: a rung costs a compile of the routed path (1.3 s a
    layer on the chip's compiler) and no step on record needed one
    (PERF.md section 6, PR 35)."""
    top = buffer_rows(tokens, k, held)
    first = 2 * -(-tokens * k * held[1] // router_width)
    return (*(r for r in (first, 2 * first) if r < top), top)


def rung_index(ladder: Tuple[int, ...], group_sizes):
    """The smallest rung that holds every held assignment of the step:
    by the rows held and by nothing else."""
    return jnp.sum(jnp.sum(group_sizes)
                   > jnp.asarray(ladder[:-1], jnp.int32)).astype(jnp.int32)


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
    # the sort's inverse by counting: an assignment's row is where its
    # expert's rows start plus the assignments of that expert before it
    one_hot = jax.nn.one_hot(key, count + 1, dtype=jnp.int32)
    sizes = jnp.sum(one_hot, axis=0)
    before = jnp.cumsum(one_hot, axis=0) - one_hot + (jnp.cumsum(sizes)
                                                       - sizes)
    pos = jnp.sum(one_hot * before, axis=1).reshape(n, k)
    valid = is_held & (pos < rows)
    group_sizes = sizes[:count]
    dropped = jnp.sum(is_held) - jnp.sum(valid)
    return Dispatch(order[:rows], pos, valid, group_sizes,
                    dropped.astype(jnp.int32))


# jitted, as is `_held_dots`: a step calls them in every layer, rung and
# half, and a nested jit is traced once a shape
@jax.jit
def _held_sum(rows, pos, valid, weights=None):
    """[N, H] f32: each token's sum, in ascending j and in f32, of the
    rows of its held slots, `rows[pos[n, j]]` where `valid[n, j]`, each
    first times `weights[n, j]` if given. A token's result depends on
    its own held rows alone, so every rung that holds them gives the
    same bits."""
    y = None
    for j in range(pos.shape[1]):
        term = _slot_rows(rows, pos, valid, j)
        if weights is not None:
            term = weights[:, j, None] * term
        y = term if y is None else y + term
    return y


@jax.jit
def _held_dots(rows, pos, valid, dy):
    """[N, k] f32: `<dy[n], rows[pos[n, j]]>` where `valid[n, j]`, else
    0; slot by slot, k gathers of [N, H] each reduced over H."""
    dy = dy.astype(jnp.float32)
    return jnp.stack([jnp.sum(dy * _slot_rows(rows, pos, valid, j), axis=1)
                      for j in range(pos.shape[1])], axis=1)


def _slot_rows(rows, pos, valid, j):
    """[N, H] f32: the row of slot j of every token, 0 where not held."""
    row = jnp.take(rows, jnp.where(valid[:, j], pos[:, j], 0), axis=0)
    return jnp.where(valid[:, j, None], row.astype(jnp.float32), 0.0)


@jax.custom_vjp
def dispatch_rows(x, row_assign, pos, valid):
    """x [N, H] -> rows [R, H]: row r is the token of assignment
    `row_assign[r]`. Backward: each token sums the cotangents of its
    held assignments' rows (`_held_sum`: no scatter-add)."""
    return x[row_assign // pos.shape[1]]


def _dispatch_fwd(x, row_assign, pos, valid):
    return dispatch_rows(x, row_assign, pos, valid), (pos, valid)


def _dispatch_bwd(res, g):
    pos, valid = res
    dx = _held_sum(g, pos, valid)
    return dx.astype(g.dtype), None, None, None


dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine_rows(rows, weights, row_assign, pos, valid):
    """y[n] = sum over the held assignments j of token n of
    `weights[n, j] * rows[pos[n, j]]`, accumulated in f32
    (`_held_sum`). Backward: row r gets its assignment's weight times
    its token's cotangent (rows past the held ones get zero), each
    weight the dot of its row with the token's cotangent."""
    return _held_sum(rows, pos, valid, weights).astype(rows.dtype)


def _combine_fwd(rows, weights, row_assign, pos, valid):
    return (combine_rows(rows, weights, row_assign, pos, valid),
            (rows, weights, row_assign, pos, valid))


def _combine_bwd(res, dy):
    rows, weights, row_assign, pos, valid = res
    n, k = pos.shape
    flat_w = jnp.where(valid, weights, 0.0).reshape(-1)
    g = dy[row_assign // k].astype(jnp.float32)
    d_rows = flat_w[row_assign][:, None] * g
    if row_assign.shape[0] <= 2 * n:
        # over the rows, in the pass that makes d_rows, read back by slot
        dots = jnp.sum(g * rows.astype(jnp.float32), axis=1)
        d_w = jnp.where(valid, dots[jnp.where(valid, pos, 0)], 0.0)
    else:
        # a rung past twice the tokens: slot by slot. Over the rows there
        # it raised the step's peak (PERF.md section 6)
        d_w = _held_dots(rows, pos, valid, dy)
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


def _routed(rung: int, x, weights, d: Dispatch, w_gate, w_up, w_down):
    """Dispatch -> grouped SwiGLU -> combine on a buffer of `rung` rows:
    right for every step whose held assignments fit it. The two scopes
    open HERE, inside what a switch branches to, so that a trace's
    `conditional` event (which spans its branch) carries neither."""
    row_assign, valid = d.row_assign, d.valid
    if rung < row_assign.shape[0]:
        row_assign, valid = row_assign[:rung], valid & (d.pos < rung)
    with jax.named_scope(MOE_ROUTE):
        rows = dispatch_rows(x, row_assign, d.pos, valid)
    with jax.named_scope(MOE_EXPERTS):
        out = grouped_swiglu(rows, w_gate, w_up, w_down, d.group_sizes)
    with jax.named_scope(MOE_ROUTE):
        return combine_rows(out, weights, row_assign, d.pos, valid)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _laddered(ladder, x, weights, d, w_gate, w_up, w_down):
    with jax.named_scope(MOE_ROUTE):
        rung = rung_index(ladder, d.group_sizes)
    return lax.switch(rung, [partial(_routed, r) for r in ladder],
                      x, weights, d, w_gate, w_up, w_down)


def _laddered_fwd(ladder, *args):
    return _laddered(ladder, *args), args


def _laddered_bwd(ladder, res, dy):
    def branch(rung, dy, x, weights, d, *w):
        _, pull = jax.vjp(
            lambda x, weights, *w: _routed(rung, x, weights, d, *w),
            x, weights, *w)
        return pull(dy)

    with jax.named_scope(MOE_ROUTE):
        rung = rung_index(ladder, res[2].group_sizes)
    dx, dweights, *dw = lax.switch(
        rung, [partial(branch, r) for r in ladder], dy, *res)
    return (dx, dweights, None, *dw)


_laddered.defvjp(_laddered_fwd, _laddered_bwd)


def routed_experts(x, weights, d: Dispatch, w_gate, w_up, w_down,
                   ladder: Tuple[int, ...]):
    """y [N, H]: each token's weighted sum over its held experts'
    SwiGLUs, on the smallest rung of `ladder` that holds the step's
    rows, chosen on the device (module docstring). `ladder[-1]` is
    `d`'s buffer, the worst case; with one rung there is no switch and
    autodiff sees the three calls themselves."""
    if len(ladder) == 1:
        y = _routed(ladder[0], x, weights, d, w_gate, w_up, w_down)
    else:
        y = _laddered(ladder, x, weights, d, w_gate, w_up, w_down)
    return checkpoint_name(y, MOE_ROUTED)


def held_counters(d: Dispatch, ladder: Tuple[int, ...]) -> dict:
    """What a step's routing did to this chip, as device scalars: the
    assignments held, the held experts' largest load over their mean,
    rows of the worst-case buffer used, the rows of the rung the step
    ran on, dropped assignments."""
    held = jnp.sum(d.group_sizes)
    mean = jnp.maximum(held, 1).astype(jnp.float32) / d.group_sizes.shape[0]
    return {
        "held_assignments": held,
        "max_load_over_mean": jnp.max(d.group_sizes) / mean,
        "buffer_rows_used": jnp.minimum(held, ladder[-1]),
        "rung_rows": jnp.asarray(ladder, jnp.int32)[
            rung_index(ladder, d.group_sizes)],
        "dropped": d.dropped,
    }
