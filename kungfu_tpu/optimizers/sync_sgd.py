"""Synchronous SGD: gradient all-reduce before the inner update.

The Horovod-equivalent S-SGD data-parallel optimizer (reference:
srcs/python/kungfu/tensorflow/optimizers/sync_sgd.py:48-79). On TPU the
per-gradient all-reduce graph machinery reduces to a single `pmean` per
leaf, which XLA fuses and schedules onto ICI; no fuse/defuse or NCCL order
negotiation is needed (SURVEY §5.8, §7).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax

from ..ops.collective import all_reduce_mean, bucket_schedule
from ..trace.scopes import GRAD_SYNC


def sync_sgd(
    inner: optax.GradientTransformation, axis_name: str = "data"
) -> optax.GradientTransformation:
    """Wrap `inner` so gradients are cluster-averaged before it runs.

    Use inside a shard_map'd train step:

        tx = sync_sgd(optax.sgd(0.1))
        updates, opt_state = tx.update(grads, opt_state, params)
    """

    def init(params):
        return inner.init(params)

    def update(grads, state, params=None):
        grads = all_reduce_mean(grads, axis_name)
        return inner.update(grads, state, params)

    return optax.GradientTransformation(init, update)


@jax.named_scope(GRAD_SYNC)  # the concatenates and slices too
def bucketed_all_reduce_mean(grads, axis_name: str = "data",
                             bucket_bytes: int = 1 << 20):
    """pmean of a gradient pytree as fixed-byte reverse-order buckets.

    The ICI mirror of the DCN `GradBucketPipeline`: instead of one
    pmean per leaf (hundreds of tiny collectives for a transformer's
    layernorm/bias tail), leaves are concatenated into
    `bucket_schedule`'s dtype-homogeneous, reverse-backward-order
    buckets and each bucket is ONE pmean. XLA sees a handful of
    well-sized collectives it can schedule against the backward
    instead of a fusion puzzle. Bitwise-identical to the per-leaf form:
    psum is elementwise, so bucketing changes the op count, never a
    value. Must be called inside `shard_map`/`pmap` over `axis_name`.
    """
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    flat = [jnp.ravel(l) for l in leaves]
    pieces = [[] for _ in leaves]  # (offset, reduced-slice) per leaf
    for _, spans in bucket_schedule(grads, bucket_bytes):
        bucket = jnp.concatenate([flat[i][o:o + n] for i, o, n in spans])
        red = lax.pmean(bucket, axis_name)
        off = 0
        for i, o, n in spans:
            pieces[i].append((o, red[off:off + n]))
            off += n
    out = []
    for i, l in enumerate(leaves):
        if not pieces[i]:  # zero-size leaf
            out.append(l)
            continue
        parts = [p for _, p in sorted(pieces[i], key=lambda t: t[0])]
        out.append(jnp.reshape(jnp.concatenate(parts), jnp.shape(l)))
    return jax.tree_util.tree_unflatten(treedef, out)


def sync_sgd_bucketed(
    inner: optax.GradientTransformation, axis_name: str = "data",
    bucket_bytes: int = 1 << 20,
) -> optax.GradientTransformation:
    """`sync_sgd` with the gradient pmean bucketed
    (`bucketed_all_reduce_mean`). Same values bit-for-bit; fewer,
    larger collectives on the wire."""

    def init(params):
        return inner.init(params)

    def update(grads, state, params=None):
        grads = bucketed_all_reduce_mean(grads, axis_name, bucket_bytes)
        return inner.update(grads, state, params)

    return optax.GradientTransformation(init, update)
