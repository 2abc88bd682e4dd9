"""Grouped small-leaf optimizer wrapper: one fused update for the tail.

`group_small_leaves` wraps an elementwise optax transformation so that
only leaves below a size threshold — the layernorm scales/biases and
projection biases, ~half the leaf COUNT but <1% of the BYTES — are
concatenated into one streaming update per dtype, while every large
2-D leaf keeps its per-leaf update in its native tiled layout (no
relayout, no serial DUS over big buffers). Conceptually the TPU
analogue of the reference's fused gradient path (reference:
srcs/python/kungfu/tensorflow/optimizers/sync_sgd.py
`nccl_fusion`/fuse): fuse many small per-tensor ops into few big ones.

Why only the tail: the adamw update runs ~3.7x above its HBM floor
because of the LONG TAIL OF SMALL LEAVES, each tiny fusion paying
launch + sub-line HBM overheads (docs/benchmarks.md round-5
attribution). Concatenating the WHOLE tree measured negative on v5e
there (104.6 -> 131.1 ms GPT-2 b=12 step: XLA lowers the 100-leaf
concatenate to a serial dynamic-update-slice loop and relayouts every
2-D tiled leaf to the 1-D linear layout and back); the tail concat is
a few hundred KB.

Correctness: valid for transformations whose update math is
elementwise per parameter (sgd, momentum, adam(w), rmsprop, adafactor
with factored=False). NOT valid inside the wrapper for anything that
couples elements ACROSS the tree — global-norm clipping would see the
tail as one flat vector PER DTYPE GROUP and clip each by its own norm.
Compose such transforms OUTSIDE:
``optax.chain(optax.clip_by_global_norm(c), group_small_leaves(adam))``.
Per-leaf-shape-dependent transforms (factored adafactor, lars/lamb
trust ratios) can never be flattened; wrap those per-leaf.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax


def _flatten_group(leaves, idxs):
    return jnp.concatenate([leaves[i].reshape(-1) for i in idxs])


def _unflatten_group(flat, leaves_like, idxs):
    # static Python offsets: traced split points would fail under jit
    offsets, total = [], 0
    for i in idxs:
        total += leaves_like[i].size
        offsets.append(total)
    parts = jnp.split(flat, offsets[:-1])
    return {i: p.reshape(leaves_like[i].shape)
            for i, p in zip(idxs, parts)}


#: leaves below this many elements join the flattened tail. 64k elems
#: (256 KiB at f32) keeps every GPT layernorm/bias leaf (<= 4*hidden)
#: and the lm_head bias in the tail while every 2-D projection matrix
#: (hidden^2 and up) stays per-leaf in its tiled layout.
SMALL_LEAF_ELEMS = 64 * 1024


class GroupedState(NamedTuple):
    small: Any          # {dtype_str: inner state on the flat tail vec}
    big: Any            # inner state on the tuple of large leaves


def _split_small(leaves, threshold):
    """(small_idxs_by_dtype, big_idxs) partition of leaf indices."""
    small, big = {}, []
    for i, leaf in enumerate(leaves):
        arr = jnp.asarray(leaf)
        if arr.size < threshold:
            small.setdefault(str(arr.dtype), []).append(i)
        else:
            big.append(i)
    return small, big


def group_small_leaves(inner: optax.GradientTransformation,
                       threshold: int = SMALL_LEAF_ELEMS
                       ) -> optax.GradientTransformation:
    """Run `inner` per-leaf on large leaves, fused on the small tail.

    Leaves with fewer than `threshold` elements are concatenated into
    one flat vector per PARAM dtype and updated as a single streaming
    kernel; the rest keep their per-leaf updates (and layouts). The
    update math is bitwise identical to per-leaf `inner` on the whole
    tree for elementwise transformations: concatenation commutes with
    elementwise ops, and the step counter advances identically in
    every partition (one `update` call each per step).

    Compose cross-tree transforms OUTSIDE the wrapper (module
    docstring).
    """

    def init(params):
        leaves, _ = jax.tree_util.tree_flatten(params)
        small, big = _split_small(leaves, threshold)
        return GroupedState(
            small={key: inner.init(_flatten_group(leaves, idxs))
                   for key, idxs in small.items()},
            big=inner.init(tuple(leaves[i] for i in big)),
        )

    def update(updates, state, params=None):
        # param-dtype/param-size partition, exactly as at init:
        # grouping by the grads' dtypes would mismatch the per-group
        # inner states whenever grad dtype differs from param dtype
        # (f32 grads for bf16 params). Without params neither is
        # observable — refuse instead of corrupting the state lookup.
        if params is None:
            raise ValueError(
                "group_small_leaves requires params at update() time: "
                "the partition is keyed by param size/dtype (as at "
                "init)")
        g_leaves, treedef = jax.tree_util.tree_flatten(updates)
        p_leaves, _ = jax.tree_util.tree_flatten(params)
        small, big = _split_small(p_leaves, threshold)
        out = [None] * len(g_leaves)
        new_small = {}
        for key, idxs in small.items():
            flat_u, new_small[key] = inner.update(
                _flatten_group(g_leaves, idxs), state.small[key],
                _flatten_group(p_leaves, idxs))
            for i, u in _unflatten_group(flat_u, g_leaves, idxs).items():
                out[i] = u
        big_u, new_big = inner.update(
            tuple(g_leaves[i] for i in big), state.big,
            tuple(p_leaves[i] for i in big))
        for i, u in zip(big, big_u):
            out[i] = u
        return (jax.tree_util.tree_unflatten(treedef, out),
                GroupedState(small=new_small, big=new_big))

    return optax.GradientTransformation(init, update)
