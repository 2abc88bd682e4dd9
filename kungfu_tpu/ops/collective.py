"""Collectives over a named mesh axis (ICI data plane).

Equivalents of the reference's collective ops (reference:
srcs/python/kungfu/tensorflow/ops/collective.py, srcs/cpp/src/tensorflow/
ops/cpu/collective.cpp), restated for SPMD JAX: every function takes a
pytree and an `axis_name` and must be called inside `shard_map`/`pmap`
tracing over that axis. XLA lowers psum/all_gather/ppermute directly onto
ICI rings — topology selection (the reference's 7 strategy graphs) is the
compiler's job here, not ours.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..trace.scopes import GRAD_SYNC


def all_reduce(tree, axis_name: str = "data"):
    """Sum each leaf over the mesh axis (reference KungfuAllReduce, sum)."""
    return jax.tree_util.tree_map(lambda x: lax.psum(x, axis_name), tree)


def all_reduce_mean(tree, axis_name: str = "data"):
    """Mean each leaf over the mesh axis — the S-SGD gradient op, and
    what a data-parallel step does to its model state and its loss.
    Under the program's scope `kf.grad_sync` (trace/scopes.py)."""
    with jax.named_scope(GRAD_SYNC):
        return jax.tree_util.tree_map(
            lambda x: lax.pmean(x, axis_name), tree)


def group_all_reduce(tensors: Sequence, axis_name: str = "data") -> List:
    """All-reduce a list of tensors. One psum per tensor, like the
    reference's per-gradient ops; XLA fuses small ones automatically, so
    explicit fusion is an optimization choice, not a correctness one."""
    return [lax.psum(t, axis_name) for t in tensors]


def broadcast(tree, axis_name: str = "data", root: int = 0):
    """Every shard adopts `root`'s value (reference KungfuBroadcast).

    Implemented as mask-then-psum: zero out non-root shards and sum. XLA
    recognises the pattern; cost equals an all-reduce of the tree.
    """

    def bc(x):
        idx = lax.axis_index(axis_name)
        mask = (idx == root).astype(x.dtype)
        return lax.psum(x * mask, axis_name)

    return jax.tree_util.tree_map(bc, tree)


def all_gather(x, axis_name: str = "data", axis: int = 0):
    """Concatenate shards along the existing leading axis (reference
    KungfuAllGather semantics: output leading dim = input dim x cluster
    size)."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=True)


def ring_neighbor(x, axis_name: str = "data", shift: int = 1):
    """Receive the value held by rank (i - shift) mod n — a ring rotation
    via collective_permute. The building block for gossip averaging."""
    n = lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def neighbor_exchange(tree, axis_name: str = "data", shift: int = 1):
    """Rotate a whole pytree around the ring by `shift`."""
    return jax.tree_util.tree_map(
        lambda x: ring_neighbor(x, axis_name, shift), tree
    )


# -- fuse/defuse -------------------------------------------------------------
# The reference packs a model into one flat buffer for fused all-reduce and
# P2P model exchange (reference: srcs/python/kungfu/tensorflow/ops/
# __init__.py:22-39, model_buffer.hpp). Same trick here: one contiguous
# vector minimizes DCN round trips for pair-averaging model transfer.


def fuse(tree) -> jnp.ndarray:
    """Flatten a pytree into one 1-D buffer.

    NOTE: mixed-dtype leaves promote to a common dtype (jnp.concatenate
    semantics) and defuse() casts back — lossless for float hierarchies
    (bf16/f16 under f32) but NOT for large ints/bools. For dtype-exact
    host-side transfer (elastic resync, checkpoints) use
    pack_bytes/unpack_bytes instead.
    """
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((0,), dtype=jnp.float32)
    return jnp.concatenate([jnp.ravel(l) for l in leaves])


def pack_bytes(tree) -> "np.ndarray":
    """Host-side dtype-exact packing: a pytree -> one uint8 numpy buffer."""
    import numpy as np

    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return np.zeros((0,), dtype=np.uint8)
    return np.concatenate(
        [np.ascontiguousarray(np.asarray(l)).view(np.uint8).ravel()
         for l in leaves]
    )


def unpack_bytes(buf, tree_like):
    """Inverse of pack_bytes: uint8 numpy buffer -> pytree with the exact
    shapes/dtypes of `tree_like`.

    Leaves come back as the same kind of array they went in as: numpy
    stays numpy — `jnp.asarray` on a numpy tree would INITIALIZE the
    accelerator backend from a pure control-plane resync and copy a
    98 MiB elastic payload onto the device (measured as the round-3
    adaptation-latency regression)."""
    import numpy as np

    buf = np.asarray(buf, dtype=np.uint8)
    leaves, treedef = jax.tree_util.tree_flatten(tree_like)
    out = []
    offset = 0
    for l in leaves:
        arr = np.asarray(l)
        nbytes = arr.size * arr.itemsize
        chunk = buf[offset:offset + nbytes]
        restored = chunk.view(arr.dtype).reshape(arr.shape)
        out.append(restored.copy() if isinstance(l, np.ndarray)
                   else jnp.asarray(restored))
        offset += nbytes
    return jax.tree_util.tree_unflatten(treedef, out)


# -- chunked streaming -------------------------------------------------------
# pack_bytes above materializes the WHOLE tree as one host buffer — a
# full extra copy of a 98 MiB model before a single byte hits the wire
# (measured: 476 ms of the 2380 ms elastic grow 2->4, BASELINE round
# 6). The chunk schedule below is the zero-copy replacement: large
# leaves stream as byte-view slices (no copy on either side — the
# receiver lands them straight into the destination leaf), runs of
# small leaves coalesce into bounded scratch chunks. elastic/
# streaming.py drives it as a pipelined broadcast.


def leaf_byte_views(leaves) -> List["np.ndarray"]:
    """Contiguous uint8 1-D views of host leaves (zero-copy for
    C-contiguous numpy leaves; accelerator arrays pay their one
    unavoidable device->host transfer in np.asarray)."""
    import numpy as np

    out = []
    for l in leaves:
        a = np.ascontiguousarray(np.asarray(l))
        out.append(a.reshape(-1).view(np.uint8))
    return out


def chunk_schedule(tree_like, chunk_bytes: int) -> List[List[Tuple[int,
                                                                   int,
                                                                   int]]]:
    """Partition a pytree's bytes into chunks of spans.

    Returns a list of chunks; each chunk is a list of
    ``(leaf_index, byte_offset_in_leaf, nbytes)`` spans covering every
    byte of every leaf exactly once, in leaf order. Schedule-only —
    derived from shapes/dtypes, so every rank computes the identical
    schedule from its own `tree_like`.

    Layout rules: a leaf of >= `chunk_bytes` closes the open chunk
    first, so each of its FULL `chunk_bytes`-sized slices is a
    SINGLE-span chunk (a pure view: no assembly copy on root, received
    in place at the destination); only its sub-chunk remainder may
    coalesce with following small leaves. Smaller leaves coalesce into
    multi-span chunks of at most `chunk_bytes`.
    """
    import numpy as np

    if chunk_bytes <= 0:
        raise ValueError(f"chunk_bytes must be positive: {chunk_bytes}")
    leaves = jax.tree_util.tree_leaves(tree_like)
    chunks: List[List[Tuple[int, int, int]]] = []
    cur: List[Tuple[int, int, int]] = []
    cur_bytes = 0
    for i, l in enumerate(leaves):
        # same leaf tolerance as pack_bytes: Python scalars (no
        # .dtype) count via np.asarray; arrays stay on device
        dt = getattr(l, "dtype", None)
        if dt is None:
            a = np.asarray(l)
            nbytes = int(a.size) * a.itemsize
        else:
            nbytes = int(np.prod(np.shape(l), dtype=np.int64)) \
                * np.dtype(dt).itemsize
        if nbytes >= chunk_bytes and cur:
            chunks.append(cur)
            cur, cur_bytes = [], 0
        off = 0
        while nbytes - off > 0:
            take = min(chunk_bytes - cur_bytes, nbytes - off)
            cur.append((i, off, take))
            cur_bytes += take
            off += take
            if cur_bytes == chunk_bytes:
                chunks.append(cur)
                cur, cur_bytes = [], 0
    if cur:
        chunks.append(cur)
    return chunks


# -- gradient bucketing ------------------------------------------------------
# chunk_schedule above is byte-oriented: broadcast copies bytes, so
# mixed-dtype spans can share a chunk. A gradient ALL-REDUCE sums typed
# elements, so its buckets must be dtype-homogeneous and element-aligned
# — and they fill in REVERSE leaf order, because backward produces the
# output-side gradients first (PyTorch DDP's reverse-registration
# bucketing, Li et al. 2020): the pipeline can put bucket 0 on the wire
# while the input-side backward is still running.


def bucket_schedule(tree_like, bucket_bytes: int) -> List[Tuple[
        "np.dtype", List[Tuple[int, int, int]]]]:
    """Partition a gradient pytree into fixed-byte all-reduce buckets.

    Returns a list of buckets; each bucket is ``(dtype, spans)`` where
    spans are ``(leaf_index, elem_offset, n_elems)`` covering every
    element of every leaf exactly once, leaves taken in REVERSE leaf
    order (the order backward produces them). Schedule-only — derived
    from shapes/dtypes, so every rank computes the identical schedule
    (and therefore the identical bucket launch order) from its own
    `tree_like`.

    Built on `chunk_schedule`: reversed leaves are split into maximal
    same-dtype runs and each run is chunked with `bucket_bytes` rounded
    down to an element multiple, so the layout rules carry over (a
    >= bucket-sized leaf opens fresh and its full slices are
    single-span — zero-copy views end to end; small leaves coalesce).
    """
    import numpy as np

    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes must be positive: {bucket_bytes}")
    leaves = jax.tree_util.tree_leaves(tree_like)
    n = len(leaves)
    rev = list(reversed(leaves))

    def leaf_dtype(l):
        dt = getattr(l, "dtype", None)
        return np.dtype(dt) if dt is not None else np.asarray(l).dtype

    out: List[Tuple[np.dtype, List[Tuple[int, int, int]]]] = []
    run_start = 0
    while run_start < n:
        dt = leaf_dtype(rev[run_start])
        run_end = run_start
        while run_end < n and leaf_dtype(rev[run_end]) == dt:
            run_end += 1
        run = rev[run_start:run_end]
        esz = dt.itemsize
        per_bucket = max(1, bucket_bytes // esz) * esz
        for spans in chunk_schedule(run, per_bucket):
            elem_spans = [(n - 1 - (run_start + i), off // esz, nb // esz)
                          for i, off, nb in spans if nb > 0]
            if elem_spans:
                out.append((dt, elem_spans))
        run_start = run_end
    return out


# -- checkpoint sharding -----------------------------------------------------
# The sharded checkpoint tier (kungfu_tpu/checkpoint_async.py) divides
# the tree's bytes across peers so each writes only its shard. The
# assignment must be a pure function of shapes/dtypes — every rank
# derives the identical owner map from its own replica, with no
# negotiation traffic on the save path — so it is a thin layer over
# chunk_schedule: chunk i belongs to shard (i % num_shards).


def shard_schedule(tree_like, chunk_bytes: int,
                   num_shards: int) -> List[Tuple[int, List[Tuple[int,
                                                                  int,
                                                                  int]]]]:
    """Partition a pytree's bytes into per-shard write chunks.

    Returns ``[(owner, spans), ...]`` — the `chunk_schedule` chunks in
    order, chunk i owned by shard ``i % num_shards`` (round-robin keeps
    shard sizes within one chunk of each other for any leaf mix). Spans
    are ``(leaf_index, byte_offset_in_leaf, nbytes)`` covering every
    byte of every leaf exactly once. Schedule-only: derived from
    shapes/dtypes, so every rank computes the identical owner map from
    its own `tree_like` — the determinism contract the kfverify
    schedule-purity pass enforces on every feeder of this function.
    """
    if num_shards <= 0:
        raise ValueError(f"num_shards must be positive: {num_shards}")
    return [(i % num_shards, spans)
            for i, spans in enumerate(chunk_schedule(tree_like,
                                                     chunk_bytes))]


def subtree_shapes(tree) -> List[Tuple]:
    return [l.shape for l in jax.tree_util.tree_leaves(tree)]


def defuse(buf: jnp.ndarray, tree_like):
    """Unflatten `buf` back into the structure/shapes/dtypes of
    `tree_like`."""
    leaves, treedef = jax.tree_util.tree_flatten(tree_like)
    out = []
    offset = 0
    for l in leaves:
        n = l.size
        out.append(jnp.reshape(buf[offset:offset + n], l.shape).astype(
            l.dtype))
        offset += n
    return jax.tree_util.tree_unflatten(treedef, out)
