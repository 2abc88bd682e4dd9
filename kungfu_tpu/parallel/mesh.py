"""Mesh construction and worker-state layout.

Layout convention: **worker-local state is stacked along a leading mesh-axis
dimension** — a pytree whose leaves have shape (n_workers, ...), sharded
P(axis) so each chip holds exactly its own row. This one representation
serves every parallelism mode:

- sync SGD keeps all rows bit-identical (asserted in tests),
- SMA / pair-averaging rows diverge by design,
- elastic resize reshapes the leading axis at the epoch boundary,
- broadcast/init is a row-0 copy.

Per-chip memory equals the replicated layout (each chip stores one model),
so nothing is paid for the generality.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding

from .rules import stacked


def data_mesh(
    num_devices: Optional[int] = None,
    axis_name: str = "data",
    devices=None,
) -> Mesh:
    """A 1-D mesh over the first `num_devices` visible devices.

    On a TPU pod slice, call after `parallel.init_distributed()` (which
    maps the kfrun KF_* env onto jax.distributed.initialize) so
    `jax.devices()` spans all hosts. Pass `devices`
    explicitly to pin the mesh to a specific backend (the multi-chip dry
    run pins virtual CPU devices this way so it never executes on whatever
    platform owns the default backend). Without `devices` a short visible
    set is a hard error, so a misconfigured pod fails fast instead of
    silently training on host CPU.
    """
    devices = list(devices) if devices is not None else jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"requested {num_devices} devices, have {len(devices)} "
                f"({devices[0].platform})")
        devices = devices[:num_devices]
    return Mesh(np.asarray(devices), (axis_name,))


def axis_size(mesh: Mesh, axis_name: str = "data") -> int:
    return mesh.shape[axis_name]


def worker_sharding(mesh: Mesh, axis_name: str = "data") -> NamedSharding:
    """Sharding of worker-stacked state: leading dim split over the axis."""
    return NamedSharding(mesh, stacked(axis_name))


def replicate_to_workers(tree, mesh: Mesh, axis_name: str = "data"):
    """Tile a single model to (n, ...) rows and shard rows onto chips.

    The data-plane equivalent of the reference's BroadcastGlobalVariablesOp
    at init (reference: srcs/python/kungfu/tensorflow/initializer/): every
    worker starts from the same row-0 state. Each chip is sent its own
    row and nothing else: the whole (n, ...) stack never exists on one
    device (built there first, it costs the first chip n models).
    """
    n = axis_size(mesh, axis_name)
    sharding = worker_sharding(mesh, axis_name)

    def place(x):
        row = jnp.asarray(x)[None]
        return jax.make_array_from_single_device_arrays(
            (n,) + row.shape[1:], sharding,
            [jax.device_put(row, d)
             for d in sharding.addressable_devices])

    return jax.tree_util.tree_map(place, tree)


def unstack_worker_state(tree, row: int = 0):
    """Extract one worker's row as an unstacked pytree (for eval/export)."""
    return jax.tree_util.tree_map(lambda x: x[row], tree)


def init_worker_state(tx, stacked_params, mesh: Mesh,
                      axis_name: str = "data"):
    """Build per-worker optimizer state for worker-stacked params."""

    def dev_init(params_s):
        local = jax.tree_util.tree_map(lambda x: x[0], params_s)
        state = tx.init(local)
        return jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None], state)

    f = shard_map(
        dev_init,
        mesh=mesh,
        in_specs=(stacked(axis_name),),
        out_specs=stacked(axis_name),
        check_vma=False,
    )
    return jax.jit(f)(stacked_params)


@lru_cache(maxsize=32)
def _broadcast_fn(mesh: Mesh, root: int, axis_name: str):
    from ..ops.collective import broadcast as bc_op

    return jax.jit(
        shard_map(
            lambda t: bc_op(t, axis_name, root),
            mesh=mesh,
            in_specs=(stacked(axis_name),),
            out_specs=stacked(axis_name),
            check_vma=False,
        )
    )


def broadcast_params(stacked, mesh: Mesh, root: int = 0,
                     axis_name: str = "data"):
    """Reset every worker's row to worker `root`'s row — the resync op used
    at elastic boundaries and AdaSGD switches. The jitted broadcast is
    cached per (mesh, root, axis) so repeat boundaries don't recompile."""
    return _broadcast_fn(mesh, root, axis_name)(stacked)


def shard_batch(batch, mesh: Mesh, axis_name: str = "data"):
    """Place a global batch so its leading dim splits across workers."""
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, worker_sharding(mesh, axis_name)), batch
    )
