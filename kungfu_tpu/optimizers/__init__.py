"""Distributed optimizers as optax gradient transformations.

TPU-native rebuilds of the reference's six distributed optimizers
(reference: srcs/python/kungfu/tensorflow/optimizers/): instead of wrapping
a TF optimizer object, each is an `optax.GradientTransformation` factory
that wraps an inner optax transform and injects ICI collectives. They are
designed to run *inside* the jitted SPMD train step (under `shard_map` over
a mesh axis), so the communication compiles onto ICI.

- `sync_sgd` — synchronous S-SGD: pmean of gradients (Horovod-equivalent).
- `sync_sgd_bucketed` — S-SGD with the pmean issued as fixed-byte
  reverse-backward-order buckets (the ICI mirror of the DCN
  `kungfu_tpu.grad_pipeline`); bitwise-identical values, fewer and
  larger collectives.
- `sma` — synchronous model averaging (SMA/EA-SGD): per-step weight
  averaging blended with factor alpha, overlapped with local updates.
- `pair_averaging` — AD-PSGD's ICI-native form: rotating ring-gossip
  weight averaging via collective_permute (the async DCN form lives in
  kungfu_tpu.parallel.pair_host).
- `ada_sgd` — adaptive hybrid: SMA before `change_step`, S-SGD after.
- `monitor_gradient_noise_scale`, `monitor_gradient_variance` — S-SGD plus
  online training-health statistics in optimizer state.
"""

from .ada_sgd import ada_sgd
from .fused import SMALL_LEAF_ELEMS, group_small_leaves
from .async_sgd import PairAveragingState, pair_averaging
from .monitors import (
    attach_gradient_noise_scale,
    GNSMonitorState,
    VarianceMonitorState,
    monitor_gradient_noise_scale,
    monitor_gradient_variance,
)
from .sma_sgd import sma
from .sync_sgd import (bucketed_all_reduce_mean, sync_sgd,
                       sync_sgd_bucketed)

__all__ = [
    "group_small_leaves",
    "SMALL_LEAF_ELEMS",
    "sync_sgd",
    "sync_sgd_bucketed",
    "bucketed_all_reduce_mean",
    "sma",
    "pair_averaging",
    "PairAveragingState",
    "ada_sgd",
    "monitor_gradient_noise_scale",
    "monitor_gradient_variance",
    "attach_gradient_noise_scale",
    "GNSMonitorState",
    "VarianceMonitorState",
]
