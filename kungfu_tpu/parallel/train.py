"""The SPMD train step: one jitted function for every optimizer family.

Replaces the reference's TF-optimizer wrapper + session machinery
(reference: srcs/python/kungfu/tensorflow/optimizers/core.py) with a single
`shard_map`-compiled step over the mesh: forward + backward on the local
batch shard, distributed optax update (whose collectives ride ICI), and
in-place parameter application. Worker-local state uses the stacked layout
of kungfu_tpu.parallel.mesh.
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax
import optax
from jax import lax
from jax import shard_map
from jax.sharding import Mesh

from ..ops.collective import all_reduce_mean
from ..trace.scopes import OPT_UPDATE
from .rules import replicated, stacked


def _squeeze(t):
    return jax.tree_util.tree_map(lambda x: x[0], t)


def _unsqueeze(t):
    return jax.tree_util.tree_map(lambda x: x[None], t)


def _apply_update(tx, grads, opt_state, params):
    """The optimizer's part of every step below, under the program's
    own scope (`kf.opt_update`, trace/scopes.py) so a device trace
    finds it whatever `tx` is."""
    with jax.named_scope(OPT_UPDATE):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state


def build_train_step_with_state(
    loss_fn: Callable,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis_name: str = "data",
    donate: bool = True,
    sync_state: bool = True,
):
    """Compile a train step for models with non-trainable state
    (BatchNorm running stats etc.).

    `loss_fn(params, model_state, batch) -> (loss, new_model_state)`.
    Model state is worker-stacked alongside params. With `sync_state=True`
    (right for sync_sgd and monitors) the model state is pmean'd so every
    worker carries identical statistics; pass `sync_state=False` for the
    divergent-row optimizers (sma, pair_averaging, ada before the switch)
    where each worker's statistics must follow its own weights. Returns
    `step(params, model_state, opt_state, batch) ->
        (params, model_state, opt_state, mean_loss)`.
    """

    def device_step(params_s, mstate_s, opt_s, batch):
        params = _squeeze(params_s)
        mstate = _squeeze(mstate_s)
        opt_state = _squeeze(opt_s)
        (loss, new_mstate), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, mstate, batch)
        params, opt_state = _apply_update(tx, grads, opt_state, params)
        if sync_state:
            new_mstate = all_reduce_mean(new_mstate, axis_name)
        return (
            _unsqueeze(params),
            _unsqueeze(new_mstate),
            _unsqueeze(opt_state),
            all_reduce_mean(loss, axis_name),
        )

    mapped = shard_map(
        device_step,
        mesh=mesh,
        in_specs=(stacked(axis_name), stacked(axis_name),
                  stacked(axis_name), stacked(axis_name)),
        out_specs=(stacked(axis_name), stacked(axis_name),
                   stacked(axis_name), replicated()),
        check_vma=False,
    )
    donate_argnums: Tuple[int, ...] = (0, 1, 2) if donate else ()
    return jax.jit(mapped, donate_argnums=donate_argnums)


def build_train_step(
    loss_fn: Callable,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis_name: str = "data",
    donate: bool = True,
):
    """Compile a train step for worker-stacked (params, opt_state).

    `loss_fn(params, batch) -> scalar` sees one worker's (unstacked) params
    and its local batch shard. Returns
    `step(params, opt_state, batch) -> (params, opt_state, mean_loss)`.

    Thin adapter over build_train_step_with_state with empty model state,
    so the two builders cannot drift.
    """
    stateful = build_train_step_with_state(
        lambda p, s, b: (loss_fn(p, b), s),
        tx,
        mesh,
        axis_name=axis_name,
        donate=donate,
        sync_state=False,  # empty state: nothing to sync
    )

    def step(params_s, opt_s, batch):
        params_s, _, opt_s, loss = stateful(params_s, {}, opt_s, batch)
        return params_s, opt_s, loss

    return step


def build_gspmd_train_step(
    loss_fn: Callable,
    tx: optax.GradientTransformation,
    donate: bool = True,
    has_aux: bool = False,
):
    """Compile a train step for the GSPMD (annotation-sharded) layout.

    The shard_map builders above use the worker-stacked DP layout; this
    one is for models whose params carry `NamedSharding`s directly
    (`parallel.tensor.shard_params` dp x tp / MoE) — no stacking, no
    explicit collectives: `loss_fn(params, batch) -> scalar`, and GSPMD
    schedules everything from the placements. Returns
    `step(params, opt_state, batch) -> (params, opt_state, loss)` with
    params+opt donated (without donation XLA double-buffers the full
    f32 state — ~4.2 GB extra for GPT-2-medium + adamw).

    With `has_aux`, `loss_fn(params, batch) -> (scalar, metrics)` (e.g.
    `gpt_loss_with_aux` for MoE router losses) and the step returns
    `(params, opt_state, loss, metrics)`.
    """

    def step(params, opt_state, batch):
        out, grads = jax.value_and_grad(loss_fn, has_aux=has_aux)(
            params, batch)
        params, opt_state = _apply_update(tx, grads, opt_state, params)
        if has_aux:
            loss, metrics = out
            return params, opt_state, loss, metrics
        return params, opt_state, out

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def build_dp_replicated_train_step(
    loss_fn: Callable,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis_name: str = "data",
    donate: bool = True,
):
    """Data-parallel train step for REPLICATED params with a per-shard
    loss — the home for Pallas-fused losses under dp.

    `build_gspmd_train_step` covers annotation-sharded layouts, but
    `pallas_call` has no GSPMD partitioning rule: under a multi-device
    mesh the partitioner replicates a fused kernel's operands (an
    all-gather of the full-batch activations) instead of running it on
    each data shard. This builder closes that gap with shard_map:
    every device evaluates `loss_fn(params, batch_shard)` — e.g.
    ``lambda p, t: gpt_fused_loss(model, p, t)`` — on its shard,
    grads and loss are pmean'd over `axis_name`, and the (replicated)
    optimizer update follows: the standard dp recipe with the kernel
    inside the per-shard region where it belongs.

    `params`/`opt_state` replicated, the batch sharded over
    `axis_name` with equal shard sizes (so the mean-of-shard-means
    equals the global mean). Returns
    `step(params, opt_state, batch) -> (params, opt_state, loss)` —
    the same signature as `build_gspmd_train_step`'s dense form.
    """

    def device_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads, loss = all_reduce_mean((grads, loss), axis_name)
        params, opt_state = _apply_update(tx, grads, opt_state, params)
        return params, opt_state, loss

    mapped = shard_map(
        device_step,
        mesh=mesh,
        in_specs=(replicated(), replicated(), stacked(axis_name)),
        out_specs=(replicated(), replicated(), replicated()),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(0, 1) if donate else ())


def build_eval_step(
    metric_fn: Callable, mesh: Mesh, axis_name: str = "data"
):
    """Compile an eval step: mean of `metric_fn(params, batch)` over the
    mesh, using worker 0's convention that all rows are equivalent for
    sync training (for diverged averaging runs, evaluate a chosen row)."""

    def device_eval(params_s, batch):
        params = jax.tree_util.tree_map(lambda x: x[0], params_s)
        return lax.pmean(metric_fn(params, batch), axis_name)

    mapped = shard_map(
        device_eval,
        mesh=mesh,
        in_specs=(stacked(axis_name), stacked(axis_name)),
        out_specs=replicated(),
        check_vma=False,
    )
    return jax.jit(mapped)
