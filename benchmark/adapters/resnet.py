"""ResNet-50 sync-SGD train step on the worker-stacked `shard_map`
layout, through what `bench.py::build` calls: `models.ResNet50`,
`optimizers.sync_sgd`, `parallel.build_train_step_with_state`,
`init_worker_state`, `replicate_to_workers`, `data_mesh`.

Where it departs from `bench.py`: the variables come from one jitted
`model.init` on the device, the batches are a ring of seeded normal
images with uniform labels (all-ones images with all-zero labels reach
loss 0.0 after one step and check nothing), made by one jitted program
straight into the workers' sharding, so no chip ever holds the global
batch.
"""

from __future__ import annotations

import math


def rows_identical(tree) -> bool:
    """SyncSGD's invariant: every worker's row of the stacked state is
    bit-identical to row 0 (`bench.py::rows_identical`'s test, copied;
    jitted, so it is one program and not three a leaf)."""
    import jax
    import jax.numpy as jnp

    def same(t):
        return jnp.all(jnp.stack([
            jnp.all(x == x[:1]) for x in jax.tree_util.tree_leaves(t)]))

    return bool(jax.jit(same)(tree))


def build(config, traffic, devs, seed):
    import jax
    import jax.numpy as jnp
    import optax

    from benchmark.runners.train import Job, optimizer
    from kungfu_tpu.models import ResNet50
    from kungfu_tpu.optimizers import sync_sgd
    from kungfu_tpu.parallel import (build_train_step_with_state, data_mesh,
                                     init_worker_state, replicate_to_workers,
                                     worker_sharding)

    model = ResNet50(num_classes=config["num_classes"],
                     num_filters=config["num_filters"],
                     dtype=jnp.dtype(config["dtype"]),
                     space_to_depth=config["space_to_depth"])
    if list(model.stage_sizes) != config["stage_sizes"]:
        raise SystemExit("models.ResNet50 no longer has the stage sizes "
                         "the configuration's file counts FLOPs from")

    def loss_fn(params, batch_stats, batch):  # bench.py::model_and_loss
        logits, updated = model.apply(
            {"params": params, "batch_stats": batch_stats},
            batch["x"], train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()
        return loss, updated["batch_stats"]

    mesh = data_mesh(len(devs), devices=devs)
    image = config["image_size"]
    global_batch = traffic["batch_per_chip"] * len(devs)
    k_params, k_data = jax.random.split(jax.random.PRNGKey(seed))

    variables = jax.jit(lambda k: model.init(
        k, jnp.zeros((2, image, image, 3), jnp.float32), train=True)
    )(k_params)

    def make_ring(key):
        out = []
        for k in jax.random.split(key, traffic["n_batches"]):
            kx, ky = jax.random.split(k)
            out.append({
                "x": jax.random.normal(
                    kx, (global_batch, image, image, 3), jnp.float32),
                "y": jax.random.randint(
                    ky, (global_batch,), 0, config["num_classes"],
                    dtype=jnp.int32)})
        return out

    ring = jax.jit(make_ring, out_shardings=worker_sharding(mesh))(k_data)

    tx = sync_sgd(optimizer(config["optimizer"]))
    params_s = replicate_to_workers(variables["params"], mesh)
    stats_s = replicate_to_workers(variables["batch_stats"], mesh)
    opt_s = init_worker_state(tx, params_s, mesh)
    step = build_train_step_with_state(loss_fn, tx, mesh)

    def verify(state):
        checks = {"rows_identical": rows_identical(state)}
        if len(devs) > 1:
            text = step.lower(*state, ring[0]).compile().as_text()
            checks["all_reduce_in_step"] = "all-reduce" in text
        return checks

    return Job(step=step, state=(params_s, stats_s, opt_s), batches=ring,
               unit="images", units_per_step=global_batch,
               loss_at_init=math.log(config["num_classes"]), verify=verify)
