"""Headline benchmark: ResNet-50 SyncSGD training throughput per chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N}

Mirrors the reference's synthetic-benchmark methodology (reference:
benchmarks/system/benchmark_kungfu.py: synthetic ImageNet-shaped data,
Horovod-style timed iterations, images/sec). Runs the full distributed
train step (forward + backward + gradient pmean + SGD-momentum update +
BatchNorm-stat sync) through this framework's SPMD path on every visible
chip and reports per-chip throughput, with the device it ran on
(`platform`, `device_kind`, `chips`) in `details`.

vs_baseline: ratio against 360 images/sec/chip — the widely reproduced
ResNet-50 fp32 V100 figure of the Horovod-era systems the reference
benchmarks against on 16xV100 (reference README.md:197-205 plots relative
throughput on that hardware; no absolute numbers are published, so the
per-chip V100 figure anchors the comparison).

`--steps N` cuts the timed loop to N steps (chip_smoke.py takes a few).
Set KF_BENCH_PROFILE=<dir> to capture a jax.profiler trace of the timed
iterations (view with tensorboard / xprof). Roofline context for the
number this prints: see docs/benchmarks.md "Single-chip roofline".
"""

import argparse
import contextlib
import json
import os
import time

BASELINE_IMAGES_PER_SEC_PER_CHIP = 360.0  # ResNet-50 fp32 on V100


def model_and_loss():
    """The benchmark's model and its
    ``loss_fn(params, batch_stats, batch) -> (loss, new_batch_stats)``."""
    import jax.numpy as jnp
    import optax

    from kungfu_tpu.models import ResNet50

    # space-to-depth stem: +2.2% step time on v5e (see docs/benchmarks.md)
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                     space_to_depth=True)

    def loss_fn(params, batch_stats, batch):
        logits, updated = model.apply(
            {"params": params, "batch_stats": batch_stats},
            batch["x"], train=True, mutable=["batch_stats"],
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()
        return loss, updated["batch_stats"]

    return model, loss_fn


def build(mesh, per_chip_batch, image):
    """The benchmark's train step on `mesh` with its placed inputs:
    ``(step, (params, batch_stats, opt_state), batch)``, all
    worker-stacked. The synthetic batch is the same on every chip, so
    the step on any mesh computes what it computes on one chip."""
    import jax
    import jax.numpy as jnp
    import optax

    from kungfu_tpu.optimizers import sync_sgd
    from kungfu_tpu.parallel import (
        build_train_step_with_state,
        init_worker_state,
        replicate_to_workers,
        shard_batch,
    )

    model, loss_fn = model_and_loss()
    global_batch = per_chip_batch * mesh.size
    x = jnp.ones((global_batch, image, image, 3), jnp.float32)
    y = jnp.zeros((global_batch,), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), x[:2], train=True)

    tx = sync_sgd(optax.sgd(0.1, momentum=0.9))
    params_s = replicate_to_workers(variables["params"], mesh)
    stats_s = replicate_to_workers(variables["batch_stats"], mesh)
    opt_s = init_worker_state(tx, params_s, mesh)
    step = build_train_step_with_state(loss_fn, tx, mesh)
    batch_s = shard_batch({"x": x, "y": y}, mesh)
    return step, (params_s, stats_s, opt_s), batch_s


def rows_identical(tree) -> bool:
    """SyncSGD's invariant: every worker's row of the stacked state is
    bit-identical to row 0."""
    import jax
    import jax.numpy as jnp

    same = [jnp.all(x == x[:1]) for x in jax.tree_util.tree_leaves(tree)]
    return bool(jnp.all(jnp.stack(same)))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=0,
                    help="timed steps (default 20; 3 on a CPU)")
    args = ap.parse_args(argv)

    import jax

    from kungfu_tpu import compile_cache
    from kungfu_tpu.parallel import data_mesh

    cache = compile_cache.enable()
    n_chips = jax.device_count()
    device = jax.devices()[0]
    platform = device.platform
    per_chip_batch = 128 if platform != "cpu" else 8
    image = 224 if platform != "cpu" else 64
    warmup, iters = (3, 20) if platform != "cpu" else (1, 3)
    iters = args.steps or iters

    step, state, batch_s = build(data_mesh(n_chips), per_chip_batch,
                                 image)
    compile_s, _ = compile_cache.timed_compile(step, *state, batch_s)
    for _ in range(warmup):
        *state, loss = step(*state, batch_s)
    float(loss)  # the fetch fences the dependent step chain

    profile_dir = os.environ.get("KF_BENCH_PROFILE")
    trace = (jax.profiler.trace(profile_dir) if profile_dir
             else contextlib.nullcontext())
    with trace:
        t0 = time.perf_counter()
        for _ in range(iters):
            *state, loss = step(*state, batch_s)
        final_loss = float(loss)
        dt = time.perf_counter() - t0
    if final_loss != final_loss:
        raise SystemExit("NaN loss in benchmark")
    if not rows_identical(state):
        raise SystemExit("worker rows diverged under sync_sgd")

    global_batch = per_chip_batch * n_chips
    per_chip = global_batch * iters / dt / n_chips
    print(json.dumps({
        "metric": "resnet50_syncsgd_images_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / BASELINE_IMAGES_PER_SEC_PER_CHIP, 3),
        "details": {
            "platform": platform,
            "device_kind": device.device_kind,
            "chips": n_chips,
            "per_chip_batch": per_chip_batch,
            "image_size": image,
            "iters": iters,
            "dtype": "bfloat16",
            "step_time_ms": round(1000 * dt / iters, 2),
            "final_loss": final_loss,
            "rows_identical": True,
            "compile_s": round(compile_s, 2),
            "compile_cache": cache.as_dict(),
        },
    }))


if __name__ == "__main__":
    main()
