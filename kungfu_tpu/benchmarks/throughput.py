"""Per-model SyncSGD training throughput (the reference's headline trio).

The reference's sync-scalability plot benchmarks ResNet-50, VGG16 and
InceptionV3 (reference: README.md:197-205, benchmarks/system/
benchmark_kungfu.py methodology: synthetic ImageNet-shaped data, timed
iterations, images/sec). `bench.py` is the driver-facing ResNet-50
headline; this module measures any zoo model the same way:

  python -m kungfu_tpu.benchmarks.throughput --model inception3
  python -m kungfu_tpu.benchmarks.throughput --model vgg16 --batch 64

Prints one JSON line per run.
"""

from __future__ import annotations

import argparse
import json
import time


MODELS = {
    # name -> (constructor kwargs resolver, image size, default batch)
    # s2d stem = the bench.py flagship config (docs/benchmarks.md)
    "resnet50": (lambda m: m.ResNet50(num_classes=1000,
                                      space_to_depth=True), 224, 128),
    "vgg16": (lambda m: m.VGG16(num_classes=1000), 224, 64),
    "inception3": (lambda m: m.InceptionV3(num_classes=1000), 299, 64),
}


def measure_rate(model_name: str, n: int, batch: int = 0, iters: int = 20,
                 warmup: int = 3):
    """Images/sec of `n`-device SyncSGD training on `model_name`.

    The one timing harness every image benchmark shares (throughput CLI,
    scaling-efficiency sweep). Returns (images_per_sec, meta_dict).
    """
    import jax
    import jax.numpy as jnp
    import optax

    import kungfu_tpu.models as models
    from kungfu_tpu.optimizers import sync_sgd
    from kungfu_tpu.parallel import (
        build_train_step_with_state,
        data_mesh,
        init_worker_state,
        replicate_to_workers,
        shard_batch,
    )

    build, image, default_batch = MODELS[model_name]
    platform = jax.devices()[0].platform
    if platform == "cpu":  # keep the smoke path fast
        image = 75 if model_name == "inception3" else 64
        default_batch = 4
        iters, warmup = min(iters, 3), min(warmup, 1)
    warmup = max(warmup, 1)  # the warmup fence binds `loss`
    batch = batch or default_batch

    # pin a device subset only for sub-size sweeps on one host; a full-
    # size run must keep data_mesh's default (multi-host pods span
    # jax.devices() across processes and a slice would strand hosts)
    devices = None if n == jax.device_count() else jax.devices()[:n]
    mesh = data_mesh(n, devices=devices)
    model = build(models)
    x = jnp.ones((batch * n, image, image, 3), jnp.float32)
    y = jnp.zeros((batch * n,), jnp.int32)
    k0, k1 = jax.random.split(jax.random.PRNGKey(0))
    # 'dropout' rng for VGG; harmless for BN models. A fixed key per step
    # keeps the step a pure function of its state (throughput-only).
    variables = model.init({"params": k0, "dropout": k1}, x[:2],
                           train=True)
    has_bn = "batch_stats" in variables

    def loss_fn(params, batch_stats, b):
        coll = {"params": params}
        if has_bn:
            coll["batch_stats"] = batch_stats
        logits, updated = model.apply(
            coll, b["x"], train=True, mutable=["batch_stats"],
            rngs={"dropout": k1},
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]).mean()
        return loss, updated.get("batch_stats", batch_stats)

    tx = sync_sgd(optax.sgd(0.1, momentum=0.9))
    params_s = replicate_to_workers(variables["params"], mesh)
    stats_s = replicate_to_workers(variables.get("batch_stats", {}), mesh)
    opt_s = init_worker_state(tx, params_s, mesh)
    step = build_train_step_with_state(loss_fn, tx, mesh)
    batch_s = shard_batch({"x": x, "y": y}, mesh)

    # XLA's own flop count for the compiled PER-DEVICE module (fwd+
    # bwd+optimizer on this device's batch/n shard): the honest
    # hardware-FLOP-utilization numerator for conv nets, where
    # hand-counting branch convs invites errors. `step` is already
    # jitted — lower it directly so the executable (and its cache
    # entry) is the same one the timing loop runs.
    # (`cost_analysis` is None where the backend has none to give)
    cost = step.lower(params_s, stats_s, opt_s,
                      batch_s).compile().cost_analysis() or {}
    step_flops = float(cost.get("flops", 0.0)) or None

    for _ in range(warmup):
        params_s, stats_s, opt_s, loss = step(params_s, stats_s, opt_s,
                                              batch_s)
    float(loss)  # the fetch fences the dependent step chain

    t0 = time.perf_counter()
    for _ in range(iters):
        params_s, stats_s, opt_s, loss = step(params_s, stats_s, opt_s,
                                              batch_s)
    final_loss = float(loss)
    dt = time.perf_counter() - t0
    assert final_loss == final_loss, "NaN loss in benchmark"

    rate = batch * n * iters / dt
    meta = {
        "platform": platform, "chips": n, "per_chip_batch": batch,
        "image_size": image, "iters": iters, "dtype": "bfloat16",
        "step_time_ms": round(1000 * dt / iters, 2),
    }
    # HFU vs the chip's bf16 peak, only where the device kind is known
    # (shared table with benchmarks/lm.py). step_flops is PER-DEVICE,
    # so the denominator is one chip's peak — n cancels.
    from kungfu_tpu.benchmarks.lm import _BF16_PEAK_BY_KIND

    # the 'v5e' in the key name is historical (the first hardware the
    # row was published on); the denominator is the peak looked up for
    # device_kind below, recorded alongside so rows self-describe.
    meta["device_kind"] = jax.devices()[0].device_kind
    peak = _BF16_PEAK_BY_KIND.get(meta["device_kind"])
    if step_flops and peak:
        hfu = step_flops / (dt / iters) / peak
        meta["hfu_vs_v5e_bf16_peak"] = round(hfu, 4)
        meta["xla_step_gflops"] = round(step_flops / 1e9, 1)
    return rate, meta


def measure_adamw_update(size: str = "small", variant: str = "per-leaf",
                         iters: int = 20, warmup: int = 3):
    """ms/step of the isolated adamw update on the GPT param tree.

    The flagship step's optimizer share (16.1 ms of 104.6, round-5
    attribution) runs ~3.7x above its HBM floor because of the long
    tail of small leaves — each tiny fusion pays launch + sub-cache-line
    HBM overheads. This harness isolates exactly that: grads in, update
    applied, nothing else, for the two partitioning strategies:

    - ``per-leaf``: plain optax (the in-repo benchmark default),
    - ``grouped``: `optimizers.group_small_leaves` — small tail fused,
      2-D leaves per-leaf in their tiled layouts.

    Returns (ms_per_step, meta). The HBM floor is 28 B/param (read
    p,m,v,g + write p,m,v at f32); `floor_ratio` is measured/floor
    against the device's delivered bandwidth where known.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from kungfu_tpu.benchmarks.lm import SIZES
    from kungfu_tpu.models import GPTConfig, GPTLM
    from kungfu_tpu.optimizers import SMALL_LEAF_ELEMS, group_small_leaves

    platform = jax.devices()[0].platform
    if platform == "cpu":  # smoke path
        size = "tiny"
        iters, warmup = min(iters, 3), min(warmup, 1)
    hidden, layers, heads, inter = SIZES[size]
    cfg = GPTConfig(vocab_size=50257, hidden_size=hidden,
                    num_layers=layers, num_heads=heads,
                    intermediate_size=inter, max_position=1024,
                    dtype=jnp.float32)
    model = GPTLM(cfg)
    toks = jnp.zeros((1, 32), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks)["params"]
    make = lambda: optax.adamw(1e-4)  # noqa: E731
    tx = {
        "per-leaf": make,
        "grouped": lambda: group_small_leaves(make()),
    }[variant]()
    opt = tx.init(params)
    # synthetic grads with per-leaf structure (values don't matter for
    # timing; elementwise math is data-independent)
    grads = jax.tree_util.tree_map(
        lambda p: jnp.full(p.shape, 1e-3, p.dtype), params)

    @jax.jit
    def step(params, opt, grads):
        u, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, u), opt

    for _ in range(max(warmup, 1)):
        params, opt = step(params, opt, grads)
    jax.block_until_ready(params)
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt = step(params, opt, grads)
    jax.block_until_ready(params)
    ms = (time.perf_counter() - t0) / iters * 1e3

    leaves = jax.tree_util.tree_leaves(params)
    n_params = sum(int(l.size) for l in leaves)
    n_bytes = sum(int(l.size) * l.dtype.itemsize for l in leaves)
    tail = [l for l in leaves if l.size < SMALL_LEAF_ELEMS]
    hbm_bytes = 28 * n_params  # r: p,m,v,g + w: p,m,v at f32
    meta = {
        "platform": platform, "size": size, "variant": variant,
        "n_leaves": len(leaves), "n_params": n_params,
        "tail_leaves": len(tail),
        "tail_frac_of_leaves": round(len(tail) / len(leaves), 3),
        "tail_frac_of_bytes": round(
            sum(int(l.size) * l.dtype.itemsize for l in tail)
            / n_bytes, 5),
        "hbm_floor_bytes": hbm_bytes,
        "device_kind": jax.devices()[0].device_kind,
        "iters": iters,
    }
    # floor vs delivered bandwidth only where measured (docs/benchmarks
    # round-5 slope probes: ~660-720 GB/s on v5e); elsewhere the floor
    # ratio would be invented
    if meta["device_kind"] in ("TPU v5 lite", "TPU v5e"):
        floor_ms = hbm_bytes / 660e9 * 1e3
        meta["hbm_floor_ms_at_660GBps"] = round(floor_ms, 2)
        meta["floor_ratio"] = round(ms / floor_ms, 2)
    return ms, meta


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=sorted(MODELS), default="resnet50")
    ap.add_argument("--batch", type=int, default=0, help="per-chip batch")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--adamw", choices=("per-leaf", "grouped"),
                    default="",
                    help="measure the isolated adamw update on the GPT "
                         "tree with this leaf partitioning instead of "
                         "image-model throughput")
    ap.add_argument("--lm-size", default="small",
                    help="(--adamw) GPT size from benchmarks/lm.py")
    args = ap.parse_args(argv)

    import jax

    from kungfu_tpu import compile_cache

    compile_cache.enable()
    if args.adamw:
        ms, meta = measure_adamw_update(args.lm_size, args.adamw,
                                        args.iters, args.warmup)
        print(json.dumps({
            "metric": "gpt_adamw_update_ms",
            "value": round(ms, 3),
            "unit": "ms/step",
            "details": meta,
        }))
        return 0

    n = jax.device_count()
    rate, meta = measure_rate(args.model, n, args.batch, args.iters,
                              args.warmup)
    print(json.dumps({
        "metric": f"{args.model}_syncsgd_images_per_sec_per_chip",
        "value": round(rate / n, 2),
        "unit": "images/sec/chip",
        "details": meta,
    }))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
