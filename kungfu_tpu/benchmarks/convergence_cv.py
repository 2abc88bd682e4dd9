"""Convergence-on-real-chip rows for the CV stack.

The reference's convergence proof is its ImageNet accuracy table
(reference: README.md:184-193) — unreachable in a zero-egress sandbox.
What IS reachable, and what this module measures end to end on the
real chip under SyncSGD:

1. **ResNet-18 on REAL handwritten digits** (sklearn `load_digits`,
   1797 genuine 8x8 scans upsampled to 32x32; 1500 train / 297 held
   out). A conv/BN network on real data through the full framework
   path — a materially stronger check than the round-3 MLP digits row.
2. **ResNet-18 on the CIFAR-shaped synthetic fallback**
   (`datasets/cifar.py synthetic=True`, disclosed as synthetic: the
   real `cifar-10-batches-py` files cannot be downloaded here; with
   `--data` pointing at them the same command trains real CIFAR-10).

Both report held-out accuracy, steps, wall-clock, and the seed.

  python -m kungfu_tpu.benchmarks.convergence_cv [--steps N]
"""

from __future__ import annotations

import argparse
import json
import time


def _train_resnet18(x, y, xt, yt, steps: int, batch: int, lr: float,
                    seed: int, num_classes: int):
    """SyncSGD ResNet-18 over every visible chip; returns
    (test_accuracy, seconds, steps)."""
    import jax
    import numpy as np
    import optax

    from kungfu_tpu.data import ElasticSampler
    from kungfu_tpu.models import ResNet18
    from kungfu_tpu.optimizers import sync_sgd
    from kungfu_tpu.parallel import (build_train_step_with_state,
                                     data_mesh, init_worker_state,
                                     replicate_to_workers, shard_batch)

    n = jax.device_count()
    mesh = data_mesh(n)
    model = ResNet18(num_classes=num_classes)
    variables = model.init(jax.random.PRNGKey(seed), x[:1], train=True)

    def loss_fn(params, batch_stats, b):
        logits, updated = model.apply(
            {"params": params, "batch_stats": batch_stats},
            b["x"], train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]).mean()
        return loss, updated["batch_stats"]

    tx = sync_sgd(optax.sgd(lr, momentum=0.9))
    params_s = replicate_to_workers(variables["params"], mesh)
    stats_s = replicate_to_workers(variables["batch_stats"], mesh)
    opt_s = init_worker_state(tx, params_s, mesh)
    step = build_train_step_with_state(loss_fn, tx, mesh)

    sampler = ElasticSampler(len(x), batch * n, rank=0, size=1,
                             seed=seed)
    # compile outside the timed region (the first compile is tens of
    # seconds and is not a training cost)
    idx = sampler.next_indices()
    b0 = shard_batch({"x": x[idx], "y": y[idx]}, mesh)
    params_s, stats_s, opt_s, loss = step(params_s, stats_s, opt_s, b0)
    float(loss)

    t0 = time.perf_counter()
    for _ in range(steps - 1):
        idx = sampler.next_indices()
        b = shard_batch({"x": x[idx], "y": y[idx]}, mesh)
        params_s, stats_s, opt_s, loss = step(params_s, stats_s, opt_s,
                                              b)
    final = float(loss)
    dt = time.perf_counter() - t0
    assert final == final, "NaN loss"

    params = jax.tree_util.tree_map(lambda t: t[0], params_s)
    stats = jax.tree_util.tree_map(lambda t: t[0], stats_s)

    @jax.jit
    def acc(params, stats, bx, by):
        logits = model.apply({"params": params, "batch_stats": stats},
                             bx, train=False)
        return (logits.argmax(-1) == by).sum()

    correct = sum(int(acc(params, stats, xt[i:i + 256], yt[i:i + 256]))
                  for i in range(0, len(xt), 256))
    return correct / len(yt), dt, steps


def run_digits(steps: int, seed: int = 0):
    import numpy as np
    from sklearn.datasets import load_digits

    d = load_digits()
    imgs = (d.images / 16.0).astype(np.float32)          # [N, 8, 8]
    # 8x8 -> 32x32 nearest-neighbour upsample, 3 channels: real pixel
    # content at a shape the conv stem accepts
    imgs = imgs.repeat(4, axis=1).repeat(4, axis=2)[..., None]
    imgs = np.repeat(imgs, 3, axis=-1)
    labels = d.target.astype(np.int32)
    rng = np.random.default_rng(seed)
    order = rng.permutation(len(imgs))
    imgs, labels = imgs[order], labels[order]
    x, y, xt, yt = imgs[:1500], labels[:1500], imgs[1500:], labels[1500:]
    acc, secs, steps = _train_resnet18(x, y, xt, yt, steps=steps,
                                       batch=64, lr=0.05, seed=seed,
                                       num_classes=10)
    return {"dataset": "sklearn_digits_real_8x8_upsampled_32",
            "real_data": True, "train": 1500, "test": len(yt),
            "model": "ResNet-18", "optimizer": "sync_sgd(momentum 0.9)",
            "steps": steps, "seed": seed,
            "test_accuracy": round(acc, 4),
            "train_seconds": round(secs, 1)}


def run_cifar(steps: int, seed: int = 0, data_dir: str = ""):
    from kungfu_tpu.datasets import Cifar10Loader

    loader = Cifar10Loader(data_dir)
    # label from what actually LOADED, not the flag: the loader falls
    # back to synthetic silently when the pickle files are absent, and
    # a typo'd --data must not mislabel a synthetic run as real
    is_real = loader.available()
    sets = loader.load_datasets()
    x, y = sets.train.images, sets.train.labels
    xt, yt = sets.test.images, sets.test.labels
    acc, secs, steps = _train_resnet18(x, y, xt, yt, steps=steps,
                                       batch=64, lr=0.05, seed=seed,
                                       num_classes=10)
    return {"dataset": ("cifar10_real" if is_real
                        else "cifar10_shaped_synthetic_fallback"),
            "real_data": is_real,
            "train": len(y), "test": len(yt),
            "model": "ResNet-18", "optimizer": "sync_sgd(momentum 0.9)",
            "steps": steps, "seed": seed,
            "test_accuracy": round(acc, 4),
            "train_seconds": round(secs, 1)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--cifar-steps", type=int, default=400)
    ap.add_argument("--data", default="",
                    help="dir containing cifar-10-batches-py/ for real "
                         "CIFAR-10 (synthetic fallback otherwise)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    for row in (run_digits(args.steps, args.seed),
                run_cifar(args.cifar_steps, args.seed, args.data)):
        print(json.dumps({"metric": "cv_convergence", **row}),
              flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
