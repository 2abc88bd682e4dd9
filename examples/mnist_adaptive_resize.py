"""Monitor-driven elasticity: the noise scale decides the cluster size.

The closed adaptation loop (docs/optimizers.md): each worker trains MNIST
with the gradient-noise-scale monitor in its optimizer state, feeds the
reading into `NoiseScalePolicy`, and — when the noise scale says a
bigger global batch would still train efficiently — the policy proposes
a larger cluster through the config server. The consensus-resize
machinery grows the cluster live; shrink happens the same way when the
noise scale drops. No schedule anywhere: the statistic drives membership
(the loop the reference documents but leaves to the user; reference:
grad_noise_scale.py:37-69 + hooks/elastic.py:12-77).

Run (boots its own config server):
  python examples/mnist_adaptive_resize.py --launch

By hand against a running config server:
  python -m kungfu_tpu.run -np 1 -H 127.0.0.1:8 -w \\
      -config-server http://127.0.0.1:9100/get -- \\
      python examples/mnist_adaptive_resize.py
"""

import argparse
import os
import subprocess
import sys

# local-emulation default; KF_WORKER_PLATFORM=tpu on a real pod
os.environ["JAX_PLATFORMS"] = os.environ.get("KF_WORKER_PLATFORM", "cpu")
# the GNS estimator needs a cross-device axis (it compares per-device vs
# averaged gradients); give each CPU-emulated worker a 2-device mesh
if (os.environ["JAX_PLATFORMS"] == "cpu"
        and "xla_force_host_platform_device_count"
        not in os.environ.get("XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2"
                               ).strip()


def launch(args):
    from kungfu_tpu.elastic import ConfigServer
    from kungfu_tpu.plan import free_port

    server = ConfigServer(port=0).start()
    try:
        cmd = [
            sys.executable, "-m", "kungfu_tpu.run",
            "-np", "1", "-H", "127.0.0.1:8",
            "-runner-port", str(free_port()),
            "-w", "-config-server", server.get_url, "--",
            sys.executable, os.path.abspath(__file__),
            "--steps", str(args.steps), "--batch", str(args.batch),
            "--max-size", str(args.max_size),
        ]
        sys.exit(subprocess.run(cmd).returncode)
    finally:
        server.stop()


def train(args):
    import jax
    import numpy as np
    import optax

    from common import load_mnist

    import kungfu_tpu
    from kungfu_tpu.data import ElasticSampler
    from kungfu_tpu.elastic import ElasticCallback, NoiseScalePolicy
    from kungfu_tpu.models import SLP
    from kungfu_tpu.optimizers import monitor_gradient_noise_scale
    from kungfu_tpu.parallel import (
        build_train_step,
        data_mesh,
        init_worker_state,
        replicate_to_workers,
        shard_batch,
    )

    import jax.numpy as jnp

    p = kungfu_tpu.init()
    x, y = load_mnist(args.data)
    n = jax.device_count()
    policy = NoiseScalePolicy(device_batch=args.batch, min_size=1,
                              max_size=args.max_size, hysteresis=2)
    # each worker consumes batch * n samples per step (n local devices)
    elastic = ElasticCallback(p, policy=policy,
                              samples_per_step=args.batch * n)
    mesh = data_mesh(n)
    model = SLP(num_classes=10)
    params = model.init(jax.random.PRNGKey(0), x[:1])["params"]

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()

    tx = monitor_gradient_noise_scale(optax.sgd(args.lr),
                                      device_batch_size=args.batch)
    params_s = replicate_to_workers(params, mesh)
    opt_s = init_worker_state(tx, params_s, mesh)
    step = build_train_step(loss_fn, tx, mesh)

    def resync(params_s):
        """Adopt survivor weights + position over DCN. Joiners and
        survivors must run the SAME sequence (broadcast + position
        all-reduce) or the epoch's collectives deadlock."""
        host = jax.device_get(params_s)
        synced = elastic.resync_params(host)
        return jax.tree_util.tree_map(jnp.asarray, synced)

    if p.config.version > 0:
        params_s = resync(params_s)
        print(f"joined at epoch {p.config.version} "
              f"step {elastic.state.step}", flush=True)

    def make_sampler():
        # data position restored from the consensus sample counter
        return ElasticSampler(len(x), args.batch * n, rank=p.rank,
                              size=p.size, seed=1,
                              offset=elastic.state.trained_samples)

    sampler = make_sampler()
    while elastic.state.keep and elastic.state.step < args.steps:
        idx = sampler.next_indices()
        batch = shard_batch({"x": x[idx], "y": y[idx]}, mesh)
        params_s, opt_s, loss = step(params_s, opt_s, batch)
        noise = float(np.asarray(jax.device_get(opt_s.noise_scale))[0])
        policy.observe(noise)
        if elastic.state.step % 20 == 0:
            print(f"step {elastic.state.step} loss {float(loss):.4f} "
                  f"noise {noise:.1f} -> target size "
                  f"{policy.target_size()} (now {p.size})", flush=True)
        if elastic.after_step():
            if not elastic.state.keep:
                print(f"evicted at step {elastic.state.step}", flush=True)
                return
            # cluster changed: same resync sequence as the joiners; the
            # mesh here is per-process so no rebuild is needed
            params_s = resync(params_s)
            sampler = make_sampler()  # new (rank, size) at agreed offset
            print(f"monitor-resize: size={p.size} at step "
                  f"{elastic.state.step}", flush=True)
    print(f"finished rank={p.rank} size={p.size} "
          f"step={elastic.state.step} noise={policy.noise_scale:.1f}",
          flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--launch", action="store_true")
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--batch", type=int, default=32, help="per-chip batch")
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--max-size", type=int, default=4)
    ap.add_argument("--data", default="", help="mnist .npz or idx dir")
    args = ap.parse_args()
    if args.launch:
        launch(args)
    else:
        train(args)


if __name__ == "__main__":
    main()
