"""Elastic MNIST: the cluster grows/shrinks *during* training.

The reference's elastic Estimator example rebuilt for this framework
(reference: scripts/tests/run-elastic-test.sh + hooks/elastic.py): a
step->size schedule drives config-server proposals; workers reach
consensus, the kfrun watcher spawns/kills processes, joiners adopt the
survivors' weights and training position, and evicted workers exit
cleanly.

Run (boots its own config server):
  python examples/mnist_elastic.py --launch --schedule "40:2,40:4,40:1"

Or by hand against a running config server:
  python -m kungfu_tpu.run -np 2 -H 127.0.0.1:4 -w \
      -config-server http://127.0.0.1:9100/get -- \
      python examples/mnist_elastic.py --schedule "40:2,40:4,40:1"
"""

import argparse
import os
import subprocess
import sys

# local-emulation default; KF_WORKER_PLATFORM=tpu on a real pod
os.environ["JAX_PLATFORMS"] = os.environ.get("KF_WORKER_PLATFORM", "cpu")


def launch(args):
    """Boot a config server + kfrun -w and run this script as the worker."""
    from kungfu_tpu.elastic import ConfigServer
    from kungfu_tpu.plan import free_port

    server = ConfigServer(port=0).start()
    try:
        cmd = [
            sys.executable, "-m", "kungfu_tpu.run",
            "-np", "2", "-H", "127.0.0.1:8",
            "-runner-port", str(free_port()),
            "-w", "-config-server", server.get_url, "--",
            sys.executable, os.path.abspath(__file__),
            "--schedule", args.schedule, "--steps", str(args.steps),
        ]
        sys.exit(subprocess.run(cmd).returncode)
    finally:
        server.stop()


def train(args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from common import load_mnist

    import kungfu_tpu
    from kungfu_tpu.data import ElasticSampler
    from kungfu_tpu.elastic import ElasticCallback
    from kungfu_tpu.initializer import broadcast_variables
    from kungfu_tpu.models import SLP
    from kungfu_tpu.ops.collective import defuse, fuse

    peer = kungfu_tpu.init()
    x, y = load_mnist(args.data)
    model = SLP(num_classes=10)
    params = model.init(jax.random.PRNGKey(0), x[:1])["params"]
    tx = optax.sgd(args.lr)
    opt_state = tx.init(params)

    @jax.jit
    def train_step(params, opt_state, batch):
        def loss_fn(p):
            logits = model.apply({"params": p}, batch["x"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["y"]).mean()
        loss, grads = jax.value_and_grad(loss_fn)(params)
        return loss, grads

    elastic = ElasticCallback(peer, schedule=args.schedule,
                              samples_per_step=args.batch)

    def make_sampler():
        return ElasticSampler(len(x), args.batch, peer.rank, peer.size,
                              seed=1, offset=elastic.state.trained_samples)

    if peer.config.version > 0:  # joiner: sync position + weights
        elastic.sync_position()
        params = broadcast_variables(params, peer=peer)
        print(f"[rank {peer.rank}] joined at epoch {peer.version} "
              f"step {elastic.state.step}", flush=True)
    sampler = make_sampler()

    while elastic.state.step < args.steps:
        idx = sampler.next_indices()
        batch = {"x": x[idx], "y": y[idx]}
        loss, grads = train_step(params, opt_state, batch)
        buf = peer.all_reduce(np.asarray(fuse(grads)),
                              name=f"g:{peer.version}:{elastic.state.step}")
        grads = defuse(jnp.asarray(buf) / peer.size, grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)

        if elastic.after_step():
            if not elastic.state.keep:
                print(f"[rank {peer.rank}] evicted at step "
                      f"{elastic.state.step}", flush=True)
                return
            elastic.sync_position()
            params = broadcast_variables(params, peer=peer)
            sampler = make_sampler()  # new (rank, size) at agreed offset
            print(f"[rank {peer.rank}] epoch {peer.version}: "
                  f"size={peer.size} step={elastic.state.step}", flush=True)
        if elastic.state.step % 20 == 0:
            print(f"[rank {peer.rank}] step {elastic.state.step} "
                  f"loss {float(loss):.4f}", flush=True)

    print(f"[rank {peer.rank}] done: step={elastic.state.step} "
          f"size={peer.size}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--launch", action="store_true",
                    help="boot config server + kfrun and run workers")
    ap.add_argument("--schedule", default="40:2,40:4,40:1")
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--data", default="")
    args = ap.parse_args()
    if args.launch:
        launch(args)
    else:
        train(args)


if __name__ == "__main__":
    main()
