"""Multi-worker MNIST under kfrun: per-process training + DCN all-reduce.

The multi-process form of the reference's MNIST examples — each worker is
a separate process (one per TPU host in production; many per host in
local emulation) whose gradients are averaged over the libkf control
plane, the path the reference's CPU all-reduce ops take (reference:
examples/tf2_mnist_gradient_tape.py run under `kungfu-run -np 4`).

Run:
  python -m kungfu_tpu.run -np 4 -H 127.0.0.1:4 -- \
      python examples/mnist_multiworker.py --steps 100

Use --optimizer {sync,sma,pair} to pick the training strategy family
(S-SGD, synchronous model averaging, async pair averaging).
"""

import argparse
import os

# Workers in local emulation share one machine: run each on the CPU
# backend. On a real TPU pod set KF_WORKER_PLATFORM=tpu so every host
# worker grabs its chips.
os.environ["JAX_PLATFORMS"] = os.environ.get("KF_WORKER_PLATFORM", "cpu")

import jax
import jax.numpy as jnp
import numpy as np
import optax

from common import load_mnist

import kungfu_tpu
from kungfu_tpu.data import ElasticSampler
from kungfu_tpu.initializer import broadcast_variables
from kungfu_tpu.models import SLP
from kungfu_tpu.ops.collective import defuse, fuse
from kungfu_tpu.parallel import PairAveragingHost


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=64, help="per-worker batch")
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--optimizer", choices=["sync", "sma", "pair"],
                    default="sync")
    ap.add_argument("--data", default="")
    args = ap.parse_args()

    peer = kungfu_tpu.init()
    x, y = load_mnist(args.data)
    model = SLP(num_classes=10)
    params = model.init(jax.random.PRNGKey(peer.rank), x[:1])["params"]
    # all workers start from rank 0's weights (reference initializer)
    params = broadcast_variables(params, peer=peer)

    tx = optax.sgd(args.lr)
    opt_state = tx.init(params)

    @jax.jit
    def local_grads(params, batch):
        def loss_fn(p):
            logits = model.apply({"params": p}, batch["x"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["y"]).mean()
        return jax.value_and_grad(loss_fn)(params)

    @jax.jit
    def apply(params, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    pair = None
    if args.optimizer == "pair":
        pair = PairAveragingHost(peer, seed=peer.rank)
        pair.init_store(params)

    sampler = ElasticSampler(len(x), args.batch, peer.rank, peer.size,
                             seed=1)
    for step in range(args.steps):
        idx = sampler.next_indices()
        batch = {"x": x[idx], "y": y[idx]}
        loss, grads = local_grads(params, batch)

        if args.optimizer == "sync":
            # S-SGD: average fused gradients every step over DCN
            buf = peer.all_reduce(np.asarray(fuse(grads)), name=f"g:{step}")
            grads = defuse(jnp.asarray(buf) / peer.size, grads)
            params, opt_state = apply(params, opt_state, grads)
        elif args.optimizer == "sma":
            # SMA: local step, then EMA-blend with the cluster average
            params, opt_state = apply(params, opt_state, grads)
            buf = peer.all_reduce(np.asarray(fuse(params)), name=f"w:{step}")
            avg = defuse(jnp.asarray(buf) / peer.size, params)
            params = jax.tree.map(lambda w, m: 0.9 * w + 0.1 * m,
                                  params, avg)
        else:
            # AD-PSGD: blend with one random peer's model, no barrier
            params = pair.mix(params)
            params, opt_state = apply(params, opt_state, grads)
            pair.publish(params)

        if step % 50 == 0 or step == args.steps - 1:
            print(f"rank {peer.rank}/{peer.size} step {step} "
                  f"loss {float(loss):.4f}", flush=True)

    if pair is not None:
        pair.stop()
    peer.barrier()


if __name__ == "__main__":
    main()
