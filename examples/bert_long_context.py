"""Long-context BERT MLM training with sequence-parallel attention.

Beyond the reference's DP-only scope: the sequence is sharded across a
mesh axis and attention mixes positions through the ICI ring
(`attention="ring"`) or two all-to-alls (`attention="ulysses"`); see
docs/architecture.md "Sequence parallelism". One process drives all
visible devices; on the 8-device CPU test mesh this trains a 4096-token
context that would not fit a single device's attention comfortably.

Run:  python examples/bert_long_context.py [--attention ring] \\
          [--seq-len 4096] [--steps 10]
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax

from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kungfu_tpu.models import BertConfig, BertEncoder


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--attention", choices=["ring", "ulysses"],
                    default="ring")
    ap.add_argument("--seq-len", type=int, default=4096)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()

    n = jax.device_count()
    mesh = Mesh(np.array(jax.devices()), ("seq",))
    cfg = BertConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                     num_heads=8, intermediate_size=256,
                     max_position=args.seq_len, dtype=jnp.float32,
                     attention=args.attention)
    model = BertEncoder(cfg)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size,
                          size=(args.batch, args.seq_len)).astype(np.int32)
    tokens = jax.device_put(
        jnp.asarray(tokens), NamedSharding(mesh, P(None, "seq")))

    def init_fn(t):
        return model.init(jax.random.PRNGKey(0), t)["params"]

    params = jax.jit(shard_map(init_fn, mesh=mesh, in_specs=P(None, "seq"),
                               out_specs=P(), check_vma=False))(tokens)
    tx = optax.adam(args.lr)
    opt_state = jax.jit(tx.init)(params)

    def step_fn(params, opt_state, t):
        def loss_fn(params):
            logits = model.apply({"params": params}, t)
            # MLM-style self-reconstruction on the local shard
            local = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), t).mean()
            # shards hold disjoint positions: global mean over the axis
            return lax.pmean(local, "seq")

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # each device holds only its shard's partial gradient of the
        # global loss; combine before updating the replicated params
        grads = jax.tree_util.tree_map(
            lambda g: lax.pmean(g, "seq"), grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    step = jax.jit(shard_map(
        step_fn, mesh=mesh,
        in_specs=(P(), P(), P(None, "seq")),
        out_specs=(P(), P(), P()), check_vma=False))

    print(f"{args.attention} attention, T={args.seq_len} over {n} devices "
          f"({args.seq_len // n} positions/device)", flush=True)
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, tokens)
        print(f"step {i} loss {float(loss):.4f}", flush=True)


if __name__ == "__main__":
    main()
