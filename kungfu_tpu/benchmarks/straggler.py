"""Async scalability under stragglers — the reference's second headline.

The reference's async-scalability plot (reference: README.md:207-209,
benchmarks/system/result/async-scalability.svg) shows PairAveraging
(AD-PSGD) holding cluster throughput where synchronization stalls. This
benchmark measures that property directly: N worker processes under
kfrun, one of which sleeps a configurable amount per step (a slow
host), trained under each strategy family; cluster throughput is the
sum of per-worker sample rates.

  - **sync** (S-SGD): the per-step gradient all-reduce barriers on the
    straggler, so every worker runs at the straggler's pace.
  - **sma**: synchronous model averaging — same barrier, same fate.
  - **pair** (AD-PSGD, `parallel.pair_host`): barrier-free gossip; the
    fast workers keep their full rate and only the straggler is slow.

Orchestrator (default mode) launches one kfrun cluster per
(strategy, straggler) cell and parses the per-worker result markers:

  python -m kungfu_tpu.benchmarks.straggler --np 8 --straggler-ms 100

Worker mode (run under kfrun) trains an SLP on synthetic MNIST and
prints one `KF_STRAGGLER_RESULT {json}` line.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

MARKER = "KF_STRAGGLER_RESULT"


def worker(args) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import optax

    import kungfu_tpu
    from kungfu_tpu.data import ElasticSampler
    from kungfu_tpu.datasets import load_synthetic_split
    from kungfu_tpu.initializer import broadcast_variables
    from kungfu_tpu.models import SLP
    from kungfu_tpu.ops.collective import defuse, fuse
    from kungfu_tpu.parallel import PairAveragingHost

    peer = kungfu_tpu.init()
    ds = load_synthetic_split(n=4096, seed=0)
    x, y = ds.images, ds.labels
    model = SLP(num_classes=10)
    params = model.init(jax.random.PRNGKey(0), x[:1])["params"]
    params = broadcast_variables(params, peer=peer)
    tx = optax.sgd(args.lr)
    opt_state = tx.init(params)

    @jax.jit
    def local_step(params, opt_state, batch):
        def loss_fn(p):
            logits = model.apply({"params": p}, batch["x"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["y"]).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        return loss, grads

    @jax.jit
    def apply(params, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    pair = None
    if args.strategy == "pair":
        pair = PairAveragingHost(peer, seed=peer.rank)
        pair.init_store(params)

    sampler = ElasticSampler(len(x), args.batch, peer.rank, peer.size,
                             seed=1)
    slow = (peer.rank == args.straggler_rank
            and args.straggler_ms > 0)

    def one_step(step, params, opt_state):
        if slow:
            time.sleep(args.straggler_ms / 1000.0)
        idx = sampler.next_indices()
        batch = {"x": x[idx], "y": y[idx]}
        loss, grads = local_step(params, opt_state, batch)
        if args.strategy == "sync":
            buf = peer.all_reduce(np.asarray(fuse(grads)),
                                  name=f"g:{step}")
            grads = defuse(jnp.asarray(buf) / peer.size, grads)
            params, opt_state = apply(params, opt_state, grads)
        elif args.strategy == "sma":
            params, opt_state = apply(params, opt_state, grads)
            buf = peer.all_reduce(np.asarray(fuse(params)),
                                  name=f"w:{step}")
            avg = defuse(jnp.asarray(buf) / peer.size, params)
            params = jax.tree.map(lambda w, m: 0.9 * w + 0.1 * m,
                                  params, avg)
        else:
            params = pair.mix(params)
            params, opt_state = apply(params, opt_state, grads)
            pair.publish(params)
        return params, opt_state

    # warmup (jit compiles, store populated), then a barrier so every
    # worker's timed region starts together
    for step in range(2):
        params, opt_state = one_step(-2 + step, params, opt_state)
    peer.barrier()
    t0 = time.perf_counter()
    for step in range(args.steps):
        params, opt_state = one_step(step, params, opt_state)
    wall = time.perf_counter() - t0
    rate = args.steps * args.batch / wall
    print(MARKER + " " + json.dumps({
        "rank": peer.rank, "size": peer.size,
        "strategy": args.strategy, "straggler_ms": args.straggler_ms,
        "samples_per_sec": round(rate, 1), "wall_s": round(wall, 3),
    }), flush=True)
    # keep serving the store until everyone is done (fast pair workers
    # must not pull their peers out from under the straggler)
    if pair is not None:
        pair.stop()
    peer.barrier()


def _launch_cell(np_, strategy, straggler_ms, steps, batch,
                 port_range, timeout):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("KF_PREWARM", "0")  # static cluster: no warm pool
    cmd = [
        sys.executable, "-m", "kungfu_tpu.run", "-np", str(np_),
        "-port-range", port_range, "--",
        sys.executable, "-m", "kungfu_tpu.benchmarks.straggler",
        "--worker", "--strategy", strategy, "--steps", str(steps),
        "--batch", str(batch), "--straggler-ms", str(straggler_ms),
    ]
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=timeout)
    by_rank = {}  # rank -> that worker's MARKER record
    for line in (out.stdout + out.stderr).splitlines():
        pos = line.find(MARKER)
        if pos >= 0:
            r = json.loads(line[pos + len(MARKER):])
            by_rank[r["rank"]] = r
    if out.returncode != 0 or len(by_rank) != np_:
        raise RuntimeError(
            f"straggler cell {strategy}/{straggler_ms}ms failed "
            f"rc={out.returncode}, {len(by_rank)}/{np_} results:\n"
            f"{out.stdout[-3000:]}\n{out.stderr[-1000:]}")
    return by_rank


def measure(np_=8, straggler_ms=100, steps=40, batch=64,
            strategies=("sync", "pair", "sma"),
            port_range="29100-29999", timeout=900):
    """Returns {strategy: {"clean_samples_per_sec": rate,
    "straggler_samples_per_sec": rate, "retention": straggler/clean,
    "clean_wall_s": {rank: s}, "straggler_wall_s": {rank: s}}} —
    cluster samples/sec summed over workers, worst case one straggler
    (rank 0) sleeping `straggler_ms`/step; wall_s is each worker's own
    clock over its timed steps."""
    results = {}
    for strategy in strategies:
        clean = _launch_cell(np_, strategy, 0, steps, batch,
                             port_range, timeout)
        slow = _launch_cell(np_, strategy, straggler_ms, steps, batch,
                            port_range, timeout)
        c, s = (sum(r["samples_per_sec"] for r in cell.values())
                for cell in (clean, slow))
        results[strategy] = {
            "clean_samples_per_sec": round(c, 1),
            "straggler_samples_per_sec": round(s, 1),
            "retention": round(s / c, 4),
            "clean_wall_s": {k: r["wall_s"] for k, r in clean.items()},
            "straggler_wall_s": {k: r["wall_s"] for k, r in slow.items()},
        }
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--np", dest="np_", type=int, default=8)
    ap.add_argument("--strategy", default="sync",
                    choices=["sync", "pair", "sma"])
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--straggler-ms", type=int, default=100)
    ap.add_argument("--straggler-rank", type=int, default=0)
    ap.add_argument("--port-range", default="29100-29999")
    args = ap.parse_args(argv)
    if args.worker:
        worker(args)
        return 0
    res = measure(args.np_, args.straggler_ms, args.steps, args.batch,
                  port_range=args.port_range)
    print(json.dumps({
        "metric": "straggler_cluster_samples_per_sec",
        "np": args.np_, "straggler_ms": args.straggler_ms,
        "steps": args.steps, "batch": args.batch,
        "results": {strategy: {k: v for k, v in r.items()
                               if not k.endswith("_wall_s")}
                    for strategy, r in res.items()},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
