"""All-reduce microbenchmark over fake-model tensor catalogs."""

from __future__ import annotations

import argparse
import time

import numpy as np


def equivalent_rate(np_: int, total_bytes: int, seconds: float) -> float:
    """The reference's all-reduce equivalent data rate: 4*(n-1)*B/t
    (reference: kungfu-bench-allreduce.go:67-75) — the bytes a ring
    all-reduce moves per unit time, independent of algorithm."""
    if np_ <= 1:
        return 0.0
    return 4.0 * (np_ - 1) * total_bytes / seconds


def bench_cpu(args) -> None:
    # catalog derivation uses jax.eval_shape only — run it on the CPU
    # backend so control-plane benchmark workers need no accelerator
    import jax

    jax.config.update("jax_platforms", "cpu")
    import kungfu_tpu
    from kungfu_tpu.models import fake_model_catalog

    peer = kungfu_tpu.init()
    catalog = fake_model_catalog(args.model, fuse=args.fuse)
    buffers = {name: np.ones(count, dtype=np.float32)
               for name, count in catalog.items()}
    total_bytes = sum(b.nbytes for b in buffers.values())

    def run_once(step: int):
        if args.mode == "par":
            import threading
            ts = [
                threading.Thread(
                    target=peer.all_reduce, args=(buf,),
                    kwargs={"name": f"{name}:{step}"})
                for name, buf in buffers.items()
            ]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        else:
            for name, buf in buffers.items():
                peer.all_reduce(buf, name=f"{name}:{step}")

    for w in range(args.warmup):
        run_once(-1 - w)
    peer.barrier()
    t0 = time.perf_counter()
    for i in range(args.iters):
        run_once(i)
    peer.barrier()
    dt = time.perf_counter() - t0

    rate = equivalent_rate(peer.size, total_bytes * args.iters, dt)
    if peer.rank == 0:
        print(
            f"CPU {args.model} np={peer.size} mode={args.mode} "
            f"fuse={args.fuse}: {len(buffers)} tensors, "
            f"{total_bytes / 2**20:.1f} MiB/iter, "
            f"{dt / args.iters * 1000:.1f} ms/iter, "
            f"equivalent rate {rate / 2**30:.2f} GiB/s",
            flush=True,
        )


def bench_ici(args) -> None:
    import jax
    import jax.numpy as jnp

    from kungfu_tpu.models import fake_model_catalog
    from kungfu_tpu.parallel import data_mesh
    from kungfu_tpu.parallel.rules import stacked

    mesh = data_mesh()
    n = mesh.shape["data"]
    catalog = fake_model_catalog(args.model, fuse=args.fuse)
    # worker-stacked buffers: row per chip
    buffers = [jnp.ones((n, count), jnp.float32) for count in
               catalog.values()]
    total_bytes = sum(int(b.nbytes) // n for b in buffers)

    @jax.jit
    def allreduce_all(bufs):
        def dev(*bs):
            return tuple(jax.lax.psum(b, "data") for b in bs)

        return jax.shard_map(
            dev, mesh=mesh,
            in_specs=tuple(stacked("data") for _ in bufs),
            out_specs=tuple(stacked("data") for _ in bufs),
            check_vma=False,
        )(*bufs)

    out = tuple(buffers)
    for _ in range(max(1, args.warmup)):
        out = allreduce_all(out)
    _ = float(out[0][0, 0])  # the fetch fences the chain
    t0 = time.perf_counter()
    for _ in range(args.iters):
        out = allreduce_all(out)
    _ = float(out[0][0, 0])
    dt = time.perf_counter() - t0
    rate = equivalent_rate(n, total_bytes * args.iters, dt)
    print(
        f"ICI {args.model} chips={n} fuse={args.fuse}: "
        f"{len(buffers)} tensors, {total_bytes / 2**20:.1f} MiB/iter, "
        f"{dt / args.iters * 1000:.2f} ms/iter, "
        f"equivalent rate {rate / 2**30:.2f} GiB/s",
        flush=True,
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--method", choices=["CPU", "ICI"], default="CPU")
    ap.add_argument("--model", default="resnet50-imagenet")
    ap.add_argument("--mode", choices=["par", "seq"], default="par")
    ap.add_argument("--fuse", action="store_true")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    args = ap.parse_args(argv)
    if args.method == "CPU":
        bench_cpu(args)
    else:
        bench_ici(args)


if __name__ == "__main__":
    main()
