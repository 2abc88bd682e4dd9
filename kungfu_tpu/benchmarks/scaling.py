"""Scaling efficiency: per-chip throughput at 1..N chips.

BASELINE.md's north star is >=90% scaling efficiency for ResNet-50
SyncSGD (the reference's headline plot is relative throughput vs
Horovod at 8-16 GPUs, README.md:197-205). This harness measures the
numerator and denominator on whatever backend is visible:

    efficiency(n) = images_per_sec(n) / (n * images_per_sec(1))

On a TPU pod slice it reports real ICI scaling; on the virtual CPU mesh
it validates the harness itself (CPU "chips" share one socket, so the
numbers are not hardware claims — the line is labeled accordingly).

Run:  python -m kungfu_tpu.benchmarks.scaling [--model resnet50]
          [--sizes 1,2,4,8] [--batch 32] [--iters 10]

`--dcn-grad` switches to the CROSS-HOST axis: np kfrun worker
processes run the per-step gradient exchange (simulated backward +
real libkf DCN collectives) and the efficiency denominator is the
comm-free backward time — 1.0 means the gradient pipeline hid every
wire byte behind backward. Rows cover {lump, bucketed-overlap} x
{fp32, bf16, int8-EF} per size (docs/grad_pipeline.md).

Prints one JSON line with per-size throughput and efficiencies.
"""

from __future__ import annotations

import argparse
import json

from .throughput import MODELS, measure_rate


def transport_matrix_main(args) -> int:
    """np x {flat, hier} x {tcp, unix, shm} on the fp32 gradient lump.

    The hierarchical-collectives acceptance matrix (ISSUE 13,
    docs/collectives.md): np workers split over two simulated hosts
    (127.0.0.1 + 127.0.0.2) run the per-step fp32 gradient all-reduce
    as a post-backward lump under STAR, each cell pinning one wire
    class for the colocated pairs and flat-vs-hierarchical graphs.
    Prints exposed comm, step wall, and the link-class egress split
    — "socket egress drops, exposed comm shrinks" is the claim under
    test (``hier_collectives``).
    """
    from .allreduce import TRANSPORT_ENV, run_grad_one, two_host_spec

    sizes = [int(s) for s in (args.sizes or "2,4,8").split(",")]
    rows = []
    for np_ in sizes:
        hosts = two_host_spec(np_)
        for hier in ("flat", "hier"):
            for transport in ("tcp", "unix", "shm"):
                env = dict(TRANSPORT_ENV[transport])
                env["KF_HIER"] = "1" if hier == "hier" else "0"
                # STAR, not AUTO: AUTO already resolves to the host-
                # aware binary-tree-star across hosts, which would make
                # "flat" half-hierarchical and hide the A/B
                r = run_grad_one(np_, args.dcn_model, args.iters,
                                 args.warmup, "lump", "none",
                                 args.backward_ms, args.bucket_mb,
                                 args.port_range, hosts=hosts,
                                 extra_env=env, strategy="STAR")
                r["hosts"] = hosts
                r["mode"] = hier
                r["transport"] = transport
                rows.append(r)
                print(json.dumps(r), flush=True)
    result = {
        "metric": "hier_collectives",
        "model": rows[0]["model"],
        "backward_ms": args.backward_ms,
        "strategy": "STAR",
        "note": ("two simulated hosts on loopback, 1-core container: "
                 "the byte attribution (socket egress off the kernel "
                 "stack) is the portable result; wall deltas rank the "
                 "per-hop overhead, not real DCN bandwidth"),
        "rows": [{k: r[k] for k in
                  ("np", "mode", "transport", "hosts",
                   "exposed_comm_ms", "step_ms",
                   "egress_mb_per_step", "socket_egress_mb_per_step",
                   "egress_by_link_mb_per_step")} for r in rows],
    }
    print(json.dumps(result), flush=True)
    return 0


def dcn_grad_main(args) -> int:
    """DCN gradient-step scaling: efficiency = backward / step wall."""
    from .allreduce import run_grad_one

    sizes = [int(s) for s in (args.sizes or "2,4,8").split(",")]
    rows = []
    for np_ in sizes:
        for pipeline in ("lump", "bucketed"):
            for compress in ("none", "bf16", "int8"):
                r = run_grad_one(np_, args.dcn_model, args.iters,
                                 args.warmup, pipeline, compress,
                                 args.backward_ms, args.bucket_mb,
                                 args.port_range)
                r["scaling_efficiency"] = round(
                    args.backward_ms / max(1e-9, r["step_ms"]), 3)
                rows.append(r)
                print(json.dumps(r), flush=True)
    out = {
        "metric": "dcn_grad_scaling_efficiency",
        "model": rows[0]["model"],
        "backward_ms": args.backward_ms,
        "bucket_mb": args.bucket_mb,
        "note": "efficiency = simulated-backward ms / measured step "
                "ms; 1.0 = all DCN comm hidden behind backward "
                "(loopback fabric, not a hardware claim)",
        "efficiency": {
            f"np{r['np']}:{r['pipeline']}:{r['compress']}":
                r["scaling_efficiency"]
            for r in rows
        },
        "exposed_comm_ms": {
            f"np{r['np']}:{r['pipeline']}:{r['compress']}":
                r["exposed_comm_ms"]
            for r in rows
        },
    }
    print(json.dumps(out))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=sorted(MODELS), default="resnet50")
    ap.add_argument("--sizes", default="",
                    help="comma list; default 1,2,4,... up to all chips")
    ap.add_argument("--batch", type=int, default=32, help="per-chip batch")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--dcn-grad", action="store_true",
                    help="measure DCN gradient-pipeline scaling over "
                         "kfrun workers instead of ICI throughput")
    ap.add_argument("--dcn-model", default="resnet50-imagenet",
                    help="fake-model catalog for --dcn-grad")
    ap.add_argument("--backward-ms", type=float, default=150.0)
    ap.add_argument("--bucket-mb", type=float, default=1.0)
    ap.add_argument("--port-range", default="14000-15500")
    ap.add_argument("--transport-matrix", action="store_true",
                    help="with --dcn-grad: np x {flat,hier} x "
                         "{tcp,unix,shm} over two simulated hosts "
                         "(docs/collectives.md)")
    args = ap.parse_args(argv)

    if args.dcn_grad and args.transport_matrix:
        return transport_matrix_main(args)
    if args.dcn_grad:
        return dcn_grad_main(args)

    import jax

    total = jax.device_count()
    if args.sizes:
        sizes = sorted({int(s) for s in args.sizes.split(",")})
        if sizes and sizes[0] < 1:
            ap.error(f"--sizes must be >= 1, got {sizes}")
    else:
        sizes, n = [], 1
        while n <= total:
            sizes.append(n)
            n *= 2
    feasible = [n for n in sizes if n <= total]
    if not feasible:
        raise SystemExit(
            f"no requested size fits the {total} visible devices: {sizes}")
    platform = jax.devices()[0].platform

    rates = {n: measure_rate(args.model, n, args.batch, args.iters,
                             args.warmup)[0]
             for n in feasible}
    # the documented metric normalizes against 1 chip; when the sweep
    # starts higher, say so in the output instead of silently rebasing
    base_n = feasible[0]
    base = rates[base_n] / base_n
    out = {
        "metric": f"{args.model}_syncsgd_scaling_efficiency",
        "platform": platform,
        "hardware_claim": platform != "cpu",  # cpu mesh shares one socket
        "per_chip_batch": args.batch,
        "baseline_size": base_n,  # efficiency is vs this size's per-chip rate
        "images_per_sec": {str(n): round(r, 1) for n, r in rates.items()},
        "efficiency": {
            str(n): round(r / (n * base), 3) for n, r in rates.items()
        },
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
