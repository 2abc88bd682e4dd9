"""Serving latency x throughput x cluster size — elastically.

The decode tier's operator-facing numbers (docs/serving.md): for each
cluster size np, drive a fixed request mix through a REAL elastic
serving cluster (config server + kfrun + `serve.worker` replicas,
`serve.harness.run_serve_cluster`) and report per-request p50/p99
latency plus generated tokens/sec — measured WARM (a front-loaded
warmup batch absorbs worker boot + jit compile, the way an operator
measures a running service, and the way every other BASELINE row
excludes compile from its timed region).

The differentiating cell is **p99 THROUGH a mid-traffic resize**: at
np0=2, once the first measured request completes (the fast path
drains the default mix faster than a replica boots, so the heavier
resize mix starts its grow immediately), the harness grows the tier
2 -> 3 through the consensus-resize path
(config-server /addworker -> every worker adopts the epoch -> the
joiner boots, adopts weights, and starts leasing) while traffic is in
flight. Survivors' in-flight requests decode straight through the
epoch switch (their paged KV pools are per-process state), so the
cell reports what a resize actually costs the tail — and the run
gates on EVERY request completing plus zero request-ledger invariant
violations, so the number cannot be bought by dropping work.

  python -m kungfu_tpu.benchmarks.serve                # the matrix
  python -m kungfu_tpu.benchmarks.serve --np 1 2       # subset

1-core loopback caveat (BASELINE.md): every replica shares one CPU
core with the config server and each other, so ABSOLUTE latencies are
container artifacts and tok/s does NOT scale with np here; the
portable results are the completion guarantees, the ledger-invariant
gate, and the tail-through-resize SHAPE (p99 bounded by resize stall
+ queueing, not by request abandonment).
"""

from __future__ import annotations

import argparse
import json

#: per-worker continuous-batch width for every cell, one knob for
#: every row. r15 kept this at 4 so a long prompt's whole-prefill
#: could not stall too many decoding rows; chunked prefill removed
#: that head-of-line tradeoff (a prompt fills KF_SERVE_PREFILL_CHUNK
#: tokens per iteration, interleaved with decode), so the width is
#: now set by the continuous-batching economics alone: more rows per
#: decode step amortize the per-iteration dispatch + control costs
MAX_BATCH = 8
#: chunked-prefill size for every cell (the fast path under test —
#: prompts at or under one chunk still take the one-shot prefill)
PREFILL_CHUNK = 16


def _latencies(results):
    lat = sorted(r["latency_ms"] for r in results)
    return lat


def _timing(logs: str) -> dict:
    """Aggregate the workers' KF_SERVE_TIMING lines: where did the
    wall time go, per cell — decode compute vs prefill compute vs
    control-plane round trips. BENCH_r15's inverse np scaling
    (167 -> 97 -> 55 tok/s at np 1/2/4) was invisible without this
    split; it was the per-sequence /serve/append storm, i.e. a
    control_ms share that GREW with np on the 1-core loopback."""
    agg = {"steps": 0, "decode_ms": 0.0, "prefill_ms": 0.0,
           "control_ms": 0.0, "warm_ms": 0.0, "prefill_chunks": 0,
           "peak_blocks": 0, "workers": 0}
    for line in logs.splitlines():
        pos = line.find("KF_SERVE_TIMING ")
        if pos < 0:
            continue
        fields = dict(kv.split("=", 1) for kv in line[pos:].split()
                      if "=" in kv)
        agg["workers"] += 1
        agg["steps"] += int(fields.get("steps", 0))
        agg["decode_ms"] += float(fields.get("decode_ms", 0.0))
        agg["prefill_ms"] += float(fields.get("prefill_ms", 0.0))
        agg["control_ms"] += float(fields.get("control_ms", 0.0))
        agg["warm_ms"] += float(fields.get("warm_ms", 0.0))
        agg["prefill_chunks"] += int(fields.get("prefill_chunks", 0))
        agg["peak_blocks"] = max(agg["peak_blocks"],
                                 int(fields.get("peak_blocks", 0)))
    for k in ("decode_ms", "prefill_ms", "control_ms", "warm_ms"):
        agg[k] = round(agg[k], 1)
    busy = agg["decode_ms"] + agg["prefill_ms"] + agg["control_ms"]
    agg["control_share"] = (round(agg["control_ms"] / busy, 3)
                            if busy else None)
    return agg


def _pct(lat, q):
    # the ledger's nearest-rank helper: ONE implementation for the
    # published rows and the /serve/stats SLO signal
    from kungfu_tpu.serve.ledger import percentile

    return round(percentile(lat, q), 1)


def measure_cell(np_: int, requests: int, gen_len: int,
                 port_range: str, timeout: int,
                 grow_when_done=None, schedule: str = "",
                 markers=None) -> dict:
    """One (np, request-mix) cell through the real elastic cluster."""
    from kungfu_tpu.serve.harness import (SERVE_MARKERS,
                                          default_requests,
                                          run_serve_cluster)

    out = run_serve_cluster(
        default_requests(requests, gen_len=gen_len),
        schedule=schedule,
        start_np=np_,
        slots=max(4, np_ + 1),
        warmup=np_,
        grow_when_done=grow_when_done,
        extra_env={"KF_SERVE_MAX_BATCH": str(MAX_BATCH),
                   "KF_SERVE_PREFILL_CHUNK": str(PREFILL_CHUNK)},
        port_range=port_range,
        timeout=timeout,
        markers=markers if markers is not None else SERVE_MARKERS,
    )
    lat = _latencies(out["results"])
    toks = sum(len(r["tokens"]) for r in out["results"])
    resumed = sum(1 for r in out["results"] if r["leases"] > 1)
    return {
        "np": np_,
        "requests": requests,
        "gen_len": gen_len,
        "completed": sum(1 for r in out["results"]
                         if r["state"] == "done"),
        "p50_ms": _pct(lat, 50),
        "p99_ms": _pct(lat, 99),
        "tokens_per_sec": round(toks / out["measured_wall_s"], 1),
        "measured_wall_s": out["measured_wall_s"],
        "resumed_requests": resumed,
        "timing": _timing(out["logs"]),
    }


def measure_prefix_cell(np_: int, requests: int, gen_len: int,
                        prefix_len: int, port_range: str,
                        timeout: int) -> dict:
    """The prefix-heavy workload (one long common prefix, short
    unique tails), with CoW prefix sharing + chunked prefill ON vs
    OFF: tok/s and the peak-blocks-in-use collapse."""
    from kungfu_tpu.serve.harness import (SERVE_MARKERS,
                                          prefix_requests,
                                          run_serve_cluster)

    reqs = prefix_requests(requests, prefix_len=prefix_len,
                           gen_len=gen_len)
    lo, hi = port_range.split("-")
    mid = (int(lo) + int(hi)) // 2
    cell = {"np": np_, "requests": requests, "gen_len": gen_len,
            "prefix_len": prefix_len}
    for label, env, ports in (
            ("sharing_on",
             {"KF_SERVE_SHARE_PREFIX": "1",
              "KF_SERVE_PREFILL_CHUNK": "16"},
             f"{lo}-{mid}"),
            ("sharing_off",
             {"KF_SERVE_SHARE_PREFIX": "0",
              "KF_SERVE_PREFILL_CHUNK": "0"},
             f"{mid + 1}-{hi}")):
        out = run_serve_cluster(
            reqs, start_np=np_, slots=max(4, np_ + 1), warmup=np_,
            extra_env={"KF_SERVE_MAX_BATCH": str(MAX_BATCH), **env},
            port_range=ports, timeout=timeout, markers=SERVE_MARKERS)
        lat = _latencies(out["results"])
        toks = sum(len(r["tokens"]) for r in out["results"])
        timing = _timing(out["logs"])
        cell[label] = {
            "completed": sum(1 for r in out["results"]
                             if r["state"] == "done"),
            "p50_ms": _pct(lat, 50),
            "p99_ms": _pct(lat, 99),
            "tokens_per_sec": round(toks / out["measured_wall_s"], 1),
            "peak_blocks": timing["peak_blocks"],
            "prefill_ms": timing["prefill_ms"],
            "prefill_chunks": timing["prefill_chunks"],
        }
    on, off = cell["sharing_on"], cell["sharing_off"]
    cell["blocks_collapse"] = (
        round(off["peak_blocks"] / on["peak_blocks"], 2)
        if on["peak_blocks"] else None)
    cell["speedup"] = (
        round(on["tokens_per_sec"] / off["tokens_per_sec"], 2)
        if off["tokens_per_sec"] else None)
    return cell


def measure(np_list=(1, 2, 4), requests: int = 16, gen_len: int = 48,
            port_base: int = 28100, timeout: int = 420,
            prefix_len: int = 48) -> dict:
    """The np sweep + the mid-traffic-resize cell + the prefix-heavy
    sharing on/off cell."""
    from kungfu_tpu.serve.harness import RESIZE_MARKERS

    rows = []
    port = port_base
    for np_ in np_list:
        rows.append(measure_cell(
            np_, requests, gen_len,
            port_range=f"{port}-{port + 99}", timeout=timeout))
        print(json.dumps({"cell": "steady", **rows[-1]}), flush=True)
        port += 100
    # the elastic cell: grow 2 -> 3 through the consensus path while
    # traffic is in flight. The fast path drains the default mix in
    # 1-2s — SHORTER than a joiner's import + model init + weight
    # adoption — so this cell carries 8x the requests (the tier must
    # still be decoding when the joiner lands) and the grow fires as
    # soon as the first measured request completes. The tail cost is
    # reported against an undisturbed np=2 cell of the SAME heavier
    # mix, so the ratio isolates the resize, not the queue depth.
    r_requests = requests * 8
    steady_heavy = measure_cell(
        2, r_requests, gen_len,
        port_range=f"{port}-{port + 99}", timeout=timeout)
    print(json.dumps({"cell": "steady_heavy", **steady_heavy}),
          flush=True)
    port += 100
    resize = measure_cell(
        2, r_requests, gen_len,
        port_range=f"{port}-{port + 99}", timeout=timeout,
        grow_when_done=2 + 1,
        markers=RESIZE_MARKERS)
    resize["grew_to"] = 3
    print(json.dumps({"cell": "resize", **resize}), flush=True)
    port += 100
    prefix = measure_prefix_cell(
        2, requests, max(gen_len // 4, 4), prefix_len,
        port_range=f"{port}-{port + 199}", timeout=timeout)
    print(json.dumps({"cell": "prefix", **prefix}), flush=True)
    return {
        "cells": rows,
        "steady_heavy_cell": steady_heavy,
        "resize_cell": resize,
        "prefix_cell": prefix,
        # the tail cost of the resize, relative to the same traffic
        # on an undisturbed np=2 tier
        "p99_through_resize_over_steady": (
            round(resize["p99_ms"] / steady_heavy["p99_ms"], 3)
            if steady_heavy["p99_ms"] else None),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--np", type=int, nargs="+", default=[1, 2, 4])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=48)
    ap.add_argument("--timeout", type=int, default=420)
    ap.add_argument("--port-base", type=int, default=28100)
    args = ap.parse_args(argv)
    res = measure(tuple(args.np), requests=args.requests,
                  gen_len=args.gen_len, port_base=args.port_base,
                  timeout=args.timeout)
    result = {
        "config": (
            f"elastic decode tier: tiny GPT, {args.requests} "
            f"requests x {args.gen_len} generated tokens per cell, "
            f"per-worker continuous batch {MAX_BATCH}, paged KV "
            "(16-token blocks), warm-tier measurement (warmup batch "
            "absorbs boot+jit); ONE batched /serve/append_batch round "
            "trip per decode iteration (stats piggybacked) — the "
            "per-cell timing block splits decode/prefill/control wall "
            "time; resize cell carries an 8x request mix (traffic "
            "must outlast the joiner's boot) and grows 2->3 via "
            "/addworker mid-traffic with completion + ledger "
            "invariants gated, p99 compared against a same-mix "
            "undisturbed cell; prefix cell "
            "drives a prefix-heavy mix with CoW sharing + chunked "
            "prefill on vs off (1-core loopback: absolute ms are "
            "container artifacts; the portable result is the "
            "completion guarantee, the control_share trend and the "
            "peak-blocks collapse)"
        ),
        **res,
    }
    print(json.dumps({"metric": "serve_elastic_latency",
                      "value": res["resize_cell"]["p99_ms"],
                      "unit": "ms (p99 through mid-traffic resize)",
                      "details": result}), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
