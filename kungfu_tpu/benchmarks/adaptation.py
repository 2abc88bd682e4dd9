"""Adaptation benchmark: wall-clock cost of an online cluster resize.

Measures what the reference's adaptive fake trainer measures per resize
(reference: tests/go/cmd/kungfu-fake-adaptive-trainer, timing around the
resize call; benchmarks/adaptation/): the time from the step that triggers
a schedule-driven resize proposal to the first step of the new epoch —
i.e. propose + config-server round trip + digest consensus + runner churn
+ epoch barrier + state resync.

Driver:  python -m kungfu_tpu.benchmarks.adaptation --launch \\
             [--schedule 3:2,3:4,3:1] [--np 2] [--payload-mb 4]
Worker (spawned by the driver under kfrun -w): same module, no --launch.

Prints one line per resize: `resize <from>-><to> <ms> ms` and a final
summary on the surviving rank.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np


def worker(args) -> int:
    # control-plane-only worker: the launcher below passes
    # JAX_PLATFORMS=cpu, so no stray jnp call takes the chip
    import kungfu_tpu
    from kungfu_tpu.elastic import ElasticCallback

    p = kungfu_tpu.init()
    elastic = ElasticCallback(p, schedule=args.schedule, samples_per_step=1)
    # A model-sized payload with a realistic leaf structure: ~100
    # matrix-sized leaves plus a long tail of small ones (the GPT tree
    # shape), so the chunk schedule exercises both the single-span
    # view path and the coalesced small-leaf tail — one flat array
    # would make any chunking look free.
    leaf_bytes = args.payload_mb * 2**20
    big = [np.zeros(max(1, leaf_bytes // 100 // 4), np.float32)
           for _ in range(100)]
    tail = [np.zeros(64, np.float32) for _ in range(100)]
    payload = {"big": big, "tail": tail}
    if p.config.version > 0:
        elastic.sync_position()
    resize_ms = []
    while elastic.state.step < args.steps:
        out = p.all_reduce(np.ones(4, np.float32),
                           name=f"work:{p.version}:{elastic.state.step}")
        assert out[0] == p.size
        if args.step_ms:
            # emulate per-step compute: resizes then happen from steady
            # state (runner's warm pool populated, imports finished)
            # instead of milliseconds after cluster boot
            time.sleep(args.step_ms / 1e3)
        old_size = p.size
        t0 = time.perf_counter()
        if elastic.after_step():
            if not elastic.state.keep:
                return 0  # evicted
            payload = elastic.resync_params(payload,
                                            chunk_mb=args.chunk_mb)
            ms = (time.perf_counter() - t0) * 1e3
            resize_ms.append(ms)
            # phase decomposition (VERDICT r5 item 7): where inside the
            # resize window the milliseconds actually go — the consensus
            # wait (includes the joiner's boot on a grow), the native
            # epoch adopt + join barrier, and the state resync (pack/
            # broadcast/overlap under the chunked streaming path)
            ph = elastic.last_resize_timings
            detail = " ".join(f"{k}={v:.1f}" if isinstance(v, float)
                              else f"{k}={v}" for k, v in ph.items())
            print(f"resize {old_size}->{p.size} {ms:.1f} ms | "
                  f"chunk_mb={args.chunk_mb} {detail}", flush=True)
    if p.rank == 0 and resize_ms:
        print(
            f"adaptation np0={args.np} resizes={len(resize_ms)} "
            f"payload={args.payload_mb}MiB chunk_mb={args.chunk_mb} "
            f"mean={np.mean(resize_ms):.1f} ms "
            f"max={np.max(resize_ms):.1f} ms",
            flush=True,
        )
    return 0


def resize_phases_from_trace(trace_dir: str) -> list:
    """Per-resize phase decomposition from kftrace flight records.

    Each `resize.resync` span (`elastic/hooks.py`) carries the full
    `last_resize_timings` dict in its args — the same numbers the
    worker prints on its `resize a->b` stdout line. Reading them from
    the structured events replaces the stdout-regex path when the run
    was launched with tracing (the marker parse in `sweep()` remains
    the fallback). Returns one dict per rank-0 resize span (the root
    pays the pack+broadcast the sweep decomposes), sorted by time.
    `total_ms` here is the resync window — the payload-bound part the
    sweep exists to decompose; the stdout fallback's total also
    includes the consensus wait upstream of it."""
    from kungfu_tpu.trace.export import merge_sources, read_flight_dir

    events, _ = merge_sources(read_flight_dir(trace_dir))
    rows = []
    for e in events:
        if e.get("name") != "resize.resync" or e.get("ph") != "X":
            continue
        if e.get("rank", -1) != 0:
            continue
        d = {"t_ms": e["ts"] / 1e3,
             "total_ms": e.get("dur", 0) / 1e3,
             "step": e.get("step"), "version": e.get("version")}
        for k, v in (e.get("args") or {}).items():
            if isinstance(v, (int, float)):
                d[k] = float(v)
        rows.append(d)
    return sorted(rows, key=lambda d: d["t_ms"])


def _run_schedule(args, chunk_mb, logdir, capture: bool,
                  trace_dir: str = ""):
    """Boot config server + elastic kfrun around one schedule run.

    Returns the CompletedProcess (output captured when `capture`) —
    the single launch body `launch()` and `sweep()` share. With
    `trace_dir`, the cluster runs under KF_TRACE=1 and flight-dumps
    there (the structured decomposition source)."""
    import subprocess

    from kungfu_tpu.elastic import ConfigServer
    from kungfu_tpu.plan import free_port

    server = ConfigServer(port=0).start()
    try:
        env = dict(os.environ)
        env.setdefault("KF_TIMEOUT_MS", "60000")
        env.setdefault("KF_LOG_LEVEL", "warn")
        if trace_dir:
            env["KF_TRACE"] = "1"
            env["KF_TRACE_DIR"] = trace_dir
        # control-plane-only workers: no accelerator needed, and the
        # benchmark must not serialize on the machine's single TPU
        env["JAX_PLATFORMS"] = "cpu"
        cmd = [
            sys.executable, "-m", "kungfu_tpu.run",
            "-np", str(args.np), "-H", f"127.0.0.1:{args.max_np}",
            "-port-range", args.port_range,
            "-runner-port", str(free_port()),
            "-w", "-config-server", server.get_url,
            "-logdir", logdir,
            "--", sys.executable, "-m", "kungfu_tpu.benchmarks.adaptation",
            "--schedule", args.schedule, "--steps", str(args.steps),
            "--payload-mb", str(args.payload_mb), "--np", str(args.np),
            "--step-ms", str(args.step_ms),
        ]
        if chunk_mb is not None:
            cmd += ["--chunk-mb", str(chunk_mb)]
        return subprocess.run(cmd, env=env, capture_output=capture,
                              text=capture)
    finally:
        server.stop()


def launch(args) -> int:
    return _run_schedule(args, args.chunk_mb, args.logdir,
                         capture=False).returncode


def sweep(args) -> int:
    """Run the resize schedule once per --chunk-mb value and publish
    the pack/broadcast/overlap decomposition per value (0 = the
    monolithic pack_bytes baseline). One JSON line per value, plus a
    trailing summary — the BASELINE row for the chunked-streaming
    resync comes from here."""
    import json
    import re

    results = []
    for chunk_mb in args.chunk_mb_sweep:
        # rerun the launch body with output captured so the per-resize
        # decomposition can be aggregated here; each run flight-dumps
        # into its own trace dir — the structured source
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            proc = _run_schedule(args, chunk_mb,
                                 f"{args.logdir}-c{chunk_mb:g}",
                                 capture=True,
                                 trace_dir="" if args.no_trace else td)
            sys.stderr.write(proc.stderr)
            phases = ([] if args.no_trace
                      else resize_phases_from_trace(td))
        source = "kftrace" if phases else "markers"
        if phases:
            # structured path: sizes come from the resize.resync span
            # args; derive from/to by walking from the launch size
            prev = args.np
            for d in phases:
                d["from"] = prev
                d["to"] = int(d.get("size", prev))
                prev = d["to"]
        else:
            # fallback: regex over the worker's stdout lines (runs
            # with tracing off, or a trace that failed to land).
            # Worker lines arrive through kfrun's log tee with a
            # colored per-rank prefix, on either stream — search,
            # don't anchor.
            for line in (proc.stdout + "\n" + proc.stderr).splitlines():
                m = re.search(r"resize (\d+)->(\d+) ([\d.]+) ms \| (.*)",
                              line)
                if not m:
                    continue
                d = {"from": int(m.group(1)), "to": int(m.group(2)),
                     "total_ms": float(m.group(3))}
                for kv in m.group(4).split():
                    k, _, v = kv.partition("=")
                    try:
                        d[k] = float(v)
                    except ValueError:
                        pass
                phases.append(d)
        # the grow resizes (to > from) carry the joiner broadcast —
        # the payload-bound phase this sweep exists to decompose
        grows = [d for d in phases if d["to"] > d["from"]]
        agg = {}
        for key in ("pack_ms", "broadcast_ms", "overlap_ms",
                    "position_ms", "total_ms"):
            vals = [d[key] for d in grows if key in d]
            if vals:
                agg[key] = round(float(np.mean(vals)), 1)
        # `source` matters for cross-row comparability: the kftrace
        # total_ms covers the resync window only, while the stdout
        # fallback's total also includes the consensus wait — a row
        # that silently fell back must be identifiable as such
        row = {"chunk_mb": chunk_mb, "resizes": len(phases),
               "grows": len(grows), "payload_mb": args.payload_mb,
               "source": source, "rc": proc.returncode, **agg}
        results.append(row)
        print(json.dumps({"metric": "elastic_resync_chunk_sweep",
                          "value": agg.get("total_ms"),
                          "unit": "ms/grow-resize", "details": row}),
              flush=True)
    baseline = next((r for r in results if r["chunk_mb"] == 0), None)
    if baseline and len(results) > 1:
        base = baseline.get("pack_ms", 0) + baseline.get(
            "broadcast_ms", 0)
        for r in results:
            if r["chunk_mb"] == 0 or not base:
                continue
            pb = r.get("pack_ms", 0) + r.get("broadcast_ms", 0)
            r["pack_bcast_vs_monolithic"] = round(pb / base, 3)
        print(json.dumps({"metric": "elastic_resync_chunk_sweep_summary",
                          "details": results}), flush=True)
    # any nonzero child rc fails the sweep (max() would mask a
    # signal-killed child's negative returncode behind a 0)
    return next((1 for r in results if r["rc"]), 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--launch", action="store_true",
                    help="boot config server + elastic kfrun around self")
    ap.add_argument("--schedule", default="3:2,3:4,3:1",
                    help="steps:size,... resize schedule")
    ap.add_argument("--steps", type=int, default=9)
    ap.add_argument("--np", type=int, default=2, help="initial cluster size")
    ap.add_argument("--max-np", type=int, default=8, help="host slot count")
    ap.add_argument("--payload-mb", type=int, default=4,
                    help="joiner-broadcast payload size")
    ap.add_argument("--step-ms", type=int, default=0,
                    help="per-step sleep emulating compute (steady-state "
                         "resizes vs boot-transient ones)")
    ap.add_argument("--chunk-mb", type=float, default=None,
                    help="streaming-resync chunk size in MiB (0 = the "
                         "monolithic pack_bytes path; default = "
                         "KF_STREAM_CHUNK_MB or the module default)")
    ap.add_argument("--chunk-mb-sweep", dest="chunk_mb_sweep",
                    type=lambda s: [float(x) for x in s.split(",")],
                    default=None, metavar="0,1,4,16",
                    help="(driver) rerun the schedule once per chunk "
                         "size and publish the pack/broadcast/overlap "
                         "decomposition per value (0 = monolithic "
                         "baseline)")
    ap.add_argument("--port-range", default="27000-27999")
    ap.add_argument("--logdir", default=".kf-adaptation-logs")
    ap.add_argument("--no-trace", action="store_true",
                    help="(driver) decompose resizes from worker "
                         "stdout lines instead of kftrace flight "
                         "records")
    args = ap.parse_args(argv)
    if args.chunk_mb_sweep:
        return sweep(args)
    return launch(args) if args.launch else worker(args)


if __name__ == "__main__":
    sys.exit(main())
