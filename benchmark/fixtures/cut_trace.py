"""Cut a small fixture out of a real profiler trace.

    python3 benchmark/fixtures/cut_trace.py <in.trace.json.gz> <out.trace.json.gz>

Keeps four consecutive steps of every device (two whole ones once the
reducer has dropped the first and the last), of each step the 150
longest operations, the benchmark's host spans over that stretch and
the process/thread names; of each operation's arguments only what the
readers select by. Dropping the short operations opens idle gaps the
real trace does not have, which is what a test of `device_idle` wants.
"""

import gzip
import json
import sys

KEEP_ARGS = ("hlo_category", "tf_op", "model_flops", "bytes_accessed")
FIRST_STEP, N_STEPS, OPS_PER_STEP = 10, 4, 150


def cut(raw):
    events = raw["traceEvents"]
    meta = [e for e in events if e.get("ph") == "M"
            and e["name"] in ("process_name", "thread_name")]
    planes = {e["pid"]: e["args"]["name"] for e in meta
              if e["name"] == "process_name"}
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in meta
               if e["name"] == "thread_name"}
    out, t_min, t_max = [], float("inf"), 0.0
    for pid, plane in planes.items():
        if not plane.startswith("/device:TPU:"):
            continue
        line = lambda e, name: (  # noqa: E731
            e.get("ph") == "X" and e["pid"] == pid
            and threads.get((pid, e["tid"])) == name)
        steps = sorted((e for e in events if line(e, "Steps")),
                       key=lambda e: e["ts"])
        steps = steps[FIRST_STEP:FIRST_STEP + N_STEPS]
        out += steps
        for s in steps:
            t0, t1 = s["ts"], s["ts"] + s["dur"]
            t_min, t_max = min(t_min, t0), max(t_max, t1)
            ops = [e for e in events
                   if line(e, "XLA Ops") and t0 <= e["ts"] < t1]
            ops.sort(key=lambda e: -e["dur"])
            for e in ops[:OPS_PER_STEP]:
                out.append({**e, "args": {
                    k: e["args"][k] for k in KEEP_ARGS
                    if k in e.get("args", {})}})
    for e in events:
        if (e.get("ph") == "X" and planes.get(e["pid"]) == "/host:CPU"
                and e["name"].startswith("kfb.")
                and t_min - 2e5 <= e["ts"] < t_max):
            out.append(e)
    used = {(e["pid"], e.get("tid")) for e in out}
    meta = [e for e in meta if (e["pid"], e.get("tid")) in used
            or (e["name"] == "process_name"
                and any(p == e["pid"] for p, _ in used))]
    out.sort(key=lambda e: e["ts"])
    return {"traceEvents": meta + out}


if __name__ == "__main__":
    with gzip.open(sys.argv[1], "rt") as f:
        small = cut(json.load(f))
    with gzip.open(sys.argv[2], "wt") as f:
        json.dump(small, f, separators=(",", ":"))
    print(len(small["traceEvents"]), "events")
