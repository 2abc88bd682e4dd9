"""The benchmark's command: run one cell once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about the cell is data: `workloads/<cell>.json` names its
configuration, chips, traffic mix and metrics; `configs/<config>.json`
its sizes, its runner (`runners/<kind>.py`) and its adapter
(`adapters/<name>.py`); `traffic/<mix>.json` the batch, lengths and
ring; `metrics/<metric>.json` each metric's unit and reader
(`readers/<module>.py`). This file holds no name of any of them.

The last line printed is the result: `correct`, `attempted`, `failed`,
`metrics` (end-to-end with `--trace 0`, per-layer with `--trace 1`),
`device`, and `breakdown` when traced. Without a TPU, with fewer chips
than the cell asks for, or on a device that `peaks.json` does not list,
it exits non-zero and prints no result. `--rehearse 1` runs the cell's
tiny twin on the CPU (virtual devices for several chips) to prove the
control flow; it never prints `correct: true`.
"""

import time

T_START = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(kind, name):
    path = os.path.join(ROOT, "benchmark", kind, f"{name}.json")
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SystemExit(f"no such file: benchmark/{kind}/{name}.json")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    cell = load("workloads", args.workload)
    config = load("configs", cell["config"])
    traffic = load("traffic", cell["traffic"])
    if args.rehearse:  # the tiny twin, on the CPU, before JAX is imported
        config = {**config, **config.get("rehearsal", {})}
        traffic = {**traffic, **traffic.get("rehearsal", {})}
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count="
            f"{cell['chips']}")
    runner = importlib.import_module(
        f"benchmark.runners.{config['runner']}")
    result = runner.run(cell, config, traffic, args, ROOT, T_START)

    units = {name: load("metrics", name)["unit"]
             for name in result["metrics"]}
    result["metrics"] = {
        name: {"value": value, "unit": units[name]}
        for name, value in result["metrics"].items()}
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
