"""Goodput under churn: replay the standard trace suite, print the
decomposition.

The operator-facing benchmark ROADMAP item 4 asks for: every canned
scenario (`kungfu_tpu/scenario/spec.py`: spot reclaim with cold
restore, one-worker preempt + re-grow, diurnal grow/drain, transient
straggler) is replayed through the REAL elastic runtime
(`scenario.runner.run_scenario`: kfrun + config server + the
continuity trainer under KF_TRACE=1) across cluster sizes, and each
run's merged flight-recorder stream is decomposed by
`trace.goodput.decompose` into the phase taxonomy
(docs/observability.md). Every cell gates on the decomposition
invariant — phases must sum to rank-active wallclock within
tolerance — so a goodput number can never silently ride an
incomplete trace.

The policy cell replays `straggler_transient` twice — under
`GoodputPolicy` (cost-aware ski-rental ride-out) and under
`NaiveStragglerPolicy` (shed on first sustained spike) — and records
the measured decision gap: the naive baseline pays a resize and
finishes one worker short, the goodput policy rides the transient out
at full size and wins on useful-samples/sec (the round-6
0.747-vs-0.185 straggler-retention gap, now priced per decision
instead of per strategy family).

Orchestrator (the only mode; every cell is a multi-process kfrun
cluster):

  python -m kungfu_tpu.benchmarks.goodput --np 2 3 4

1-core-container caveat (BASELINE.md): all np workers + runner +
config server timeshare ONE core, so wire/hook waits include core
contention and goodput ratios here are lower bounds; the DECISION
rows (resized-or-not, invariant, lost-step attribution) and the
phase *structure* are the portable results.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

#: the sweep members; flaky_net needs netns (scripts/chaos.sh only)
SCENARIOS = ("spot_preempt", "spot_kill_regrow", "spot_host_kill",
             "diurnal", "straggler_transient")


def _decompose_dir(trace_dir: str, device_batch: int):
    from kungfu_tpu.trace.export import read_flight_dir
    from kungfu_tpu.trace.goodput import decompose

    return decompose(read_flight_dir(trace_dir),
                     device_batch=device_batch)


def _row(run, decomp) -> dict:
    from kungfu_tpu.trace.goodput import PHASES

    t = decomp["totals"]
    wall = t["wall_ms"] or 1.0
    return {
        "goodput_ratio": decomp["goodput_ratio"],
        "useful_samples_per_sec": decomp.get("useful_samples_per_sec"),
        "useful_step_ranks": decomp["useful_step_ranks"],
        "lost_step_ranks": decomp["lost_step_ranks"],
        "restored_step": decomp.get("restored_step"),
        "phases_pct": {
            p: round(100.0 * t[f"{p}_ms"] / wall, 1)
            for p in PHASES
        },
        "other_pct": round(100.0 * t["other_ms"] / wall, 1),
        "wall_ms": t["wall_ms"],
        "relaunch_gap_s": run.relaunch_gap_s,
        "invariant_error_pct": decomp["invariant"]["error_pct"],
    }


def _replay_cell(name: str, np0: int, port_block: int,
                 policy: str = "", keep_dir: str = "") -> tuple:
    """One (scenario, np0) replay -> (ScenarioRun, decomposition)."""
    from kungfu_tpu.scenario import canned, run_scenario

    d = keep_dir or tempfile.mkdtemp(prefix=f"kf-goodput-{name}-")
    try:
        run = run_scenario(
            canned(name, np0=np0),
            trace_dir=os.path.join(d, "trace"),
            logdir=os.path.join(d, "logs"),
            policy=policy,
            port_range=f"{port_block}-{port_block + 59}")
        decomp = _decompose_dir(os.path.join(d, "trace"),
                                run.plan.device_batch)
        if not decomp["invariant"]["ok"]:
            raise RuntimeError(
                f"goodput invariant violated on {name} np0={np0}"
                f"{' policy=' + policy if policy else ''}: "
                f"{decomp['invariant']}")
        return run, decomp
    finally:
        if not keep_dir:
            shutil.rmtree(d, ignore_errors=True)


def measure(np_list, scenarios=SCENARIOS, port_base: int = 27100,
            verbose: bool = True) -> dict:
    """The scenario x np sweep + the policy-decision cell."""
    rows: dict = {}
    block = port_base
    for name in scenarios:
        rows[name] = {}
        for np0 in np_list:
            t0 = time.perf_counter()
            run, decomp = _replay_cell(name, np0, block)
            block += 60
            rows[name][str(np0)] = _row(run, decomp)
            if verbose:
                print(f"  {name} np0={np0}: goodput "
                      f"{decomp['goodput_ratio']:.3f} "
                      f"useful={decomp['useful_step_ranks']} "
                      f"lost={decomp['lost_step_ranks']} "
                      f"({time.perf_counter() - t0:.0f}s)",
                      flush=True)

    # the priced decision: ride out vs shed a transient straggler
    comparison = {}
    for policy in ("naive_straggler", "goodput"):
        run, decomp = _replay_cell("straggler_transient", 2, block,
                                   policy=policy)
        block += 60
        comparison[policy] = {
            **_row(run, decomp),
            "resized": "resized:" in run.logs,
        }
        if verbose:
            print(f"  policy={policy}: goodput "
                  f"{decomp['goodput_ratio']:.3f} "
                  f"useful_samples_per_sec="
                  f"{decomp.get('useful_samples_per_sec')} "
                  f"resized={comparison[policy]['resized']}",
                  flush=True)
    n, g = comparison["naive_straggler"], comparison["goodput"]
    comparison["goodput_policy_wins"] = bool(
        not g["resized"] and n["resized"]
        and (g["useful_samples_per_sec"] or 0)
        > (n["useful_samples_per_sec"] or 0))
    return {"scenarios": rows, "policy_comparison": comparison}


def run_goodput(args) -> dict:
    res = measure(args.np, scenarios=args.scenarios,
                  port_base=args.port_base)
    ratios = [cell["goodput_ratio"]
              for per_np in res["scenarios"].values()
              for cell in per_np.values()]
    return {
        "config": (
            f"canned scenario replays x np in {args.np} through the "
            "real elastic runtime (kfrun + config server + SLP "
            "continuity trainer, KF_TRACE=1, loopback); each cell = "
            "trace.goodput.decompose over the run's merged "
            "flight-recorder stream, gated on the phase-sum "
            "invariant; policy cell = straggler_transient under "
            "GoodputPolicy vs NaiveStragglerPolicy at np0=2"
        ),
        "caveat": (
            "1-core container: all workers + runner + config server "
            "timeshare one core, so wire/hook waits include core "
            "contention and ratios are lower bounds; decision rows, "
            "lost-step attribution and the phase structure are the "
            "portable results"
        ),
        "mean_goodput_ratio": round(sum(ratios) / len(ratios), 4)
        if ratios else 0.0,
        **res,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--np", type=int, nargs="+", default=[2, 3, 4],
                    help="cluster sizes to sweep (default 2 3 4)")
    ap.add_argument("--scenarios", nargs="+", default=list(SCENARIOS),
                    choices=list(SCENARIOS),
                    help="canned scenarios to replay")
    ap.add_argument("--port-base", type=int, default=27100)
    args = ap.parse_args(argv)

    print(json.dumps(run_goodput(args)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
