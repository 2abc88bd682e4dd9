"""The `train` runner: one cell's training loop, timed as a user's is.

Set-up (all of it `setup_s`): reach the chip, let the cell's adapter
make parameters, optimizer state and a ring of seeded batches on the
device, compile through the persistent cache, run warm-up steps until
two in a row compile nothing, fence. Window: dispatch steps for
`--seconds`, cycling the ring; after dispatching step i fetch the loss
of step i - steps_ahead (the traffic's) and stamp the host clock: one
completion interval per step, as a loop that logs its loss gives. A
one-chip machine shares its host's cores: ~100 ms hiccups in the fetch
starve a device that is one step ahead (43 ms of slack) and took
0.6-6.5% off the rate's spread; four steps of queued work ride them
out (PERF.md section 6). Every hiccup still shows in `step_ms_p95`'s
intervals and on the `window` line's `longest`.

An adapter is a module `benchmark/adapters/<name>.py` with
`build(config, traffic, devices, seed) -> Job`.
"""

from __future__ import annotations

import collections
import contextlib
import importlib
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Callable

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
MAX_WARMUP = 8
# traced steps, over all chips: enough for a median, few enough that the
# trace stays some megabytes and under the export's million events (40
# steps of GPT-2-small on one chip: 10 MB, 230k events)
TRACE_SKIP, TRACE_STEPS = 5, 40


@dataclass
class Job:
    """What an adapter hands the runner."""

    step: Callable            # step(*state, batch) -> (*state, loss)
    state: tuple
    batches: list             # the ring, on the device
    unit: str                 # "tokens" | "images": names the rate
    units_per_step: int       # over all chips
    loss_at_init: float       # ln(vocab), ln(classes)
    # checks on the state after the window: name -> bool
    verify: Callable[[tuple], dict] = field(default=lambda state: {})


class CompileCounter:
    """Traces and compiles since the last `reset()`, from JAX's own
    monitoring events: a window that holds one is not steady state."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event in COMPILE_EVENTS:
            self.n += 1

    def reset(self):
        self.n = 0


def p95(values) -> float:
    """Nearest-rank 95th percentile."""
    s = sorted(values)
    return s[max(0, math.ceil(0.95 * len(s)) - 1)]


def log(**kw):
    """A line of context before the result line (the driver reads only
    the last line)."""
    print(json.dumps(kw), flush=True)


def optimizer(spec):
    """`{"name": "adamw", "learning_rate": 1e-4}` -> `optax.adamw(...)`:
    the configuration's file names the optax optimizer and its
    hyperparameters."""
    import optax

    return getattr(optax, spec["name"])(
        **{k: v for k, v in spec.items() if k != "name"})


def warm_up(job, compiles):
    """Steps until two in a row compile nothing (the GSPMD step hands
    its state back laid out otherwise and compiles again at its second
    call, PERF.md section 6). Returns the state and every loss."""
    state, ring = job.state, job.batches
    losses, quiet = [], 0
    while quiet < 2:
        if len(losses) >= MAX_WARMUP:
            raise SystemExit(f"still compiling after {MAX_WARMUP} steps")
        compiles.reset()
        *state, loss = job.step(*state, ring[len(losses) % len(ring)])
        losses.append(float(loss))
        quiet = quiet + 1 if compiles.n == 0 else 0
    return state, losses


def window(job, state, seconds, ahead, trace_dir, trace_steps):
    """Dispatch steps for `seconds`, letting the device run `ahead`
    steps ahead of the host: after dispatching step i, fetch the loss
    of step i - ahead. With a `trace_dir`, the profiler runs over
    `trace_steps` steps from step TRACE_SKIP on, fenced at their end.
    Returns (state, losses, completion stamps, seconds inside each
    dispatch call, window start)."""
    import jax

    ring = job.batches
    span = (jax.profiler.TraceAnnotation if trace_dir
            else lambda _name: contextlib.nullcontext())
    losses, stamps, calls, pending = [], [], [], collections.deque()
    i, tracing = 0, False

    def fetch(keep):
        while len(pending) > keep:
            with span("kfb.fetch"):
                losses.append(float(pending.popleft()))
            stamps.append(time.perf_counter())

    t0 = time.perf_counter()
    while True:
        if trace_dir and i == TRACE_SKIP:
            jax.profiler.start_trace(trace_dir)
            tracing = True
        called = time.perf_counter()
        with span("kfb.dispatch"):
            *state, loss = job.step(*state, ring[i % len(ring)])
        calls.append(time.perf_counter() - called)
        pending.append(loss)
        i += 1
        fetch(keep=ahead)
        over = time.perf_counter() >= t0 + seconds
        if tracing and (over or i == TRACE_SKIP + trace_steps):
            fetch(keep=0)  # the traced steps are done on the device
            tracing = False
            jax.profiler.stop_trace()
        if over:
            break
    fetch(keep=0)
    return state, losses, stamps, calls, t0


def run(cell, config, traffic, args, root, t_start):
    import jax

    from benchmark import chip
    from kungfu_tpu import compile_cache

    rehearse = bool(args.rehearse)
    devs = chip.devices_for(cell["chips"], rehearse)
    t_chip = time.perf_counter()
    peak = None if rehearse else chip.peak_for(devs[0].device_kind, root)
    cache = compile_cache.enable()
    # keep every program, however quick its compile: the second run of
    # a cell then finds all of them and set-up is steady
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = CompileCounter()

    adapter = importlib.import_module(
        f"benchmark.adapters.{config['adapter']}")
    job = adapter.build(config, traffic, devs, args.seed)
    t_built = time.perf_counter()
    state, warm_losses = warm_up(job, compiles)
    setup_s = time.perf_counter() - t_start
    log(phase="setup", setup_s=setup_s, reach_chip_s=t_chip - t_start,
        build_s=t_built - t_chip, warmup_s=t_start + setup_s - t_built,
        warmup_losses=warm_losses, compile_cache=cache.as_dict())

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(root, ".bench-trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    compiles.reset()
    state, losses, stamps, calls, t0 = window(
        job, state, args.seconds, traffic["steps_ahead"], trace_dir,
        TRACE_STEPS // len(devs))
    compiles_in_window = compiles.n
    steps = len(losses)
    elapsed = stamps[-1] - t0
    intervals = [b - a for a, b in zip([t0] + stamps[:-1], stamps)]

    n = len(job.batches)
    low, high = config["first_loss_band"]
    checks = {
        "losses_finite": all(math.isfinite(x) for x in losses),
        "first_loss_in_band":
            low <= warm_losses[0] - job.loss_at_init <= high,
        "loss_fell": steps >= 2 * n and sum(losses[-n:]) < sum(losses[:n]),
        "no_compile_in_window": compiles_in_window == 0,
        **job.verify(tuple(state)),
    }
    log(phase="window", steps=steps, elapsed_s=elapsed,
        step_ms_median=1000 * sorted(intervals)[steps // 2],
        step_ms_p95=1000 * p95(intervals), samples=steps,
        # where a hiccup sat: [step, seconds into the window, ms between
        # the fetches before and of it, ms of that inside the dispatch
        # call of the step after it]
        longest=[[k, stamps[k] - t0, 1000 * intervals[k],
                  1000 * calls[min(k + 1, steps - 1)]]
                 for k in sorted(range(steps),
                                 key=lambda k: -intervals[k])[:3]],
        first_cycle_loss=sum(losses[:n]) / n,
        last_cycle_loss=sum(losses[-n:]) / n,
        first_loss=warm_losses[0], loss_at_init=job.loss_at_init,
        checks=checks, memory_stats=devs[0].memory_stats())

    device = chip.device_line(devs)
    result = {
        "correct": all(checks.values()) and not rehearse,
        "attempted": steps,
        "failed": sum(not math.isfinite(x) for x in losses),
        "device": device,
    }
    if not args.trace:
        measured = {
            f"{job.unit}_per_s_chip":
                steps * job.units_per_step / elapsed / len(devs),
            "step_ms_p95": 1000 * p95(intervals),
            "setup_s": setup_s,
        }
        result["metrics"] = {k: measured[k] for k in cell["end_to_end"]}
    elif rehearse:  # no device plane on a CPU: nothing to reduce
        result["metrics"] = {}
    else:
        from benchmark import trace_reduce

        trace = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find_trace(trace_dir)))
        ctx = {"config": config, "traffic": traffic, "chips": len(devs),
               "peak": peak}
        result["metrics"] = read_metrics(
            cell["per_layer"], trace, ctx, root)
        device["busy_s"], device["window_s"] = (
            trace_reduce.device_seconds(trace))
        result["breakdown"] = trace_reduce.breakdown(trace)
    return result


def read_metrics(names, trace, ctx, root):
    """Each named metric through the reader its file names; on several
    chips the worst device's value. A reader that finds nothing returns
    nothing and the metric is left out."""
    out = {}
    for name in names:
        with open(os.path.join(root, "benchmark", "metrics",
                               f"{name}.json")) as f:
            spec = json.load(f)
        module, fn = spec["reader"].rsplit(".", 1)
        reader = getattr(importlib.import_module(
            f"benchmark.readers.{module}"), fn)
        values = reader(trace, ctx, **spec.get("args", {}))
        if values:
            worst = max if spec["better"] == "lower" else min
            out[name] = worst(values)
        else:
            print(f"metric {name}: nothing to read", file=sys.stderr)
    return out
