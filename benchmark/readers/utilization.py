"""Shares of the chip's published peaks (`benchmark/peaks.json`), with
the work counted by `benchmark/flops.py` and the time read from the
device trace. No clamp: a share above 100% is a fault to find."""

import importlib

from benchmark import trace_reduce as tr
from benchmark.readers import device_steps


def counter(name):
    """`flops.gpt_train_step` -> that function of `benchmark/flops.py`:
    a later configuration brings a module of its own."""
    module, fn = name.rsplit(".", 1)
    return getattr(importlib.import_module(f"benchmark.{module}"), fn)


def mfu(trace, ctx):
    """Model FLOP/s utilization: the train step's forward and backward
    matmul work (the function the configuration's file names) over the
    median device step, the chips and the bf16 peak."""
    work = counter(ctx["config"]["flops"])(
        ctx["config"], ctx["traffic"], ctx["chips"])
    peak = ctx["peak"]["bf16_flops_per_s"] * ctx["chips"]
    return [100.0 * work / (ms / 1000.0) / peak
            for ms in device_steps.median_ms(trace, ctx)]


def kernel_roofline(trace, ctx, work, **patterns):
    """A kernel's share of its roofline: the least time one chip could
    take for the step's calls (the larger of FLOPs over the bf16 peak
    and bytes over the HBM peak, `work` naming the function that counts
    them) over the time of the operations `patterns` select."""
    need = counter(work)(ctx["config"], ctx["traffic"], ctx["chips"])
    least_s = max(need["flops"] / ctx["peak"]["bf16_flops_per_s"],
                  need["bytes"] / ctx["peak"]["hbm_bytes_per_s"])
    out = []
    for dev in trace.devices:
        ops = tr.select(dev, **patterns)
        if not ops:
            return None
        out.append(100.0 * least_s / (tr.ms_per_step(dev, ops) / 1000.0))
    return out
