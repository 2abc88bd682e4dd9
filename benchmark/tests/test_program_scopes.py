"""The two metrics that read the program's own scopes
(`kungfu_tpu/trace/scopes.py`), on a synthetic trace through their own
files' `args`: `opt_update_ms` takes the optimizer's arithmetic and
leaves out the gradient all-reduce `sync_sgd` nests inside it;
`fused_ce_ms` takes the backward's head matmuls in with the kernels."""

import os

import pytest

from benchmark import trace_reduce as tr
from benchmark.runners.train import read_metrics

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

STEP = "jit(device_step)/shard_map"
# (name, category, scope path, microseconds): one whole step of 1000 us
OPS = [
    ("fusion.1", "loop fusion", f"{STEP}/kf.opt_update/add", 300.0),
    ("all-reduce.1", "all-reduce",
     f"{STEP}/kf.opt_update/kf.grad_sync/psum", 200.0),
    ("fusion.2", "convolution fusion",
     f"{STEP}/transpose(jvp())/kf.fused_ce/dot_general", 150.0),
    ("custom-call.1", "custom-call",
     f"{STEP}/jvp(kf.fused_ce)/pallas_call", 100.0),
    ("custom-call.2", "custom-call",
     f"{STEP}/jvp(GPTLM)/Block_0/CausalSelfAttention_0/pallas_call", 50.0),
    ("copy-done.1", "data formatting", "", 25.0),
]


@pytest.fixture(scope="module")
def trace():
    dev = tr.DeviceTrace("/device:TPU:0", steps=[(0.0, 1000.0)])
    at = 0.0
    for name, category, tf_op, dur in OPS:
        dev.ops.append(tr.Op(name, at, dur, category, tf_op))
        at += dur
    return tr.Trace([dev])


@pytest.mark.parametrize("metric, expected", [
    ("opt_update_ms", 0.300),          # the add; not the psum under it
    ("fused_ce_ms", 0.250),            # backward matmul + forward kernel
    ("optimizer_ms", 0.525),           # by exclusion: add, psum, copy
    ("pallas_ms", 0.150),              # CE kernel + flash kernel
])
def test_metric_on_synthetic_trace(trace, metric, expected):
    got = read_metrics([metric], trace, {}, ROOT)
    assert got[metric] == pytest.approx(expected, rel=1e-12)


def test_a_step_without_the_scopes_reports_neither(trace):
    # the parent of the PR that named the step: nothing to read, the
    # metric is left out and nothing raises
    (dev,) = trace.devices
    bare = tr.Trace([tr.DeviceTrace(dev.name, steps=dev.steps, ops=[
        tr.Op(o.name, o.start, o.dur, o.category,
              o.tf_op.replace("kf.opt_update/", "")
              .replace("kf.grad_sync/", "")
              .replace("kf.fused_ce", ""))
        for o in dev.ops])])
    assert read_metrics(["opt_update_ms", "fused_ce_ms"], bare, {},
                        ROOT) == {}
