"""The three metrics of `ouro-2.6b.train-b1-t4096` through their own
files' `args`: on a synthetic trace whose scope paths are the ones the
program's lowered step carries (`tests/test_ouro.py` holds those on the
program's side), and on a cut of the builder's own chip trace
(`fixtures/ouro-2.6b.train-b1-t4096.trace.json.gz`). Each selects its
operations and leaves the others'; a step without the scopes (the
parent's) reports none of them and raises nothing."""

import json
import os

import pytest

from benchmark import flops_ouro
from benchmark import trace_reduce as tr
from benchmark.runners.train import read_metrics

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURE = os.path.join(ROOT, "benchmark", "fixtures",
                       "ouro-2.6b.train-b1-t4096.trace.json.gz")
NEW = ["loop_stack_ms", "loop_exit_ms", "loop_flash_roofline"]

FWD = "jit(step)/jvp(OuroLM)/kf.loop_stack/stack"
BWD = ("jit(step)/transpose(jvp(OuroLM))/kf.loop_stack/stack/jvp(OuroLM)"
       "/kf.loop_stack/stack")
# (name, category, scope path, microseconds): one whole step of 2000 us
OPS = [
    ("custom-call.1", "custom-call",
     f"{FWD}/Block_0/RotaryAttention_0/pallas_call", 200.0),
    ("custom-call.2", "custom-call",
     f"{BWD}/Block_0/RotaryAttention_0/pallas_call", 300.0),
    ("custom-call.3", "custom-call",
     f"{BWD}/Block_7/RotaryAttention_0/pallas_call", 300.0),
    ("fusion.1", "convolution fusion",
     f"{FWD}/Block_1/RotaryAttention_0/q/dot_general", 100.0),
    ("fusion.2", "convolution fusion",
     f"{BWD}/rematted_computation/Block_0/mlp/gate/dot_general", 150.0),
    ("fusion.3", "loop fusion", f"{BWD}/Block_0/mlp/gate/add_any", 50.0),
    ("fusion.4", "loop fusion", f"{FWD}/final_norm/mul", 25.0),
    ("fusion.5", "convolution fusion",
     "jit(step)/jvp(OuroLM)/kf.loop_exit/exit_gate/dot_general", 30.0),
    ("fusion.6", "loop fusion",
     "jit(step)/transpose(jvp(kf.loop_exit))/mul", 45.0),
    ("custom-call.6", "custom-call",
     "jit(step)/jvp(kf.fused_ce)/pallas_call", 120.0),
    ("fusion.7", "convolution fusion",
     "jit(step)/transpose(jvp(kf.fused_ce))/dot_general", 180.0),
    ("fusion.8", "loop fusion", "jit(step)/kf.opt_update/add", 350.0),
]


@pytest.fixture(scope="module")
def ctx():
    def load(kind, name):
        with open(os.path.join(ROOT, "benchmark", kind,
                               f"{name}.json")) as f:
            return json.load(f)

    return {"config": load("configs", "ouro-2.6b"),
            "traffic": load("traffic", "train-b1-t4096"), "chips": 1,
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def trace_of(ops):
    dev = tr.DeviceTrace("/device:TPU:0", steps=[(0.0, 2000.0)])
    at = 0.0
    for name, category, tf_op, dur in ops:
        dev.ops.append(tr.Op(name, at, dur, category, tf_op))
        at += dur
    return tr.Trace([dev])


@pytest.mark.parametrize("metric, expected_ms", [
    ("loop_stack_ms", 1.125),   # kernels, matmuls, the dW add, the norm
    ("loop_exit_ms", 0.075),    # the gate's matmul, the weighting's mul
    ("pallas_ms", 0.920),       # flash and the CE kernel
    ("fused_ce_ms", 0.300),     # outside both loop scopes
    ("opt_update_ms", 0.350),
])
def test_time_metrics_select_their_scopes(ctx, metric, expected_ms):
    got = read_metrics([metric], trace_of(OPS), ctx, ROOT)
    assert got[metric] == pytest.approx(expected_ms, rel=1e-12)


def test_roofline_divides_the_counted_work_by_the_kernels_time(ctx):
    got = read_metrics(["loop_flash_roofline"], trace_of(OPS), ctx, ROOT)
    least = flops_ouro.flash_train_step(
        ctx["config"], ctx["traffic"], 1)["flops"] / 197e12
    # the kernels directly under RotaryAttention_<n>: 800 us, not `q`
    assert got["loop_flash_roofline"] == pytest.approx(
        100 * least / 800e-6, rel=1e-12)


def test_a_step_without_the_scopes_reports_none_of_them(ctx):
    # the parent's program (another model): the readers find nothing,
    # the metrics are left out, nothing raises
    bare = trace_of([
        ("custom-call.1", "custom-call",
         "jit(step)/jvp(GPTLM)/Block_0/CausalSelfAttention_0/pallas_call",
         300.0),
        ("custom-call.2", "custom-call",
         "jit(step)/jvp(GlmMoeLM)/Block_0/kf.mla/MLAttention_0/pallas_call",
         300.0),
        ("fusion.5", "loop fusion", "jit(step)/kf.opt_update/add", 350.0)])
    assert read_metrics(NEW, bare, ctx, ROOT) == {}


# -- the same files on a cut of the builder's chip trace ----------------------
# (my chip run, PR 32, seed 3200000041: `fixtures/cut_trace.py`'s cut, two
# whole steps of the 150 longest operations each, plus the operations
# under kf.loop_exit of those steps, which are all too short to be among
# the 150; taken before `ops/fused_ce_rows.py` chose the target column
# of d from the row's f32 loss, which adds 2.6 ms to `fused_ce_ms`)


@pytest.fixture(scope="module")
def chip_trace():
    return tr.reduce(tr.load(FIXTURE))


def test_on_the_chips_trace_every_new_metric_finds_its_operations(
        ctx, chip_trace):
    (dev,) = chip_trace.devices
    assert len(dev.steps) == 2
    got = read_metrics(NEW + ["pallas_ms", "fused_ce_ms"], chip_trace, ctx,
                       ROOT)
    assert set(NEW) <= set(got)
    # 96 flash kernels a step: 32 layer applications, forward, dq and
    # dkv, none of them a recomputed forward
    flash = tr.select(dev, tf_op=r"RotaryAttention_\d+/pallas_call")
    assert len(flash) == 2 * 96
    assert not [o for o in flash if "rematted_computation" in o.tf_op]
    assert all("kf.loop_stack" in o.tf_op for o in flash)
    # the share of the counted work: 33.5 ms over the kernels' 88.6
    assert got["loop_flash_roofline"] == pytest.approx(37.8, abs=0.1)
    assert 0 < got["loop_flash_roofline"] < 100
    # flash and the CE kernels are all the Pallas time there is
    ce = tr.ms_per_step(dev, tr.select(dev, tf_op=r"kf\.fused_ce.*pallas"))
    assert got["pallas_ms"] == pytest.approx(
        tr.ms_per_step(dev, flash) + ce, rel=1e-9)
    # the exit arithmetic is a third of a millisecond; the heads are not
    # in it, nor in the stack's time
    assert got["loop_exit_ms"] == pytest.approx(0.34, abs=0.01)
    assert not tr.select(dev, tf_op=r"kf\.loop_(stack|exit).*kf\.fused_ce")
    assert got["fused_ce_ms"] == pytest.approx(64.4, abs=0.1)
    # a cut keeps the longest operations only: most of the stack's
    # matmuls are gone, its kernels are not
    assert got["loop_stack_ms"] > tr.ms_per_step(dev, flash)


def test_the_older_cells_trace_reports_none_of_them(ctx):
    gpt = tr.reduce(tr.load(os.path.join(
        ROOT, "benchmark", "fixtures", "gpt2-small.train-b8.trace.json.gz")))
    assert read_metrics(NEW, gpt, ctx, ROOT) == {}
