"""The six metrics of `glm-4.7-flash.train-b1-t8192` through their own
files' `args`, on a synthetic trace whose scope paths are the ones the
program's lowered step carries (`tests/test_device_scopes.py` holds
those on the program's side): each selects its operations and leaves
the others', and a step without the scopes (the parent's) reports none
of them and raises nothing."""

import json
import os

import pytest

from benchmark import flops_glm
from benchmark import trace_reduce as tr
from benchmark.runners.train import read_metrics

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

FWD = "jit(step)/jvp(GlmMoeLM)"
BWD = "jit(step)/transpose(jvp(GlmMoeLM))/jvp(GlmMoeLM)"
REMAT = f"{BWD}/rematted_computation"
MTP_BWD = ("jit(step)/transpose(jvp(GlmMoeLM))/kf.mtp/mtp/jvp(GlmMoeLM)"
           "/kf.mtp/mtp/rematted_computation")
# (name, category, scope path, microseconds): one whole step of 2000 us
OPS = [
    ("custom-call.1", "custom-call",
     f"{FWD}/Block_0/kf.mla/MLAttention_0/pallas_call", 300.0),
    ("custom-call.2", "custom-call",
     f"{BWD}/Block_0/kf.mla/MLAttention_0/pallas_call", 500.0),
    ("fusion.1", "convolution fusion",
     f"{FWD}/Block_1/kf.mla/MLAttention_0/q_b/dot_general", 100.0),
    ("custom-call.3", "custom-call",
     f"{MTP_BWD}/block/kf.mla/MLAttention_0/pallas_call", 200.0),
    # XLA:TPU names its grouped-matmul kernels itself and drops the path
    ("ragged-dot-none.4", "custom-call", "ragged-dot-none:", 140.0),
    ("ragged-dot-metadata.1", "custom-call", "ragged-dot-metadata:", 10.0),
    ("fusion.2", "convolution fusion",
     f"{FWD}/Block_1/moe/kf.moe_experts/shared/gate/dot_general", 50.0),
    ("fusion.6", "loop fusion",
     f"{MTP_BWD}/block/moe/kf.moe_experts/mul", 100.0),
    ("sort.1", "sort", f"{FWD}/Block_1/moe/kf.moe_route/sort", 40.0),
    ("fusion.3", "loop fusion",
     f"{BWD}/Block_1/moe/kf.moe_route/gather", 60.0),
    ("fusion.4", "convolution fusion",
     f"{FWD}/kf.mtp/mtp/eh_proj/dot_general", 30.0),
    ("custom-call.6", "custom-call",
     "jit(step)/jvp(kf.fused_ce)/pallas_call", 120.0),
    ("fusion.5", "loop fusion", "jit(step)/kf.opt_update/add", 350.0),
]


@pytest.fixture(scope="module")
def ctx():
    def load(kind, name):
        with open(os.path.join(ROOT, "benchmark", kind,
                               f"{name}.json")) as f:
            return json.load(f)

    return {"config": load("configs", "glm-4.7-flash"),
            "traffic": load("traffic", "train-b1-t8192"), "chips": 1,
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def trace_of(ops):
    dev = tr.DeviceTrace("/device:TPU:0", steps=[(0.0, 2000.0)])
    at = 0.0
    for name, category, tf_op, dur in ops:
        dev.ops.append(tr.Op(name, at, dur, category, tf_op))
        at += dur
    return tr.Trace([dev])


@pytest.mark.parametrize("metric, expected_ms", [
    ("mla_ms", 1.100),          # three kernels + q_b, the MTP block's too
    ("moe_experts_ms", 0.300),  # scope-less grouped, shared, the MTP's
    ("moe_route_ms", 0.100),    # the sort and the backward gather
    ("mtp_ms", 0.330),          # its kernel, its SwiGLU, eh_proj
    ("pallas_ms", 1.120),       # flash and the CE kernel, not ragged-dot
    ("fused_ce_ms", 0.120),
    ("opt_update_ms", 0.350),
])
def test_time_metrics_select_their_scopes(ctx, metric, expected_ms):
    got = read_metrics([metric], trace_of(OPS), ctx, ROOT)
    assert got[metric] == pytest.approx(expected_ms, rel=1e-12)


def test_rooflines_divide_the_counted_work_by_their_ops_time(ctx):
    got = read_metrics(["mla_flash_roofline", "moe_expert_roofline"],
                       trace_of(OPS), ctx, ROOT)
    args = (ctx["config"], ctx["traffic"], 1)
    flash = flops_glm.mla_flash_train_step(*args)["flops"] / 197e12
    moe = flops_glm.moe_expert_train_step(*args)["flops"] / 197e12
    # the kernels directly under MLAttention_<n>: 1000 us, not q_b
    assert got["mla_flash_roofline"] == pytest.approx(
        100 * flash / 1000e-6, rel=1e-12)
    assert got["moe_expert_roofline"] == pytest.approx(
        100 * moe / 300e-6, rel=1e-12)


def test_a_step_without_the_scopes_reports_none_of_them(ctx):
    # the parent's program (another model, no kf.mla .. kf.mtp): the
    # readers find nothing, the metrics are left out, nothing raises
    bare = trace_of([
        ("custom-call.1", "custom-call",
         "jit(step)/jvp(GPTLM)/Block_0/CausalSelfAttention_0/pallas_call",
         300.0),
        ("fusion.5", "loop fusion", "jit(step)/kf.opt_update/add", 350.0)])
    assert read_metrics(
        ["mla_flash_roofline", "moe_expert_roofline", "moe_experts_ms",
         "moe_route_ms", "mla_ms", "mtp_ms"], bare, ctx, ROOT) == {}
