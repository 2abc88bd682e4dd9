"""The five metrics of `trinity-mini.train-b1-t8192` through their own
files' `args`: on a synthetic trace whose scope paths are the ones the
program's lowered step carries (`tests/test_afmoe.py` holds those on
the program's side), and on a cut of the builder's own chip trace
(`fixtures/trinity-mini.train-b1-t8192.trace.json.gz`). Each selects
its operations and leaves the others'; a step without the scopes (the
parent's) reports none of them and raises nothing."""

import json
import os

import pytest

from benchmark import flops_afmoe
from benchmark import trace_reduce as tr
from benchmark.runners.train import read_metrics

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURE = os.path.join(ROOT, "benchmark", "fixtures",
                       "trinity-mini.train-b1-t8192.trace.json.gz")
NEW = ["attn_local_ms", "attn_global_ms", "window_flash_roofline",
       "global_flash_roofline", "afmoe_expert_roofline"]

FWD = "jit(step)/jvp(AfmoeLM)"
BWD = "jit(step)/transpose(jvp(AfmoeLM))/checkpoint"
# (name, category, scope path, microseconds): one whole step of 3000 us
OPS = [
    ("custom-call.1", "custom-call",
     f"{FWD}/Block_0/kf.attn_local/LocalAttention_0/pallas_call", 100.0),
    ("custom-call.2", "custom-call",
     f"{BWD}/Block_0/kf.attn_local/LocalAttention_0/pallas_call", 150.0),
    ("custom-call.3", "custom-call",
     f"{BWD}/Block_0/kf.attn_local/LocalAttention_0/pallas_call", 250.0),
    ("custom-call.4", "custom-call",
     f"{FWD}/Block_3/kf.attn_global/GlobalAttention_0/pallas_call", 200.0),
    ("custom-call.5", "custom-call",
     f"{BWD}/Block_3/kf.attn_global/GlobalAttention_0/pallas_call", 400.0),
    ("fusion.1", "convolution fusion",
     f"{FWD}/Block_0/kf.attn_local/LocalAttention_0/q/dot_general", 100.0),
    ("fusion.2", "convolution fusion",
     f"{BWD}/rematted_computation/Block_3/kf.attn_global/"
     "GlobalAttention_0/gate/dot_general", 150.0),
    ("fusion.3", "loop fusion",
     f"{BWD}/Block_3/kf.attn_global/attn_out_norm/mul", 50.0),
    ("fusion.4", "loop fusion",
     f"{FWD}/Block_2/moe/kf.moe_route/gather", 120.0),
    ("fusion.5", "convolution fusion",
     f"{FWD}/Block_2/moe/kf.moe_experts/shared/up/dot_general", 80.0),
    ("ragged-dot-none.1", "custom-call", "ragged-dot-none:", 220.0),
    ("fusion.6", "convolution fusion",
     f"{FWD}/Block_0/mlp/gate/dot_general", 130.0),
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

    return {"config": load("configs", "trinity-mini"),
            "traffic": load("traffic", "train-b1-t8192"), "chips": 1,
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def trace_of(ops):
    dev = tr.DeviceTrace("/device:TPU:0", steps=[(0.0, 3000.0)])
    at = 0.0
    for name, category, tf_op, dur in ops:
        dev.ops.append(tr.Op(name, at, dur, category, tf_op))
        at += dur
    return tr.Trace([dev])


@pytest.mark.parametrize("metric, expected_ms", [
    ("attn_local_ms", 0.600),    # three kernels and q's matmul
    ("attn_global_ms", 0.800),   # two kernels, the recomputed gate, N2
    ("moe_route_ms", 0.120),
    ("moe_experts_ms", 0.300),   # the shared expert and the ragged dot
    ("pallas_ms", 1.220),        # both kinds of flash call and the CE's
    ("fused_ce_ms", 0.300),
    ("opt_update_ms", 0.350),
])
def test_time_metrics_select_their_scopes(ctx, metric, expected_ms):
    got = read_metrics([metric], trace_of(OPS), ctx, ROOT)
    assert got[metric] == pytest.approx(expected_ms, rel=1e-12)


@pytest.mark.parametrize("metric, work, kernels_us", [
    ("window_flash_roofline", "window_flash_train_step", 500.0),
    ("global_flash_roofline", "global_flash_train_step", 600.0),
    ("afmoe_expert_roofline", "moe_expert_train_step", 300.0),
])
def test_rooflines_divide_the_counted_work_by_their_own_kernels_time(
        ctx, metric, work, kernels_us):
    got = read_metrics([metric], trace_of(OPS), ctx, ROOT)
    need = getattr(flops_afmoe, work)(ctx["config"], ctx["traffic"], 1)
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert least == need["flops"] / 197e12   # compute-bound, all three
    # each kind's kernels directly under its own module, not the other
    # kind's and not the projections
    assert got[metric] == pytest.approx(
        100 * least / (kernels_us * 1e-6), rel=1e-12)


def test_a_step_without_the_scopes_reports_none_of_them(ctx):
    # the parent's programs (other models): the readers find nothing,
    # the metrics are left out, nothing raises
    bare = trace_of([
        ("custom-call.1", "custom-call",
         "jit(step)/jvp(GPTLM)/Block_0/CausalSelfAttention_0/pallas_call",
         300.0),
        ("custom-call.2", "custom-call",
         "jit(step)/jvp(OuroLM)/kf.loop_stack/stack/Block_0/"
         "RotaryAttention_0/pallas_call", 300.0),
        ("fusion.5", "loop fusion", "jit(step)/kf.opt_update/add", 350.0)])
    assert read_metrics(NEW, bare, ctx, ROOT) == {}


def test_the_older_cells_traces_report_none_of_them(ctx):
    for name in ("gpt2-small.train-b8", "ouro-2.6b.train-b1-t4096"):
        old = tr.reduce(tr.load(os.path.join(
            ROOT, "benchmark", "fixtures", f"{name}.trace.json.gz")))
        # the ouro cut has no expert layer either
        assert read_metrics(NEW, old, ctx, ROOT) == {}


# -- the same files on a cut of the builder's chip trace ----------------------
# (my chip run, PR 34, seed 3402000040: `fixtures/cut_trace.py`'s cut, two
# whole steps of the 150 longest operations each, plus, for those steps,
# every operation under kf.moe_experts and XLA's ragged-dot kernels, most
# of which are too short to be among the 150)


@pytest.fixture(scope="module")
def chip_trace():
    return tr.reduce(tr.load(FIXTURE))


def test_on_the_chips_trace_every_new_metric_finds_its_operations(
        ctx, chip_trace):
    (dev,) = chip_trace.devices
    assert len(dev.steps) == 2
    got = read_metrics(NEW + ["pallas_ms", "moe_experts_ms"], chip_trace,
                       ctx, ROOT)
    assert set(NEW) <= set(got)
    # 18 kernels a step under the six sliding layers (forward, dq, dkv)
    # and 4 under the two full ones (forward and ONE backward), none of
    # them a recomputed forward, each under its own kind's scope
    local = tr.select(dev, tf_op=r"LocalAttention_\d+/pallas_call")
    full = tr.select(dev, tf_op=r"GlobalAttention_\d+/pallas_call")
    assert (len(local), len(full)) == (2 * 18, 2 * 4)
    assert not [o for o in local + full
                if "rematted_computation" in o.tf_op]
    assert all("kf.attn_local" in o.tf_op for o in local)
    assert all("kf.attn_global" in o.tf_op for o in full)
    assert len([o for o in local if "transpose(" in o.tf_op]) == 2 * 12
    assert len([o for o in full if "transpose(" in o.tf_op]) == 2 * 2
    # the shares of the counted work: 21.98 ms over the sliding layers'
    # 61.1, 16.75 over the full layers' 28.6
    assert got["window_flash_roofline"] == pytest.approx(35.9, abs=0.1)
    assert got["global_flash_roofline"] == pytest.approx(58.6, abs=0.1)
    for name in ("window_flash_roofline", "global_flash_roofline",
                 "afmoe_expert_roofline"):
        assert 0 < got[name] < 100, name
    # the expert layers' own matmuls: 14.1 ms of counted work over the
    # 56.9 the scope and the scope-less ragged-dot kernels (90 a step,
    # 9.06 ms) take together
    ragged = tr.select(dev, tf_op=r"^ragged-dot-")
    assert len(ragged) == 2 * 90
    assert got["moe_experts_ms"] == pytest.approx(56.9, abs=0.1)
    assert got["afmoe_expert_roofline"] == pytest.approx(24.8, abs=0.1)
    # flash of both kinds and the CE kernels are all the Pallas time
    ce = tr.ms_per_step(dev, tr.select(dev, tf_op=r"kf\.fused_ce.*pallas"))
    assert got["pallas_ms"] == pytest.approx(
        tr.ms_per_step(dev, local + full) + ce, rel=1e-9)
    # a cut keeps the longest operations only: most of the projections'
    # small fusions are gone, the kernels are not
    assert got["attn_local_ms"] > tr.ms_per_step(dev, local)
    assert got["attn_global_ms"] > tr.ms_per_step(dev, full)
