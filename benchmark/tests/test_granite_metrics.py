"""The three metrics of `granite-4.0-h-micro.train-b1-t8192` through their
own files' `args`, on a synthetic trace whose scope paths are the ones
the program's lowered step carries (`tests/test_granite_hybrid.py` holds
those on the program's side). Each selects its operations and leaves
the others'; a step without the scopes (the parent's) reports none of
them and raises nothing."""

import json
import os

import pytest

from benchmark import flops_granite
from benchmark import trace_reduce as tr
from benchmark.runners.train import read_metrics

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NEW = ["ssm_ms", "ssd_ms", "ssd_roofline", "nope_flash_roofline"]

FWD = "jit(step)/jvp(GraniteHybridLM)"
BWD = ("jit(step)/transpose(jvp(GraniteHybridLM))/jvp(GraniteHybridLM)/"
       "checkpoint")
# (name, category, scope path, microseconds): one whole step of 3000 us
OPS = [
    ("fusion.1", "convolution fusion",
     f"{FWD}/Block_0/kf.ssm/mamba/in_proj/dot_general", 200.0),
    ("fusion.2", "convolution fusion",
     f"{FWD}/Block_0/kf.ssm/mamba/jvp(kf.ssd)/kf.ssd/bcin,bcjn->bcij/"
     "dot_general", 100.0),
    ("fusion.3", "loop fusion",
     f"{BWD}/Block_0/kf.ssm/mamba/transpose(jvp(kf.ssd))/kf.ssd/exp", 300.0),
    ("fusion.4", "loop fusion",
     f"{BWD}/rematted_computation/Block_0/kf.ssm/mamba/jvp(kf.ssd)/kf.ssd/"
     "cumsum", 50.0),
    ("fusion.5", "convolution fusion",
     f"{BWD}/Block_0/kf.ssm/mamba/out_proj/dot_general", 150.0),
    ("while.1", "while", "", 400.0),
    ("custom-call.1", "custom-call",
     f"{FWD}/Block_5/self_attn/pallas_call", 120.0),
    ("fusion.6", "convolution fusion",
     f"{FWD}/Block_0/shared_mlp/gate/dot_general", 330.0),
    ("custom-call.2", "custom-call",
     "jit(step)/jvp(kf.fused_ce)/pallas_call", 80.0),
    ("fusion.7", "loop fusion", "jit(step)/kf.opt_update/add", 250.0),
]


@pytest.fixture(scope="module")
def ctx():
    def load(kind, name):
        with open(os.path.join(ROOT, "benchmark", kind,
                               f"{name}.json")) as f:
            return json.load(f)

    return {"config": load("configs", "granite-4.0-h-micro"),
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
    ("ssm_ms", 0.800),       # the projections and the SSD's three
    ("ssd_ms", 0.450),       # the SSD alone, recomputed forward included
    ("pallas_ms", 0.200),    # flash and the CE's kernel
    ("fused_ce_ms", 0.080),
])
def test_time_metrics_select_their_scopes(ctx, metric, expected_ms):
    got = read_metrics([metric], trace_of(OPS), ctx, ROOT)
    assert got[metric] == pytest.approx(expected_ms, rel=1e-12)


def test_the_ssd_roofline_divides_its_counted_work_by_its_time(ctx):
    got = read_metrics(["ssd_roofline"], trace_of(OPS), ctx, ROOT)
    need = flops_granite.ssd_train_step(ctx["config"], ctx["traffic"], 1)
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert least == need["bytes"] / 819e9     # bound by the bytes
    assert got["ssd_roofline"] == pytest.approx(
        100 * least / 450e-6, rel=1e-12)


def test_the_flash_roofline_divides_its_counted_work_by_its_time(ctx):
    got = read_metrics(["nope_flash_roofline"], trace_of(OPS), ctx, ROOT)
    need = flops_granite.flash_train_step(ctx["config"], ctx["traffic"], 1)
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert least == need["flops"] / 197e12     # bound by the FLOPs
    # the attention layer's kernel alone: not the CE's
    assert got["nope_flash_roofline"] == pytest.approx(
        100 * least / 120e-6, rel=1e-12)


def test_a_step_without_the_scopes_reports_none_of_them(ctx):
    # the parent's programs (other models): the readers find nothing,
    # the metrics are left out, nothing raises
    bare = trace_of([
        ("custom-call.1", "custom-call",
         "jit(step)/jvp(GPTLM)/Block_0/CausalSelfAttention_0/pallas_call",
         300.0),
        ("fusion.5", "loop fusion", "jit(step)/kf.opt_update/add", 350.0)])
    assert read_metrics(NEW, bare, ctx, ROOT) == {}


def test_the_older_cells_traces_report_none_of_them(ctx):
    for name in ("gpt2-small.train-b8", "ouro-2.6b.train-b1-t4096",
                 "trinity-mini.train-b1-t8192"):
        old = tr.reduce(tr.load(os.path.join(
            ROOT, "benchmark", "fixtures", f"{name}.trace.json.gz")))
        assert read_metrics(NEW, old, ctx, ROOT) == {}


# -- the same files on a cut of a trace taken on the chip ---------------------
# (one v5e chip, seed 4100000009: `fixtures/cut_trace.py`'s cut, two
# whole steps of the 150 longest operations each, plus, for those steps,
# every operation under kf.ssd, most of which are too short to be among
# the 150: 5,410 a step, the bodies of the scan's loops among them)

FIXTURE = os.path.join(ROOT, "benchmark", "fixtures",
                       "granite-4.0-h-micro.train-b1-t8192.trace.json.gz")


@pytest.fixture(scope="module")
def chip_trace():
    return tr.reduce(tr.load(FIXTURE))


def test_on_the_chips_trace_every_new_metric_finds_its_operations(
        ctx, chip_trace):
    (dev,) = chip_trace.devices
    assert len(dev.steps) == 2
    got = read_metrics(NEW + ["pallas_ms"], chip_trace, ctx, ROOT)
    assert set(NEW) <= set(got)
    # the scan, forward, recomputed forward and backward of nine layers
    assert got["ssd_ms"] == pytest.approx(122.78, abs=0.05)
    assert got["ssd_roofline"] == pytest.approx(3.77, abs=0.01)
    assert 0 < got["ssd_roofline"] < 100
    ssd = tr.select(dev, tf_op=r"kf\.ssd")
    assert all("kf.ssm" in o.tf_op for o in ssd)
    assert {"/mamba/" in o.tf_op for o in ssd} == {True}
    # a cut keeps the longest operations only: the SSD's whole, and of
    # the rest of the sublayer its large matmuls
    assert got["ssm_ms"] > got["ssd_ms"]
    # the attention layer's flash calls: a forward and ONE fused
    # backward a step, neither recomputed
    attn = tr.select(dev, tf_op=r"self_attn/pallas_call")
    assert len(attn) == 2 * 2
    assert not [o for o in attn if "rematted_computation" in o.tf_op]
    assert len([o for o in attn if "transpose(" in o.tf_op]) == 2
    assert got["nope_flash_roofline"] == pytest.approx(
        100 * 4.1866e-3 / (sum(o.dur for o in attn) / 2 / 1e6), rel=1e-3)
    assert 0 < got["nope_flash_roofline"] < 100
