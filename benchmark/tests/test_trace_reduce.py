"""The reducer and the readers on a small recorded trace.

`fixtures/gpt2-small.train-b8.trace.json.gz` is cut from a real trace of
this cell on a TPU v5 lite (PR 22's chip run, `fixtures/cut_trace.py`):
four steps, of each the 150 longest operations. The expected values
were worked out over the same file by plain loops, independent of
`trace_reduce`.
"""

import json
import os

import pytest

from benchmark import trace_reduce as tr
from benchmark.runners.train import read_metrics

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

FIXTURE = os.path.join(ROOT, "benchmark", "fixtures",
                       "gpt2-small.train-b8.trace.json.gz")


@pytest.fixture(scope="module")
def trace():
    return tr.reduce(tr.load(FIXTURE))


@pytest.fixture(scope="module")
def ctx():
    def load(kind, name):
        with open(os.path.join(ROOT, "benchmark", kind,
                               f"{name}.json")) as f:
            return json.load(f)

    return {"config": load("configs", "gpt2-small"),
            "traffic": load("traffic", "train-b8"), "chips": 1,
            "peak": load("", "peaks")["TPU v5 lite"]}


def test_whole_steps_only(trace):
    (dev,) = trace.devices
    assert dev.name == "/device:TPU:0"
    assert len(dev.steps) == 2          # four traced, first and last dropped
    assert len(dev.ops) == 300
    assert set(trace.spans) == {"kfb.dispatch", "kfb.fetch"}


@pytest.mark.parametrize("metric, expected", [
    ("device_step_ms", 70.074660625),
    ("fwd_bwd_ms", 54.446208399),
    ("optimizer_ms", 3.018111836),
    ("pallas_ms", 28.282610117),
    ("device_idle", 17.995578255),
    ("host_dispatch_ms", 4.4837045),
    # 6.5357 TFLOP a step / 70.0747 ms / 197 TFLOP/s
    ("mfu", 100 * 8192 * 797_815_296 / 70.074660625e-3 / 197e12),
    # 464.31 GFLOP / 197 TFLOP/s = 2.3569 ms least, over 21.6697 ms
    ("flash_roofline", 100 * 464_309_452_800 / 197e12 / 21.669725586e-3),
])
def test_metric_on_fixture(trace, ctx, metric, expected):
    got = read_metrics([metric], trace, ctx, ROOT)
    assert got[metric] == pytest.approx(expected, rel=1e-9)


def test_reader_with_nothing_to_read_is_left_out(trace, ctx):
    # one chip: no all-reduce ran
    assert read_metrics(["allreduce_exposed_ms"], trace, ctx, ROOT) == {}


def test_breakdown_and_device_seconds(trace):
    b = tr.breakdown(trace)
    assert len(b["device_ops"]) == 10
    name, seconds = b["device_ops"][0]
    assert name == ("custom-call|jit(step)/transpose(jvp(GPTLM))/Block_N/"
                    "CausalSelfAttention_N/pallas_call")
    assert seconds == pytest.approx(14.514e-3, rel=1e-3)
    assert {n for n, _ in b["idle_gaps"]} <= {
        "kfb.dispatch", "kfb.fetch", "outside-spans"}
    busy, window = tr.device_seconds(trace)
    assert window == pytest.approx(2 * 70.074660625e-3, rel=1e-9)
    assert busy / window == pytest.approx(1 - 0.17995578255, rel=1e-9)


def test_too_few_steps_is_an_error():
    raw = tr.load(FIXTURE)
    steps = [e for e in raw["traceEvents"] if e.get("ph") == "X"
             and e["name"] in ("10", "11")]
    assert len(steps) == 2
    raw = {"traceEvents": [e for e in raw["traceEvents"]
                           if e not in steps]}
    with pytest.raises(ValueError, match="whole step"):
        tr.reduce(raw)
