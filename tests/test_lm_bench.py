"""The LM benchmark's entry point on more than one device.

`python -m kungfu_tpu.benchmarks.lm` at its CPU smoke size on the eight
virtual devices: the tensor-parallel and the expert-parallel rows take
their steps through the jitted callable, which re-specialises when a
step hands its state back laid out otherwise than it came in (the MoE
step on a model axis does). A fixed ahead-of-time executable in the
loop refused that at the second step, and nothing ran `lm.py` on more
than one device to see it.
"""

import json
import math
import sys

import pytest

from kungfu_tpu import compile_cache
from kungfu_tpu.benchmarks import lm


@pytest.mark.parametrize("flags,expect", [
    (["--tp", "2"], {"tp": 2, "fused_ce_sharding": "vocab/2"}),
    (["--tp", "2", "--experts", "4"],
     {"tp": 2, "num_experts": 4, "fused_ce_sharding": "vocab/2"}),
    ([], {"tp": 1}),
], ids=["tp2", "tp2-moe4", "dp"])
def test_lm_main_on_eight_devices(flags, expect, monkeypatch, tmp_path,
                                  capsys):
    # placed from outside, `enable()` sets no cache directory in this
    # process: the rest of the worker's tests compile as they did
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["lm", "--iters", "2"] + flags)
    lm.main()
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["metric"] == "gpt_tokens_per_sec" and row["value"] > 0
    d = row["details"]
    assert (d["platform"], d["device_kind"], d["devices"]) == (
        "cpu", "cpu", 8)
    for key, want in expect.items():
        assert d[key] == want, (key, d)
    # one warm-up step and two timed ones on the same batch
    losses = d["losses"]
    assert len(losses) == 3 and all(math.isfinite(x) for x in losses)
    assert losses[-1] < losses[0], losses
    assert d["compile_s"] > 0 and d["pallas_kernels"] == 0
