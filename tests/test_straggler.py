"""Async scalability under a straggler (reference README.md:207-209).

One slow worker must not drag the barrier-free strategy down: under
pair averaging (AD-PSGD) the fast workers finish their steps without
waiting for the sleeper, while under SyncSGD every worker waits for it
every step. Asserted on each worker's own wall clock inside the
straggler launch, against the time the sleeps alone take: the host's
load (five other xdist workers) can only lengthen a wall clock, so it
cannot turn a barrier into none, and a rate or a ratio of two launches'
rates — which it can move either way — is asserted nowhere.
"""

from kungfu_tpu.benchmarks.straggler import measure

STEPS = 20
STRAGGLER_MS = 120
SLEPT_S = STEPS * STRAGGLER_MS / 1000.0  # 2.4 s: rank 0's sleeps alone


def test_pair_averaging_holds_throughput_under_straggler():
    # each kfrun cell is bounded by the launcher's own 420 s timeout
    res = measure(np_=4, straggler_ms=STRAGGLER_MS, steps=STEPS, batch=64,
                  strategies=("sync", "pair"),
                  port_range="29400-29899", timeout=420)
    sync = res["sync"]["straggler_wall_s"]
    pair = res["pair"]["straggler_wall_s"]
    fast = [pair[r] for r in (1, 2, 3)]
    # sync barriers on the straggler every step: nobody finishes before
    # the sleeper has slept all its sleeps
    assert all(w >= SLEPT_S for w in sync.values()), res
    assert pair[0] >= SLEPT_S, res
    # async gossip: same compute, no sleep, and nobody to wait for
    assert all(w < pair[0] for w in fast), res
    # ... and at least one fast worker waited on the sleeper in fewer
    # than half its steps. The margin is for load: the clean launch's
    # 20 steps take a worker 0.06-0.08 s, alone on the host or with
    # `pytest tests/test_control_plane.py -n 4` beside them, so SLEPT_S/2
    # leaves the compute fifteen times that
    assert min(fast) < SLEPT_S / 2, res
