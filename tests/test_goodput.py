"""Goodput accounting unit suite: the phase taxonomy on synthetic
flight sources, each attribution rule in isolation, and the live
GoodputMeter families.

The synthetic sources mirror exactly what `export.read_flight_dir`
yields from real flight records — so every rule asserted here
(straggler overlap, lost-work duplicates, restore-anchored victim
attribution, the sum-to-wall invariant and its violation mode) is the
same code path the `--goodput` CLI gate runs on a replayed scenario.
"""

import pytest

from kungfu_tpu.trace.export import span_coverage
from kungfu_tpu.trace.goodput import (GoodputMeter, decompose,
                                      format_table)
from kungfu_tpu.trace.metrics import Registry

MS = 1000  # µs per ms


def X(name, ts_ms, dur_ms, rank, step=-1, i=None, **args):
    ev = {"name": name, "ph": "X", "cat": "t", "ts": int(ts_ms * MS),
          "dur": int(dur_ms * MS), "tid": "MainThread", "rank": rank,
          "version": 0, "step": step}
    if i is not None:
        ev["i"] = i
    if args:
        ev["args"] = args
    return ev


def I(name, ts_ms, rank, step=-1, **args):  # noqa: E743 - instant
    ev = {"name": name, "ph": "i", "cat": "t", "ts": int(ts_ms * MS),
          "tid": "MainThread", "rank": rank, "version": 0,
          "step": step}
    if args:
        ev["args"] = args
    return ev


def source(nonce, events, role="worker"):
    for n, e in enumerate(events):
        e.setdefault("i", n + 1)
    return {"meta": {"nonce": nonce, "role": role}, "events": events,
            "footer": {}}


def clean_rank(rank, steps=3, t0=0.0):
    """steps x (compute 100ms, wire 10ms, hook 5ms), 120ms pitch."""
    evs = []
    t = t0
    for s in range(steps):
        evs.append(X("step.compute", t, 100, rank, step=s))
        evs.append(X("step.grad_wire", t + 100, 10, rank, step=s))
        evs.append(X("step.hook", t + 110, 5, rank, step=s))
        t += 120
    return evs


def test_clean_run_decomposes_and_sums_to_wall():
    srcs = [source("a", clean_rank(0)), source("b", clean_rank(1))]
    d = decompose(srcs, device_batch=64)
    assert d["invariant"]["ok"] and d["invariant"]["error_pct"] == 0
    t = d["totals"]
    assert t["compute_ms"] == 600 and t["wire_ms"] == 60
    assert t["hook_ms"] == 30 and t["lost_ms"] == 0
    # wall per rank = 355 (last hook ends at 345+... envelope 0..355)
    assert t["wall_ms"] == 2 * 355
    assert t["other_ms"] == t["wall_ms"] - 690
    assert d["useful_step_ranks"] == 6
    assert d["useful_samples"] == 6 * 64
    assert abs(d["goodput_ratio"] - 600 / 710) < 1e-3
    # the table renders every phase plus the invariant verdict
    table = format_table(d)
    assert "goodput_ratio" in table and "OK" in table


def test_straggler_overlap_reclassifies_wire_wait():
    # rank 1 sleeps 80ms inside its hook (chaos.straggler span);
    # rank 0's wire span [100, 200] overlaps the window [120, 200]
    r0 = [X("step.compute", 0, 100, 0, step=0),
          X("step.grad_wire", 100, 100, 0, step=0)]
    r1 = [X("step.compute", 0, 100, 1, step=0),
          X("step.hook", 100, 110, 1, step=0),
          X("chaos.straggler", 120, 80, 1, step=0)]
    d = decompose([source("a", r0), source("b", r1)])
    rank0 = d["ranks"]["0"]
    rank1 = d["ranks"]["1"]
    # rank 0: 80ms of its 100ms wire was waiting on the straggler
    assert rank0["straggler"] == 80 and rank0["wire"] == 20
    # rank 1: the sleep is billed to straggler, NOT double-counted in
    # hook (110ms hook - 80ms nested sleep = 30ms control plane)
    assert rank1["straggler"] == 80 and rank1["hook"] == 30
    assert d["invariant"]["ok"]


def test_redone_step_attempts_are_lost_work():
    # rank 0 computes step 1 twice (wire failed, recovery, redo):
    # the FIRST attempt is lost, the second useful
    evs = [X("step.compute", 0, 100, 0, step=0),
           X("recovery.adopt", 110, 40, 0, step=0),
           X("recovery.restore", 150, 30, 0, step=0),
           X("step.compute", 200, 100, 0, step=0),
           X("step.grad_wire", 300, 10, 0, step=0)]
    d = decompose([source("a", evs)])
    r = d["ranks"]["0"]
    assert r["lost"] == 100 and r["compute"] == 100
    assert r["recovery"] == 70
    assert d["lost_steps_by_rank"] == {"0": 1}
    assert d["useful_step_ranks"] == 1


def test_victim_steps_past_restore_are_lost_from_flight_dump():
    # boot 1 (nonce a/b): two ranks compute steps 1..4, checkpoint at
    # step 2, die. boot 2 (nonce c): restores gen_step=2, recomputes
    # 3..4. Victims' steps 3,4 must be attributed lost — their spans
    # exist ONLY in the pre-kill flight dumps.
    def victim(rank):
        evs = []
        for s in range(4):  # tags 0..3 = steps 1..4
            evs.append(X("step.compute", s * 120, 100, rank, step=s))
        evs.append(I("chaos.crash_worker", 4 * 120, rank, step=4))
        return evs

    reboot = [I("ckpt.restored", 1000, 0, step=2, gen_step=2)]
    for s in (2, 3):  # tags 2,3 = steps 3,4 again
        reboot.append(X("step.compute", 1100 + (s - 2) * 120, 100, 0,
                        step=s))
    d = decompose([source("a", victim(0)), source("b", victim(1)),
                   source("c", reboot)])
    assert d["restored_step"] == 2
    # rank 0: steps 3,4 of boot 1 lost (recomputed after restore AND
    # past the generation); rank 1 (not present in boot 2): steps 3,4
    # lost via the restore rule alone — the flight dump attribution
    assert d["lost_steps_by_rank"] == {"0": 2, "1": 2}
    assert d["ranks"]["1"]["lost"] == 200
    # useful: rank0 steps 1,2 + redone 3,4; rank1 steps 1,2
    assert d["useful_step_ranks"] == 6


def test_resync_nested_in_recovery_restore_is_not_double_billed():
    """Survivor recovery wraps resync_params in recovery.restore, and
    resync_params emits its own resize.resync span (hooks.py) — the
    nested span must stay billed to `recovery`, not ALSO to `resize`
    (the one-sided invariant would silently absorb the double count
    into a shrunken `other` instead of failing)."""
    evs = clean_rank(0, steps=2)
    # recovery.restore [240, 440] wholly contains resize.resync
    # [250, 430]; a planned resize later [500, 560] stays "resize"
    evs.append(X("recovery.restore", 240, 200, 0))
    evs.append(X("resize.resync", 250, 180, 0))
    evs.append(X("resize.resync", 500, 60, 0))
    d = decompose([source("r0", evs)])
    assert d["totals"]["recovery_ms"] == 200.0
    assert d["totals"]["resize_ms"] == 60.0  # only the planned one
    assert d["invariant"]["ok"], d


def test_compile_is_its_own_phase_taken_out_of_the_span_round_it():
    """A jitted call compiles inside the span round its call site
    (`compile_cache.py` emits the three spans): the first
    `step.compute` of a process, `fuse`'s programs inside
    `step.grad_wire`, a resync nested in a restore. Each compile is
    billed ONCE, to `compile`, and leaves the phase it nests in."""
    evs = clean_rank(0, steps=2)   # compute [0,100], wire [100,110]...
    # the step's program inside the first step.compute: 20 + 10 + 50
    evs.append(X("compile.trace", 5, 20, 0, fun="step", cache="miss"))
    evs.append(X("compile.lower", 25, 10, 0, fun="step", cache="miss"))
    evs.append(X("compile.backend", 35, 50, 0, fun="step", cache="miss"))
    # a one-op program inside the wire span [100, 110]
    evs.append(X("compile.backend", 102, 6, 0, fun="concatenate",
                 cache="hit"))
    # a resync [250, 430] nested in a restore [240, 440] recompiles
    # for 100 ms: recovery loses it, resize stays 0, compile has it once
    evs.append(X("recovery.restore", 240, 200, 0))
    evs.append(X("resize.resync", 250, 180, 0))
    evs.append(X("compile.backend", 300, 100, 0, fun="step",
                 cache="miss"))
    # a program traced inside another's trace: the union, not the sum
    evs.append(X("compile.trace", 500, 40, 0, fun="outer", cache="off"))
    evs.append(X("compile.backend", 510, 20, 0, fun="eager", cache="off"))
    d = decompose([source("r0", evs)])
    t = d["totals"]
    assert t["compile_ms"] == 80 + 6 + 100 + 40
    assert t["compute_ms"] == 200 - 80
    assert t["wire_ms"] == 20 - 6
    assert t["recovery_ms"] == 200 - 100 and t["resize_ms"] == 0
    assert d["invariant"]["ok"] and d["invariant"]["error_pct"] == 0
    assert "compile" in format_table(d)
    # without the subtraction the same spans overrun the wall: the
    # phases above sum to what the spans cover, no more
    covered = 200 + 20 + 10 + 200 + 40
    assert sum(t[f"{p}_ms"] for p in (
        "compute", "wire", "hook", "recovery", "resize",
        "compile")) == covered


def test_double_counting_violates_the_invariant():
    # two overlapping resize spans: attributed exceeds the envelope —
    # the taxonomy must FAIL the run, not flatter it
    evs = [X("step.compute", 0, 10, 0, step=0),
           X("resize.resync", 10, 90, 0, step=0),
           X("resize.resync", 20, 90, 0, step=0)]
    d = decompose([source("a", evs)])
    assert not d["invariant"]["ok"]
    assert d["invariant"]["error_pct"] > 5
    assert "VIOLATED" in format_table(d)


def test_no_useful_steps_fails_the_gate():
    d = decompose([source("a", [X("step.hook", 0, 10, 0)])])
    assert not d["invariant"]["ok"]


def test_ckpt_snapshot_counts_async_writer_reported_aside():
    evs = [X("step.compute", 0, 100, 0, step=0),
           X("ckpt.snapshot", 100, 20, 0, step=0),
           # writer-thread wall overlapping the next step: excluded
           # from the sum (it would double-count the 1-core wall)
           X("ckpt.save", 100, 500, 0, step=0),
           X("step.compute", 120, 100, 0, step=1)]
    d = decompose([source("a", evs)])
    assert d["ranks"]["0"]["checkpoint"] == 20
    assert d["totals"]["checkpoint_async_ms"] == 500
    assert d["invariant"]["ok"]


def test_multi_boot_wall_excludes_relaunch_gap():
    # two boots of rank 0 with a 10s orchestration gap between them:
    # rank-active wall sums the envelopes, not the gap
    b1 = [X("step.compute", 0, 100, 0, step=0)]
    b2 = [X("step.compute", 20000, 100, 0, step=1)]
    d = decompose([source("a", b1), source("b", b2)])
    assert d["ranks"]["0"]["wall_ms"] == 200
    # ...but samples/sec uses the operator-real elapsed envelope
    assert d["elapsed_ms"] == 20100 if "elapsed_ms" in d else True


# -- the live meter -----------------------------------------------------------

def test_goodput_meter_maintains_registry_families():
    reg = Registry()
    m = GoodputMeter(registry=reg)
    m.observe_step(compute_ms=90, wire_ms=10)
    m.observe_step(compute_ms=90, wire_ms=10, hook_ms=5)
    m.observe("resize", 100)
    m.observe("compile", 0)  # a step that compiled nothing
    m.observe("straggler", 0)  # no-op: zero never creates a cell
    assert reg.read("kf_lost_ms_total", phase="compile") == 0
    assert reg.read("kf_useful_ms_total") == 180
    assert reg.read("kf_lost_ms_total", phase="wire") == 20
    assert reg.read("kf_lost_ms_total", phase="hook") == 5
    assert reg.read("kf_lost_ms_total", phase="resize") == 100
    assert reg.read("kf_lost_ms_total", phase="straggler") == 0
    assert abs(reg.read("kf_goodput_ratio") - 180 / 305) < 1e-6
    assert abs(m.ratio - 180 / 305) < 1e-6
    # the families render on /metrics
    text = "\n".join(reg.render())
    assert "kf_goodput_ratio" in text
    assert 'kf_lost_ms_total{phase="wire"}' in text


def test_registry_read_missing_family_is_zero():
    reg = Registry()
    assert reg.read("kf_nope") == 0.0
    reg.observe("kf_hist_ms", 7.0)
    assert reg.read("kf_hist_ms") == 7.0  # histogram -> running sum


# -- the --summary coverage satellite -----------------------------------------

def test_span_coverage_per_rank_clips_nesting():
    events = [X("step.compute", 0, 50, 0),
              X("step.hook", 50, 50, 0),
              # nested span must not push coverage past 100%
              X("inner", 60, 10, 0),
              X("step.compute", 0, 25, 1)]
    cov = span_coverage(events)
    assert cov["run_ms"] == 100
    assert cov["per_rank"]["0"]["pct_of_run"] == 100.0
    assert cov["per_rank"]["1"]["pct_of_run"] == 25.0


def test_summary_includes_coverage():
    from kungfu_tpu.trace.export import summarize

    out = summarize([X("step.compute", 0, 50, 0)])
    assert out["coverage"]["per_rank"]["0"]["span_ms"] == 50.0
