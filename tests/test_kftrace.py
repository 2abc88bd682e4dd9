"""kftrace: recorder, flight recorder, collection, export, metrics.

The observability layer's unit surface (docs/observability.md):

- ring-buffer semantics: bounded, drop-OLDEST on overflow with a
  counted `dropped_events`, never grows, never blocks;
- SPMD span semantics across an epoch switch: a span opened in
  version v closes correctly (and is attributed to v) after the
  context moved to the rebuilt world;
- flight dumps round-trip through the exporter, deduplicate against
  shipped copies, and produce Perfetto-valid Chrome trace JSON;
- the /trace collection path: shipper -> config server -> snapshot,
  bounded on both sides, drop-on-overload, never raising into the
  training thread even with a dead collector;
- the recovery decomposition from structured events;
- chaos faults emit their structured event AND the victim's flight
  dump BEFORE the destructive action (subprocess proof);
- the metrics registry renders consistent Prometheus text;
- the profiler bridge: while a `jax.profiler` session runs, a span is
  also in that session's trace as `kf.<name>`, on the host plane,
  inside its caller's interval, whether or not the ring records; and
  a process that only imports `kungfu_tpu.trace` never loads JAX.
"""

import glob
import gzip
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from kungfu_tpu import trace
from kungfu_tpu.trace.collect import TraceShipper, TraceStore
from kungfu_tpu.trace.export import (merge_sources, read_flight_dir,
                                     recovery_decomposition, summarize,
                                     to_chrome_trace,
                                     validate_chrome_trace)
from kungfu_tpu.trace.metrics import Registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_trace_state():
    trace._reset_for_tests()
    yield
    trace._reset_for_tests()


def _enable(tmp_path=None, capacity=64):
    return trace.configure(enabled_=True, capacity=capacity,
                           directory=str(tmp_path) if tmp_path else "")


# -- recorder -----------------------------------------------------------------

def test_disabled_recorder_is_noop():
    trace.configure(enabled_=False)
    assert trace.span("x") is trace.NOOP_SPAN
    trace.event("y")  # must not create a recorder
    assert trace._rec is None


def test_span_records_context_and_duration():
    rec = _enable()
    trace.set_context(rank=2, version=3, step=7)
    with trace.span("step.compute", cat="step", foo=1):
        time.sleep(0.002)
    (ev,) = rec.snapshot()
    assert ev["name"] == "step.compute" and ev["ph"] == "X"
    assert ev["rank"] == 2 and ev["version"] == 3 and ev["step"] == 7
    assert ev["dur"] >= 1500  # slept 2 ms
    assert ev["args"] == {"foo": 1}


def test_span_opened_in_old_epoch_closes_attributed_to_it():
    """The satellite semantics: a span straddling a resize/recovery
    belongs to the version that OPENED it — the epoch that did the
    work — and is recorded exactly once."""
    rec = _enable()
    trace.set_context(rank=0, version=1, step=5)
    sp = trace.span("step.grad_wire", cat="step")
    sp.__enter__()
    # mid-span the world is rebuilt: recovery adopts version 4, the
    # rank moves, the agreed step advances
    trace.set_context(rank=1, version=4, step=9)
    sp.__exit__(None, None, None)
    events = rec.snapshot()
    assert len(events) == 1
    ev = events[0]
    assert ev["version"] == 1 and ev["rank"] == 0 and ev["step"] == 5
    # while a NEW span picks up the rebuilt context
    with trace.span("step.compute"):
        pass
    ev2 = rec.snapshot()[-1]
    assert ev2["version"] == 4 and ev2["rank"] == 1 and ev2["step"] == 9


def test_ring_overflow_drops_oldest_and_counts():
    rec = _enable(capacity=16)
    # capacity floor is 16 (recorder.TraceRecorder)
    for i in range(50):
        trace.event("e", i=i)
    snap = rec.snapshot()
    assert len(snap) == 16  # never grows
    assert rec.dropped_events == 50 - 16
    # oldest dropped: the survivors are the LAST 16 emitted
    assert [e["args"]["i"] for e in snap] == list(range(34, 50))


def test_emit_is_safe_across_threads():
    rec = _enable(capacity=1024)

    def emit(k):
        for i in range(200):
            with trace.span(f"t{k}", cat="x"):
                pass

    ts = [threading.Thread(target=emit, args=(k,)) for k in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert rec.appended == 800
    assert len(rec.snapshot()) == 800
    # per-event ids are unique (the dedup key)
    ids = [e["i"] for e in rec.snapshot()]
    assert len(set(ids)) == 800


# -- flight recorder + export -------------------------------------------------

def test_flight_dump_roundtrip_and_dedup(tmp_path):
    rec = _enable(tmp_path)
    trace.set_context(rank=1, version=2, step=3)
    with trace.span("step.compute", cat="step"):
        pass
    trace.event("recovery.caught", cat="recovery")
    p1 = rec.dump(reason="first")
    p2 = rec.dump(reason="second")  # same ring again, new file
    assert p1 != p2 and os.path.exists(p1) and os.path.exists(p2)
    sources = read_flight_dir(str(tmp_path))
    # headers parsed; dumps carry reason + context
    metas = {s["meta"]["reason"] for s in sources}
    assert metas == {"first", "second"}
    events, info = merge_sources(sources)
    # the double dump deduplicates on (nonce, id): each event once
    names = sorted(e["name"] for e in events
                   if e["name"].startswith(("step.", "recovery.")))
    assert names == ["recovery.caught", "step.compute"]
    doc = to_chrome_trace(events, info)
    assert validate_chrome_trace(doc) == []


def test_same_process_recorders_never_share_a_nonce(tmp_path):
    """Two recorders born in the same process within one clock tick
    (worker + runner-role, or configure() swapping mid-process) must
    NOT collide on the (nonce, id) dedup key — a collision makes
    merge_sources silently drop the second recorder's events, which
    for the goodput plane means unattributed (or worse, vanished)
    wall. Regression: the pid+wall-ms nonce collided exactly here."""
    recs = [trace.TraceRecorder(directory=str(tmp_path))
            for _ in range(8)]
    assert len({r.nonce for r in recs}) == len(recs)
    for n, r in enumerate(recs):
        r.event(f"ev{n}", cat="step")
        r.dump()
    events, _ = merge_sources(read_flight_dir(str(tmp_path)))
    got = {e["name"] for e in events if e["name"].startswith("ev")}
    assert got == {f"ev{n}" for n in range(8)}


def test_chrome_trace_tracks_and_metadata(tmp_path):
    # worker process: nested spans on the rank-0 track
    rec = _enable(tmp_path)
    trace.set_context(rank=0, version=0, step=1)
    with trace.span("outer", cat="step"):
        with trace.span("inner", cat="step"):
            pass
    rec.dump()
    # runner process (fresh recorder, own nonce): detect event
    rec2 = trace.configure(enabled_=True, role="runner",
                           directory=str(tmp_path))
    rec2.event("recovery.detect", cat="recovery")
    rec2.dump()
    events, info = merge_sources(read_flight_dir(str(tmp_path)))
    doc = to_chrome_trace(events, info)
    assert validate_chrome_trace(doc) == []
    pids = {e["pid"] for e in doc["traceEvents"] if e["ph"] != "M"}
    assert 0 in pids and 1000 in pids  # rank-0 + runner tracks
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e["name"] == "process_name"}
    assert "rank 0" in names and "runner" in names


def test_validator_rejects_broken_nesting_and_schema():
    bad = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0, "dur": 100, "pid": 0,
         "tid": 0},
        # overlaps `a` without being contained: a broken recorder
        {"name": "b", "ph": "X", "ts": 50, "dur": 100, "pid": 0,
         "tid": 0},
    ]}
    problems = validate_chrome_trace(bad)
    assert any("without nesting" in p for p in problems)
    assert validate_chrome_trace({}) == ["traceEvents missing or empty"]
    missing = {"traceEvents": [{"ph": "X", "ts": 0, "dur": -1}]}
    assert validate_chrome_trace(missing)


def test_recovery_decomposition_from_events():
    ms = 1000  # µs per ms

    def ev(name, t_ms, ph="i", dur_ms=0):
        cat = name.split(".")[0]
        e = {"name": name, "ph": ph, "ts": t_ms * ms, "rank": 0,
             "i": t_ms, "cat": cat}
        if ph == "X":
            e["dur"] = dur_ms * ms
        return e

    events = [
        ev("chaos.crash_worker", 100),
        ev("recovery.detect", 350),
        ev("recovery.propose", 360),
        ev("recovery.adopt", 365, "X", 80),    # ends 445
        ev("recovery.adopt", 370, "X", 100),   # slowest: ends 470
        ev("recovery.restore", 470, "X", 6),   # ends 476
        ev("recovery.resume", 490),
    ]
    d = recovery_decomposition(events)
    assert d is not None
    assert d["detect_ms"] == pytest.approx(250)
    assert d["propose_ms"] == pytest.approx(10)
    assert d["consensus_ms"] == pytest.approx(110)
    assert d["restore_ms"] == pytest.approx(6)
    assert d["resume_ms"] == pytest.approx(14)
    assert d["mttr_ms"] == pytest.approx(390)
    # incomplete timeline -> None (benchmark falls back to markers)
    assert recovery_decomposition(events[:-1]) is None
    s = summarize(events)
    assert s["recovery"]["mttr_ms"] == pytest.approx(390)
    assert any(l["name"] == "chaos.crash_worker"
               for l in s["landmarks"])


# -- collection path ----------------------------------------------------------

def test_trace_store_bounds_and_snapshot():
    store = TraceStore(max_events=10)
    took = store.add_batch({"role": "worker", "rank": 0, "nonce": "a",
                            "events": [{"i": i, "ts": i}
                                       for i in range(8)]})
    assert took == 8
    took = store.add_batch({"role": "worker", "rank": 1, "nonce": "b",
                            "events": [{"i": i, "ts": i}
                                       for i in range(8)]})
    assert took == 2  # ceiling reached: overflow dropped, counted
    snap = store.snapshot()
    assert snap["total_events"] == 10 and snap["dropped"] == 6
    with pytest.raises(ValueError):
        store.add_batch({"events": "nope"})


def test_shipper_posts_to_config_server_and_export_fetches():
    from kungfu_tpu.elastic.config_server import ConfigServer
    from kungfu_tpu.trace.export import fetch_server

    server = ConfigServer(port=0).start()
    try:
        rec = _enable()
        trace.set_context(rank=0, version=0, step=1)
        ship = TraceShipper(
            f"http://127.0.0.1:{server.port}/trace", rec,
            period_s=10.0)  # manual flushes only
        ship.start()
        with trace.span("step.compute", cat="step"):
            pass
        trace.event("mark", cat="x")
        ship.stop(flush=True)  # drains the queue through one POST
        assert ship.posted_events == 2 and ship.post_failures == 0
        sources = fetch_server(f"http://127.0.0.1:{server.port}/get")
        events, _ = merge_sources(sources)
        assert sorted(e["name"] for e in events) == \
            ["mark", "step.compute"]
    finally:
        server.stop()


def test_shipper_never_raises_with_dead_collector():
    rec = _enable()
    # nothing listens here: every flush must drop, not raise/block
    ship = TraceShipper("http://127.0.0.1:9/trace", rec,
                        period_s=10.0, timeout_s=0.2)
    ship.start()
    for i in range(5):
        trace.event("e", i=i)
    t0 = time.perf_counter()
    ship.stop(flush=True)
    assert time.perf_counter() - t0 < 5.0  # bounded by the timeout
    assert ship.post_failures >= 1 and ship.posted_events == 0


def test_shipper_queue_is_bounded():
    rec = _enable(capacity=4096)
    ship = TraceShipper("http://127.0.0.1:9/trace", rec,
                        period_s=1000.0, queue_max=100)
    rec._ship = ship  # attach without starting the thread
    for i in range(500):
        trace.event("e", i=i)
    assert len(ship._q) == 100  # drop-on-overload, never grows
    assert ship.dropped == 400


# -- chaos integration --------------------------------------------------------

def test_chaos_fault_emits_event_and_flight_dump_before_death(tmp_path):
    """The chaos satellite: a crash_worker fault flight-dumps the ring
    (containing the just-emitted structured chaos event) BEFORE the
    destructive action, so even a process that dies mid-fault leaves
    its own record of the crash instant."""
    prog = textwrap.dedent("""
        from kungfu_tpu import chaos, trace
        trace.set_context(rank=1, version=0, step=2)
        trace.event("step.marker", cat="step")
        trace.set_context(step=3)
        chaos.on_step(rank=1, step=3)   # schedule fires: EXIT here
        raise SystemExit("fault did not fire")
    """)
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "KF_TRACE": "1",
        "KF_TRACE_DIR": str(tmp_path),
        "KF_CHAOS": json.dumps({"faults": [{
            "type": "crash_worker", "rank": 1, "step": 3,
            "signal": "EXIT", "code": 41}]}),
    })
    r = subprocess.run([sys.executable, "-c", prog], env=env,
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 41, (r.stdout, r.stderr)
    assert "KF_CHAOS_FIRE" in r.stdout
    events, _ = merge_sources(read_flight_dir(str(tmp_path)))
    names = [e["name"] for e in events]
    assert "chaos.crash_worker" in names, names
    assert "step.marker" in names  # the pre-fault ring rode along
    ev = next(e for e in events if e["name"] == "chaos.crash_worker")
    assert ev["args"]["signal"] == "EXIT" and ev["step"] == 3


# -- the profiler bridge --------------------------------------------------------

def _host_events(log_dir):
    """Complete events of the `/host:CPU` plane in the trace the
    profiler wrote under `log_dir`, as `benchmark/trace_reduce.py`
    reads them."""
    (path,) = glob.glob(os.path.join(
        str(log_dir), "plugins", "profile", "*", "*.trace.json.gz"))
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    (host,) = [e["pid"] for e in events if e.get("ph") == "M"
               and e["name"] == "process_name"
               and e["args"]["name"] == "/host:CPU"]
    return [e for e in events if e.get("ph") == "X" and e["pid"] == host]


@pytest.mark.parametrize("kf_trace", [False, True],
                         ids=["KF_TRACE-off", "KF_TRACE-on"])
def test_span_is_in_a_running_profiler_session(tmp_path, kf_trace):
    import jax

    rec = trace.configure(enabled_=kf_trace)
    trace.set_context(rank=0, version=3, step=7)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("outer"):
            with trace.span("step.hook", cat="step", foo=1) as sp:
                assert sp.set(bar=2) is sp
                time.sleep(0.002)
            # not bridged: the ring's instant events and retroactive
            # spans (the compile ledger's)
            trace.event("resize.adopted", cat="elastic")
            trace.complete("compile.backend", 0, 5, cat="compile")
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    (outer,) = [e for e in events if e["name"] == "outer"]
    (hook,) = [e for e in events if e["name"] == "kf.step.hook"]
    assert not [e for e in events if e["name"].startswith("kf.")
                and e is not hook]
    # the calling thread's line, inside the caller's interval: one clock
    assert hook["tid"] == outer["tid"]
    assert outer["ts"] <= hook["ts"]
    assert hook["ts"] + hook["dur"] <= outer["ts"] + outer["dur"]
    assert hook["dur"] >= 1500  # microseconds; slept 2 ms
    if kf_trace:
        assert hook["args"] == {"step": "7", "version": "3"}
        (ev,) = [e for e in rec.snapshot() if e["cat"] == "step"]
        assert ev["name"] == "step.hook" and ev["cat"] == "step"
        assert ev["step"] == 7 and ev["version"] == 3
        assert ev["args"] == {"foo": 1, "bar": 2}
        assert abs(ev["dur"] - hook["dur"]) < 1000
    else:
        assert "args" not in hook   # no context without the ring
        assert trace._rec is None   # and no recorder because of it
    # the session over, a site costs what it did before
    if not kf_trace:
        assert trace.span("step.hook") is trace.NOOP_SPAN


def test_importing_trace_and_opening_a_span_does_not_load_jax():
    code = textwrap.dedent("""
        import sys
        from kungfu_tpu import trace
        with trace.span("step.hook") as sp:
            sp.set(a=1)
        trace.configure(enabled_=True)
        with trace.span("step.hook") as sp:
            sp.set(a=1)
        assert len(trace.recorder().snapshot()) == 1
        loaded = sorted(m for m in sys.modules
                        if m == "jax" or m.startswith(("jax.", "jaxlib")))
        assert not loaded, loaded
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


# -- metrics registry ---------------------------------------------------------

def test_metrics_registry_families_render():
    reg = Registry()
    reg.inc("kf_wire_bytes_total", 1024, collective="grad")
    reg.inc("kf_wire_bytes_total", 512, collective="resync")
    reg.set("kf_ckpt_pending", 2)
    for v in (0.5, 3.0, 40.0, 9999.0):
        reg.observe("kf_step_latency_ms", v)
    lines = reg.render(extra_labels={"rank": "1"})
    text = "\n".join(lines)
    assert 'kf_wire_bytes_total{collective="grad",rank="1"} 1024' \
        in text
    assert 'kf_ckpt_pending{rank="1"} 2' in text
    # histogram: cumulative buckets, sum, count
    assert 'kf_step_latency_ms_bucket{le="1",rank="1"} 1' in text
    assert 'kf_step_latency_ms_bucket{le="5",rank="1"} 2' in text
    assert 'kf_step_latency_ms_bucket{le="+Inf",rank="1"} 4' in text
    assert 'kf_step_latency_ms_count{rank="1"} 4' in text


def test_metrics_registry_threadsafe_totals():
    reg = Registry()

    def work():
        for _ in range(500):
            reg.inc("c")
            reg.observe("h", 1.0)

    ts = [threading.Thread(target=work) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert reg.counter("c").value == 2000
    assert reg.histogram("h").count == 2000
