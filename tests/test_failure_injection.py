"""Failure injection: detection, fencing, and survivor-driven recovery.

VERDICT r1 Next #9 + the chaos-schedule recovery loop. Detection:
a worker dies abruptly mid-epoch; the survivors' blocked receives must
fail fast with KF_ERR_CONN (transport fail_peer on collective-conn EOF)
instead of blocking out their full timeout (reference analog: runner
fail-fast, watch.go:136-149, plus connection.go:81-87 conn-level
errors). Fencing: a peer evicted by an epoch switch keeps sending; the
token fence rejects it with KF_ERR_EPOCH, observable from Python.

Recovery (the tentpole): a chaos-scheduled SIGKILL mid-training must
end in the SURVIVORS shrinking membership through the config server,
restoring state over the live resync path, and finishing training with
loss continuity — no operator action (`-recover`,
`elastic/harness.run_survivor_recovery`). Plus: a config server that
chaos-crashes and restarts mid-training must be bridged by the shared
retry policy, and a netns partition that HEALS within the stall
deadline must not kill anyone (chaos/slow marker).
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from kungfu_tpu.ffi import KF_ERR_EPOCH, KfError, NativePeer

from test_control_plane import alloc_ports

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "workers",
                      "fake_mid_collective_crash.py")
# worker j listens on the range's first port + j, so files that run
# side by side under xdist each need a first port of their own: the
# harness's default 27100 is also `__graft_entry__.dryrun_multichip`'s
# (tests/test_models.py), and the two collided
PORT_RANGE = "27700-27999"


def test_mid_collective_crash_fails_fast():
    ports = alloc_ports(3)
    spec = ",".join(f"127.0.0.1:{p}" for p in ports)
    env = dict(os.environ)
    env["KF_REPO"] = REPO
    env["KF_LOG_LEVEL"] = "error"
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(r), f"127.0.0.1:{ports[r]}", spec],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for r in range(3)
    ]
    t0 = time.perf_counter()
    outs = {}
    for r, p in enumerate(procs):
        out, _ = p.communicate(timeout=60)
        outs[r] = (p.returncode, out)
    wall = time.perf_counter() - t0
    assert outs[2][0] == 17, outs  # the injected crash
    for r in (0, 1):
        rc, out = outs[r]
        assert rc == 0, (r, rc, out, outs)
        assert "failed fast=True" in out, (r, out)
    # the whole run must beat the 30s collective timeout by a wide margin
    assert wall < 20, (wall, outs)


def test_stale_epoch_sender_rejected():
    ports = alloc_ports(2)
    spec = ",".join(f"127.0.0.1:{p}" for p in ports)
    peers = [NativePeer(f"127.0.0.1:{p}", spec, version=0, strategy="RING",
                        timeout_ms=20000) for p in ports]
    for p in peers:
        p.start()
    try:
        # warm epoch 0: both in, conns established
        results = [None, None]

        def warm(i):
            results[i] = peers[i].all_reduce(np.ones(4, np.float32),
                                             name="warm")

        ts = [threading.Thread(target=warm, args=(i,)) for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert results[0][0] == 2.0

        # peer 0 moves to epoch 1 with peer 1 evicted
        peers[0].update(f"127.0.0.1:{ports[0]}", version=1)
        assert peers[0].version == 1

        # the evicted peer keeps using its stale epoch: the token fence
        # must reject it (KF_ERR_EPOCH), not hang or silently deliver
        t0 = time.perf_counter()
        with pytest.raises(KfError) as ei:
            peers[1].all_reduce(np.ones(4, np.float32), name="stale")
        assert ei.value.code == KF_ERR_EPOCH, str(ei.value)
        assert time.perf_counter() - t0 < 15

        # the survivor's new epoch still works (single-peer degenerate)
        out = peers[0].all_reduce(np.ones(4, np.float32), name="post")
        assert out[0] == 1.0
    finally:
        for p in peers:
            p.close()


@pytest.mark.chaos
def test_survivor_recovery_after_chaos_worker_kill(tmp_path):
    """THE acceptance scenario: a worker SIGKILLed mid-training via a
    chaos schedule => surviving workers shrink membership, restore
    state, continue training with loss continuity asserted, and the
    schedule even re-grows the cluster back to target size through the
    normal elastic path — all with zero operator action. Every phase of
    the recovery pipeline is asserted marker-by-marker
    (harness.RECOVERY_MARKERS) — and, since round 11, span-by-span:
    the run flight-records under KF_TRACE and the kftrace structured
    MTTR decomposition must AGREE with the stdout-marker one
    (docs/observability.md)."""
    from kungfu_tpu.benchmarks.recovery import (check_agreement,
                                                decompose,
                                                decompose_events)
    from kungfu_tpu.elastic.harness import run_survivor_recovery

    trace_dir = str(tmp_path / "kftrace")
    logs = run_survivor_recovery(crash_rank=1, crash_step=5,
                                 total_steps=12, start_np=3,
                                 port_range=PORT_RANGE, timeout=300,
                                 extra_env={"KF_TRACE": "1",
                                            "KF_TRACE_DIR": trace_dir})
    # the recovery epoch ran at the shrunken size...
    assert "KF_RECOVERY_DONE rank=0 size=2" in logs, logs[-3000:]
    # ...and the schedule healed the cluster back to 3 afterwards: the
    # replacement joiner proved it adopted trained state, and the run
    # completed at full size
    assert "KF_JOINER_CONTINUITY" in logs, logs[-3000:]
    assert "size=3 step=12" in logs, logs[-3000:]
    # the two MTTR decompositions — stdout markers vs the kftrace
    # flight-recorder span tree (chaos victim's own crash record,
    # runner detect/propose, survivor adopt/restore/resume) — must
    # both be complete and reconcile
    d_markers = decompose(logs)
    d_events = decompose_events(trace_dir)
    assert d_markers is not None, logs[-3000:]
    assert d_events is not None, "structured MTTR timeline incomplete"
    disagreements = check_agreement(d_markers, d_events)
    assert not disagreements, disagreements


@pytest.mark.chaos
@pytest.mark.slow
def test_host_master_death_recovery_hier_shm_grad_pipeline(tmp_path):
    """ISSUE 14 acceptance: SIGKILL a HOST MASTER mid-step at np=4
    over two emulated hosts (one kfrun per host) with KF_HIER=1, the
    shm rings carrying the intra-host edges and the bucketed gradient
    pipeline on the wire. Survivors — including the dead master's
    colocated leaf, whose ring peer vanished — must detect via
    hello-EOF/socket error, ride the survivor path, re-derive the
    hierarchy over the survivors (the leaf is promoted to master), and
    finish the run at full size with loss continuity. The structured
    and marker MTTR decompositions must both complete and agree."""
    from kungfu_tpu.benchmarks.recovery import (check_agreement,
                                                decompose,
                                                decompose_events)
    from kungfu_tpu.elastic.harness import run_survivor_recovery

    trace_dir = str(tmp_path / "kftrace")
    logs = run_survivor_recovery(
        crash_rank=2,  # host 2's master (ranks 2,3 live on 127.0.0.2)
        crash_step=5, total_steps=12, start_np=4,
        hosts="127.0.0.1:2,127.0.0.2:2",
        port_range=PORT_RANGE, timeout=300,
        extra_env={"KF_HIER": "1", "KF_GRAD_BUCKET_MB": "0.25",
                   "KF_TRACE": "1", "KF_TRACE_DIR": trace_dir})
    assert "KF_RECOVERY_DONE rank=0 size=3" in logs, logs[-3000:]
    assert "size=4 step=12" in logs, logs[-3000:]
    assert "KF_JOINER_CONTINUITY" in logs, logs[-3000:]
    d_markers = decompose(logs)
    d_events = decompose_events(trace_dir)
    assert d_markers is not None, logs[-3000:]
    assert d_events is not None, "structured MTTR timeline incomplete"
    assert not check_agreement(d_markers, d_events)


@pytest.mark.chaos
@pytest.mark.slow
def test_whole_host_death_recovery_hier_shm(tmp_path):
    """ISSUE 14 acceptance: the crash_host chaos fault SIGKILLs EVERY
    rank on one emulated host (master + leaf + their rings) at a step
    boundary. The dead host's runner reaps the burst as ONE shrunken
    proposal and LINGERS; cross-host survivors recover at half size,
    and the schedule re-grows back onto the reclaimed host."""
    from kungfu_tpu.elastic.harness import run_survivor_recovery

    logs = run_survivor_recovery(
        crash_host=1, crash_step=5, total_steps=12, start_np=4,
        hosts="127.0.0.1:2,127.0.0.2:2",
        port_range=PORT_RANGE, timeout=300,
        extra_env={"KF_HIER": "1"})
    # both victims fired their own flight-anchored chaos markers
    assert logs.count("type=crash_host") >= 2, logs[-3000:]
    # ONE batched proposal took the cluster straight to the survivors
    assert "KF_RECOVERY_DONE rank=0 size=2" in logs, logs[-3000:]
    # the emptied host's runner lingered and respawned the joiners
    assert "lingering" in logs, logs[-3000:]
    assert "KF_JOINER_CONTINUITY" in logs, logs[-3000:]
    assert "size=4 step=12" in logs, logs[-3000:]


@pytest.mark.chaos
def test_whole_cluster_kill_restores_from_sharded_checkpoint(tmp_path):
    """The durable rung: the ONE fault class survivor recovery cannot
    cover. A chaos schedule SIGKILLs EVERY worker at the same step
    (whole-cluster death, rank-unpinned crash fault); async sharded
    checkpoint generations were landing under training; a relaunch at
    a DIFFERENT np restores the latest complete generation (re-sharded
    2-way from a 4-way save), proves loss continuity vs fresh init,
    and finishes the run."""
    from kungfu_tpu.elastic.harness import run_checkpoint_restore

    logs = run_checkpoint_restore(
        str(tmp_path / "ckpt"), save_np=4, restore_np=2, kill_step=9,
        save_every=2, port_range=PORT_RANGE, timeout=300)
    # every restore-cluster rank ran the proof and resumed mid-run
    assert "KF_RESTORE_CONTINUITY rank=0" in logs, logs[-3000:]
    assert "KF_RESTORE_CONTINUITY rank=1" in logs, logs[-3000:]
    # and the restored run kept checkpointing at its own np
    assert "KF_CKPT_SAVED" in logs, logs[-3000:]


@pytest.mark.chaos
def test_config_server_restart_mid_training(tmp_path):
    """The config server chaos-crashes mid-run and restarts on the same
    port: workers must ride the outage (resize polls tolerate the dead
    server; proposals go through the shared retry policy) and the
    scheduled grow must still complete after the restart."""
    from kungfu_tpu import chaos
    from kungfu_tpu.elastic import ConfigServer
    from kungfu_tpu.elastic.harness import (CONTINUITY_MARKERS,
                                            _run_continuity_cluster)

    server = ConfigServer(port=0).start()
    died = threading.Event()
    try:
        # the schedule lives in THIS process (the server is in-process,
        # injected into the shared harness); the cluster's own env
        # stays chaos-free
        chaos.load({"faults": [
            {"type": "die_config_server", "after_requests": 4}]})

        def _resurrect():
            deadline = time.time() + 60
            while time.time() < deadline:
                if server._httpd is None:
                    died.set()
                    time.sleep(0.5)  # a real restart is not instant
                    chaos.load(None)
                    server.restart()
                    return
                time.sleep(0.1)

        t = threading.Thread(target=_resurrect, daemon=True)
        t.start()
        logs = _run_continuity_cluster(
            schedule="8:2,20:3", total_steps=16, start_np=2, slots=4,
            port_range=PORT_RANGE, timeout=300, logdir=str(tmp_path),
            markers=CONTINUITY_MARKERS,
            extra_env={"KF_CHAOS": ""},  # cluster stays chaos-free
            server=server)
        t.join(timeout=60)
        assert died.is_set(), "the chaos fault never killed the server"
        # the grow proposed AFTER the outage window completed: the
        # restarted server carried the cluster through
        assert "size=3 step=16" in logs, logs[-3000:]
    finally:
        chaos.load(None)
        server.stop()


STEPPER_FIXED = """
import os, time
import numpy as np
import kungfu_tpu
p = kungfu_tpu.init()
steps = int(os.environ.get("TEST_TOTAL_STEPS", "80"))
for step in range(steps):
    out = p.all_reduce(np.ones(64, np.float32), name=f"s{step}")
    if step == 0:
        print(f"rank {p.rank}/{p.size} first allreduce ok", flush=True)
    time.sleep(0.1)
print(f"rank {p.rank} completed {steps} steps", flush=True)
"""


@pytest.mark.chaos
@pytest.mark.slow
def test_network_partition_heals_training_continues(tmp_path):
    """A partition that HEALS inside the failure-detection deadline is
    NOT a failure: both netns-backed hosts stay alive, the veth link
    drops for ~2.5s mid-run and comes back, TCP retransmits bridge the
    gap, and every worker completes every step with exit 0 — the
    complement of test_multirunner's partition-kills test, proving the
    detector doesn't fire early (chaos.FakeNet is the fault fabric)."""
    import signal
    import textwrap

    from kungfu_tpu import chaos as kf_chaos

    if not kf_chaos.netns_capable():
        pytest.skip("needs root + CAP_NET_ADMIN for netns/veth")

    REPO_ = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tag = f"kh{os.getpid() % 10000}"
    net = kf_chaos.FakeNet(tag, subnet="10.77.41")
    worker_py = tmp_path / "stepper.py"
    worker_py.write_text(textwrap.dedent(STEPPER_FIXED))
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ + os.pathsep + env.get("PYTHONPATH", "")
    env["KF_LOG_LEVEL"] = "warn"
    env["KF_TIMEOUT_MS"] = "60000"  # the heal beats this deadline
    env["TEST_TOTAL_STEPS"] = "80"
    procs = []
    try:
        a_host = net.add_host("a")
        b_host = net.add_host("b")

        def spawn(host, logdir, outfile):
            cmd = net.exec_prefix(host.name) + [
                sys.executable, "-m", "kungfu_tpu.run", "-np", "4",
                "-H", f"{a_host.ip}:2,{b_host.ip}:2", "-self", host.ip,
                "-port-range", "30100-30999", "-logdir", str(logdir),
                "-q", "--", sys.executable, str(worker_py)]
            out = open(outfile, "w")
            return subprocess.Popen(cmd, env=env, cwd=REPO_, stdout=out,
                                    stderr=subprocess.STDOUT, text=True,
                                    start_new_session=True), out

        a, fa = spawn(a_host, tmp_path / "a", tmp_path / "a.out")
        b, fb = spawn(b_host, tmp_path / "b", tmp_path / "b.out")
        procs = [(a, fa), (b, fb)]

        # wait for warm-up so the partition hits mid-run, not boot
        deadline = time.time() + 90
        logs_a = ""
        while time.time() < deadline:
            logs_a = "".join(
                open(tmp_path / "a" / f).read()
                for f in os.listdir(tmp_path / "a")
            ) if (tmp_path / "a").exists() else ""
            if logs_a.count("first allreduce ok") >= 2:
                break
            if a.poll() is not None or b.poll() is not None:
                break
            time.sleep(0.25)
        assert a.poll() is None and b.poll() is None, (
            open(tmp_path / "a.out").read(),
            open(tmp_path / "b.out").read())
        assert logs_a.count("first allreduce ok") >= 2, logs_a

        net.partition("a")
        time.sleep(2.5)  # well under KF_TIMEOUT_MS
        net.heal("a")

        ra = a.wait(timeout=120)
        rb = b.wait(timeout=120)
        logs = ""
        for side in ("a", "b"):
            for f in sorted(os.listdir(tmp_path / side)):
                logs += open(tmp_path / side / f).read()
        console = (open(tmp_path / "a.out").read()
                   + open(tmp_path / "b.out").read())
        assert ra == 0 and rb == 0, (ra, rb, console, logs[-3000:])
        # every worker finished every step — no failure was declared
        assert logs.count("completed 80 steps") == 4, logs[-3000:]
    finally:
        for p, f in procs:
            if p.poll() is None:
                try:
                    os.killpg(os.getpgid(p.pid), signal.SIGKILL)
                except Exception:
                    p.kill()
                p.wait(timeout=10)
            f.close()
        net.cleanup()
