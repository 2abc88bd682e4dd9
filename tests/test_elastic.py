"""Elastic end-to-end: config server + watch runner + live resizes.

The rebuild of the reference's run-elastic-test.sh (reference:
scripts/tests/run-elastic-test.sh + kungfu-fake-adaptive-trainer): a
config server holds the versioned cluster, kfrun -w supervises workers,
and the fake adaptive trainer walks a resize schedule 2 -> 4 -> 1 while
training position is agreed across epochs.
"""

import os
import subprocess
import sys

from kungfu_tpu.elastic import ConfigServer
from kungfu_tpu.plan import free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKERS = os.path.join(REPO, "tests", "workers")


def test_elastic_schedule_resize(tmp_path):
    server = ConfigServer(port=0).start()
    try:
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["KF_TIMEOUT_MS"] = "60000"
        env["KF_LOG_LEVEL"] = "warn"
        env["TEST_SCHEDULE"] = "2:2,2:4,4:1"
        env["TEST_TOTAL_STEPS"] = "8"
        cmd = [
            sys.executable, "-m", "kungfu_tpu.run",
            "-np", "2", "-H", "127.0.0.1:4",
            "-port-range", "29000-29999",
            "-runner-port", str(free_port()),
            "-w", "-config-server", server.get_url,
            "-logdir", str(tmp_path), "-q",
        ]
        cmd += ["--", sys.executable,
                os.path.join(WORKERS, "fake_adaptive_trainer.py")]
        r = subprocess.run(cmd, cwd=REPO, env=env, timeout=180,
                           capture_output=True, text=True)
        logs = ""
        for f in sorted(os.listdir(tmp_path)):
            logs += f"--- {f} ---\n" + open(os.path.join(tmp_path, f)).read()
        assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:], logs)
        # grew to 4: at least one joiner synced position from survivors
        assert "joined at epoch" in logs, logs
        # shrank to 1: evicted workers exited cleanly
        assert "evicted at step" in logs, logs
        # the survivor finished the full schedule at size 1
        assert "finished rank=0 size=1 step=8" in logs, logs
    finally:
        server.stop()


def test_elastic_resize_loss_continuity(tmp_path):
    """2 -> 4 growth during REAL training: joiners must adopt trained
    weights (not fresh inits) and survivors' loss must not jump — the
    state-broadcast path made load-bearing. Shares the harness with
    the driver's `__graft_entry__.dryrun_multichip` elastic phase."""
    from kungfu_tpu.elastic.harness import run_loss_continuity

    logs = run_loss_continuity(port_range="29000-29999",
                               logdir=str(tmp_path), timeout=300)
    # both joiners proved broadcast weights beat their fresh init
    assert logs.count("KF_JOINER_CONTINUITY") >= 2, logs
    # the cluster finished the schedule at size 4
    assert "size=4 step=12" in logs, logs
