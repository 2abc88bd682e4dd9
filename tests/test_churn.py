"""Containerized-style cluster churn on netns fake hosts (VERDICT r5
"What's missing" item 1 / Next #9).

The reference exercises membership churn with a docker-compose cluster
(reference: benchmarks/adaptation/gen-compose.py): hosts with isolated
network roots join and leave while training runs. Here the container
runtime is replaced by `kungfu_tpu.chaos.FakeNet`: each fake host is a
network namespace on a shared bridge with its own /etc/hosts view, so
runners discover each other through HOSTNAME entries in -H (the
orchestrator-DNS path of `run/discovery.py`), not raw IPs.

The churn itself is driven through the config server exactly like an
operator/autoscaler would: POST /addworker grows onto the emptiest
host (the spare fake host whose runner idles with -keep), POST
/removeworker evicts it again — while the original workers keep
training through both epoch switches.
"""

import os
import signal
import subprocess
import sys
import textwrap
import time
import urllib.request

import pytest

from kungfu_tpu import chaos
from kungfu_tpu.plan import free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# poll-only elastic stepper: membership changes arrive exclusively from
# the config server (external churn), never from a worker-side schedule
CHURN_WORKER = """
import os, time
import numpy as np
import kungfu_tpu
from kungfu_tpu.elastic import ElasticCallback

p = kungfu_tpu.init()
elastic = ElasticCallback(p)
steps = int(os.environ.get("TEST_TOTAL_STEPS", "60"))
if p.config.version > 0:
    elastic.sync_position()
    print(f"churn joiner rank={p.rank} epoch={p.version} "
          f"step={elastic.state.step}", flush=True)
while elastic.state.step < steps:
    out = p.all_reduce(np.ones(16, np.float32),
                       name=f"s:{p.version}:{elastic.state.step}")
    assert out[0] == p.size
    if elastic.state.step == 0:
        print(f"churn started rank={p.rank}/{p.size}", flush=True)
    time.sleep(0.1)
    if elastic.after_step():
        if not elastic.state.keep:
            print(f"churn evicted rank={p.rank} "
                  f"step={elastic.state.step}", flush=True)
            raise SystemExit(0)
        elastic.sync_position()
        print(f"churn epoch {p.version} size={p.size} "
              f"step={elastic.state.step}", flush=True)
print(f"churn done rank={p.rank} size={p.size}", flush=True)
"""


def _post(url: str, timeout=10) -> str:
    req = urllib.request.Request(url, data=b"", method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read().decode()


def _logs(root) -> str:
    logs = ""
    for side in sorted(os.listdir(root)):
        d = os.path.join(root, side)
        if not os.path.isdir(d):
            continue
        for f in sorted(os.listdir(d)):
            logs += f"--- {side}/{f} ---\n" + open(os.path.join(d, f)).read()
    return logs


@pytest.mark.chaos
@pytest.mark.slow
def test_netns_host_churn_through_hostname_discovery(tmp_path):
    if not chaos.netns_capable():
        pytest.skip("needs root + CAP_NET_ADMIN for netns/veth")

    from kungfu_tpu.elastic import ConfigServer

    tag = f"kc{os.getpid() % 10000}"
    net = chaos.FakeNet(tag, subnet="10.77.42")
    worker_py = tmp_path / "worker.py"
    worker_py.write_text(textwrap.dedent(CHURN_WORKER))
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["KF_LOG_LEVEL"] = "warn"
    env["JAX_PLATFORMS"] = "cpu"
    env["KF_TIMEOUT_MS"] = "90000"
    env["TEST_TOTAL_STEPS"] = "60"
    server = None
    procs = []
    try:
        hosts = {n: net.add_host(n) for n in ("kfa", "kfb", "kfc")}
        net.publish_etc_hosts()
        # the config server lives on the bridge address: reachable from
        # every namespace, owned by none of them (an external operator)
        server = ConfigServer(host=f"{net.subnet}.254", port=0).start()

        runner_port = str(free_port())  # one for the cluster's runners

        def spawn(name, keep=False):
            logdir = tmp_path / name
            out = open(tmp_path / f"{name}.out", "w")
            cmd = net.exec_prefix(name) + [
                sys.executable, "-m", "kungfu_tpu.run", "-np", "2",
                "-H", "kfa:1,kfb:1,kfc:1",  # HOSTNAMES, not IPs
                "-port-range", "30100-30999",
                "-runner-port", runner_port,
                "-w", "-config-server", server.get_url,
                "-logdir", str(logdir), "-q"]
            if keep:
                cmd += ["-keep"]
            cmd += ["--", sys.executable, str(worker_py)]
            p = subprocess.Popen(cmd, env=env, cwd=REPO, stdout=out,
                                 stderr=subprocess.STDOUT, text=True,
                                 start_new_session=True)
            procs.append((p, out))
            return p

        a = spawn("kfa")
        b = spawn("kfb")
        c = spawn("kfc", keep=True)  # spare host: idles at 0 workers

        def wait_for(needle, count, timeout_s, procs_alive=(a, b)):
            deadline = time.time() + timeout_s
            while time.time() < deadline:
                logs = _logs(tmp_path)
                if logs.count(needle) >= count:
                    return logs
                for p in procs_alive:
                    assert p.poll() is None, (
                        f"runner died waiting for {needle!r}",
                        _logs(tmp_path)[-3000:],
                        open(tmp_path / "kfa.out").read()[-2000:],
                        open(tmp_path / "kfb.out").read()[-2000:])
                time.sleep(0.25)
            raise AssertionError(
                f"timeout waiting for {count}x {needle!r}:\n"
                + _logs(tmp_path)[-3000:])

        # 2 workers on hosts a+b training through hostname discovery
        wait_for("churn started", 2, 120)

        # ADD: grow onto the emptiest host => the spare fake host kfc
        _post(server.get_url.replace("/get", "/addworker"))
        logs = wait_for("churn joiner", 1, 120)
        assert "churn epoch 1 size=3" in logs, logs[-3000:]

        # REMOVE: shrink back; the kfc worker is evicted cleanly
        _post(server.get_url.replace("/get", "/removeworker"))
        logs = wait_for("churn evicted", 1, 120)

        # the original workers ride BOTH churn epochs to completion
        ra = a.wait(timeout=180)
        rb = b.wait(timeout=180)
        logs = _logs(tmp_path)
        assert ra == 0 and rb == 0, (ra, rb, logs[-3000:])
        assert logs.count("churn done") >= 2, logs[-3000:]
        assert "churn epoch 2 size=2" in logs, logs[-3000:]
        # the spare runner is still alive (-keep) after its worker left
        assert c.poll() is None, "spare host runner died"
    finally:
        for p, f in procs:
            if p.poll() is None:
                try:
                    os.killpg(os.getpgid(p.pid), signal.SIGKILL)
                except Exception:
                    p.kill()
                p.wait(timeout=10)
            f.close()
        if server is not None:
            server.stop()
        net.cleanup()
