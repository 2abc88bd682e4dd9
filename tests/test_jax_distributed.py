"""Multi-process JAX runtime bootstrap from the kfrun env.

Two real processes, each with 2 virtual CPU devices, join one global
runtime through `init_distributed` (KF_* env -> jax.distributed) and
run a psum over a 4-device global mesh — the exact shape of a 2-host
TPU pod bootstrap, minus the hardware.
"""

import os
import socket
import subprocess
import sys

import pytest

from kungfu_tpu import env as kf_env
from kungfu_tpu.parallel.bootstrap import (
    COORDINATOR_PORT_OFFSET,
    coordinator_address,
    init_distributed,
    shutdown_distributed,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "workers", "jax_dist_worker.py")


def free_port_pair_with_coordinator():
    """A base where base, base+1 AND base+COORDINATOR_PORT_OFFSET all
    bind — the three ports the 2-process bootstrap actually uses."""
    for _ in range(64):
        with socket.socket() as probe:
            probe.bind(("127.0.0.1", 0))
            base = probe.getsockname()[1]
        if base + 1 + COORDINATOR_PORT_OFFSET > 0xFFFF:
            continue
        try:
            socks = []
            for p in (base, base + 1, base + COORDINATOR_PORT_OFFSET):
                s = socket.socket()
                s.bind(("127.0.0.1", p))
                socks.append(s)
            for s in socks:
                s.close()
            return base
        except OSError:
            for s in socks:
                s.close()
    raise RuntimeError("no free port triple found")


def test_standalone_is_noop():
    environ = {k: v for k, v in os.environ.items()
               if not k.startswith("KF_")}
    cfg = kf_env.from_env(environ)
    assert init_distributed(cfg) == (0, 1)


def test_coordinator_port_overflow_raises():
    peers = "127.0.0.1:65000,127.0.0.1:65001"
    cfg = kf_env.from_env({"KF_SELF_SPEC": "127.0.0.1:65000",
                           "KF_INIT_PEERS": peers})
    with pytest.raises(ValueError, match="port-range"):
        coordinator_address(cfg)


def test_reinit_different_cluster_raises(monkeypatch):
    """An elastic joiner must get a clear error, not a coordinator
    deadlock, if the process re-initializes against a new peer list."""
    from kungfu_tpu.parallel import bootstrap

    monkeypatch.setattr(bootstrap, "_initialized",
                        ("127.0.0.1:33000", 2, 0))
    peers = "127.0.0.1:41000,127.0.0.1:41001,127.0.0.1:41002"
    cfg = kf_env.from_env({"KF_SELF_SPEC": "127.0.0.1:41000",
                           "KF_INIT_PEERS": peers})
    with pytest.raises(RuntimeError, match="shutdown_distributed"):
        init_distributed(cfg)
    # idempotent re-entry with the SAME cluster is fine
    monkeypatch.setattr(
        bootstrap, "_initialized",
        (coordinator_address(cfg), 3, 0))
    assert init_distributed(cfg) == (0, 3)
    # and shutdown on a never-initialized process is a no-op
    monkeypatch.setattr(bootstrap, "_initialized", None)
    shutdown_distributed()


def test_coordinator_address_is_rank0():
    peers = "127.0.0.1:31000,127.0.0.1:31001"
    cfg = kf_env.from_env({"KF_SELF_SPEC": "127.0.0.1:31001",
                           "KF_INIT_PEERS": peers})
    assert cfg.rank == 1
    assert coordinator_address(cfg) == \
        f"127.0.0.1:{31000 + COORDINATOR_PORT_OFFSET}"


def test_two_process_global_mesh(tmp_path):
    base = free_port_pair_with_coordinator()
    peers = f"127.0.0.1:{base},127.0.0.1:{base + 1}"
    procs = []
    try:
        for rank in range(2):
            env = dict(os.environ)
            env.pop("XLA_FLAGS", None)  # worker sets its own 2-dev flag
            env["PYTHONPATH"] = (REPO + os.pathsep
                                 + env.get("PYTHONPATH", ""))
            env["KF_SELF_SPEC"] = f"127.0.0.1:{base + rank}"
            env["KF_INIT_PEERS"] = peers
            procs.append(subprocess.Popen(
                [sys.executable, WORKER], env=env, cwd=REPO,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    finally:
        for p in procs:  # a hung partner must not outlive the test
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, (rank, out[-3000:])
        assert f"JAX_DIST_OK rank={rank} devices=4" in out, out[-2000:]
