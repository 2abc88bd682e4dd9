"""Worker process creation: env injection, chip assignment, log capture.

TPU translation of the reference's job package (reference: srcs/go/job/
{job,proc,gpu_resource,cuda_visible_device}.go): the GPU slot bitmask pool
becomes a TPU chip pool driving TPU_VISIBLE_DEVICES (plus
JAX_PLATFORMS=cpu passthrough for host-simulation runs), and each worker's
stdout/stderr is captured to a log file and optionally tee'd to the
console with a rank prefix (reference: srcs/go/utils/iostream).
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from .. import chaos
from .. import compile_cache
from .. import env as kfenv
from ..plan import PeerID, PeerList, free_port


class ChipPool:
    """Bitmask allocator of local accelerator slots (reference GPUPool,
    gpu_resource.go:17-51)."""

    def __init__(self, slots: int):
        # grabbed by the reconcile loop and worker-exit callbacks at once
        self._free = list(range(slots))  # kf: guarded_by(_lock)
        self._lock = threading.Lock()

    def get(self) -> Optional[int]:
        with self._lock:
            return self._free.pop(0) if self._free else None

    def put(self, chip: int):
        with self._lock:
            self._free.append(chip)
            self._free.sort()


@dataclass
class Proc:
    """One supervised worker process."""

    peer: PeerID
    rank: int
    popen: subprocess.Popen
    chip: Optional[int]
    log_path: str
    pumps: List[threading.Thread] = field(default_factory=list)

    def wait(self) -> int:
        code = self.popen.wait()
        for t in self.pumps:
            t.join(timeout=2.0)
        return code

    def terminate(self):
        if self.popen.poll() is None:
            self.popen.terminate()

    def kill(self):
        if self.popen.poll() is None:
            self.popen.kill()


_COLORS = [31, 32, 33, 34, 35, 36, 91, 92, 93, 94, 95, 96]


def _pump(stream, log_file, prefix: str, color: int, quiet: bool):
    """Forward a worker stream to its log file (+ prefixed console)."""
    with log_file:
        for raw in iter(stream.readline, b""):
            log_file.write(raw)
            log_file.flush()
            if not quiet:
                line = raw.decode(errors="replace").rstrip("\n")
                sys.stderr.write(
                    f"\x1b[{color}m[{prefix}]\x1b[0m {line}\n")
        stream.close()


#: chip slot -> the mesh-controller port its newest worker was given
_mesh_ports: Dict[int, int] = {}


def _chip_env(chip: int) -> Dict[str, str]:
    """One TPU chip per slot, like CUDA_VISIBLE_DEVICES per GPU slot
    (reference: job.go:41-47). The visible chip alone is not enough
    for libtpu: of four such processes on a four-chip v5e host one
    starts and three abort on libtpu's multi-process lock (PERF.md,
    PR 21). The bounds make each worker a one-chip slice of its own,
    and each slice's mesh controller listens on a port the OS just
    handed out as free — not libtpu's default plus the slot, which a
    second job on the host, or the dying worker a respawn replaces,
    may hold. A launch that lays its processes out itself
    (`TPU_PROCESS_BOUNDS` in the runner's environment) keeps its
    layout. Harmless when workers run on CPU."""
    env = {"TPU_VISIBLE_DEVICES": str(chip)}
    if not os.environ.get("TPU_PROCESS_BOUNDS"):
        _mesh_ports.pop(chip, None)
        port = free_port()
        # workers spawned together have not bound theirs yet
        while port in _mesh_ports.values():
            port = free_port()
        _mesh_ports[chip] = port
        env.update({
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{port}",
            "TPU_MESH_CONTROLLER_PORT": str(port),
        })
    return env


def _worker_env_delta(
    self_id: PeerID,
    peers: PeerList,
    version: int,
    strategy: str,
    parent: Optional[PeerID],
    config_server: str,
    chip: Optional[int],
    extra_env: Optional[Dict[str, str]],
) -> Dict[str, str]:
    env = dict(
        kfenv.worker_env(
            self_id,
            peers,
            version,
            strategy=strategy,
            parent=parent,
            config_server=config_server,
        )
    )
    if chip is not None:
        env.update(_chip_env(chip))
    # persistent XLA compilation cache shared across worker GENERATIONS:
    # an elastic resize rebuilds mesh + jitted step in the new epoch's
    # workers; with the cache the recompile is a disk hit instead of a
    # from-scratch XLA run (VERDICT r2 item 5). One fixed place for
    # every run (compile_cache.py): the path is part of the cache key
    env[compile_cache.ENV] = compile_cache.cache_dir()
    if extra_env:
        env.update(extra_env)
    return env


def _attach_pump(popen, rank, log_path: str, quiet: bool) -> Proc:
    # append, never truncate: a replacement joiner after a recovery
    # reuses its predecessor's (rank, port) — and the predecessor's
    # log holds its crash record (KF_CHAOS_FIRE, flight-dump notices),
    # exactly the bytes a post-mortem (and the MTTR harness) needs
    log_file = open(log_path, "ab")
    color = _COLORS[(rank if rank is not None else 0) % len(_COLORS)]
    pump = threading.Thread(
        target=_pump,
        args=(popen.stdout, log_file, str(rank), color, quiet),
        daemon=True,
    )
    pump.start()
    return popen, pump


def spawn_worker(
    prog: List[str],
    self_id: PeerID,
    peers: PeerList,
    version: int,
    strategy: str = "AUTO",
    parent: Optional[PeerID] = None,
    config_server: str = "",
    chip: Optional[int] = None,
    logdir: str = ".",
    quiet: bool = False,
    extra_env: Optional[Dict[str, str]] = None,
) -> Proc:
    rank = peers.rank(self_id)
    # chaos hook: a scheduled spawn_delay fault for this rank holds the
    # spawn here — inside the resize window — emulating a slow host
    chaos.on_spawn(rank)
    env = dict(os.environ)
    env.update(
        _worker_env_delta(self_id, peers, version, strategy, parent,
                          config_server, chip, extra_env)
    )

    os.makedirs(logdir, exist_ok=True)
    log_path = os.path.join(logdir, f"worker-{rank}-{self_id.port}.log")
    popen = subprocess.Popen(
        prog,
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        bufsize=0,
    )
    popen, pump = _attach_pump(popen, rank, log_path, quiet)
    return Proc(
        peer=self_id,
        rank=rank if rank is not None else -1,
        popen=popen,
        chip=chip,
        log_path=log_path,
        pumps=[pump],
    )


def _is_python_prog(prog: List[str]) -> bool:
    """True only for programs prewarm can actually re-run via runpy:
    `python -m mod ...` or `python script.py ...`. Interpreter flags
    (`python -u x.py`) are rejected — runpy can't honor them, and a
    wrongly-warmed slot would crash at activation and fail the whole
    cluster fast. The interpreter must resolve to THIS runner's
    `sys.executable`: warm slots are spawned with it, so accepting any
    'python*' basename would warm-activate a job meant for a different
    interpreter (e.g. a venv's) under the wrong one."""
    if not prog:
        return False
    import shutil

    exe = shutil.which(prog[0]) or prog[0]
    try:
        # same interpreter file AND same bin directory: venvs symlink
        # bin/python to one base interpreter, so a realpath match alone
        # would accept a *different* venv's python (whose site-packages
        # the warm slot does not have)
        if (os.path.realpath(exe)
                != os.path.realpath(sys.executable)
                or os.path.realpath(os.path.dirname(os.path.abspath(exe)))
                != os.path.realpath(os.path.dirname(sys.executable))):
            return False
    except OSError:
        return False
    tail = prog[1:]
    if not tail:
        return False
    if tail[0] == "-m":
        return len(tail) >= 2
    return not tail[0].startswith("-")


class WarmPool:
    """Pre-spawned worker slots: interpreter + imports paid OUTSIDE the
    resize window (see `run/prewarm.py`; reference peers swap membership
    in-process in ms — peer.go:137-159 — this is the closest a
    process-per-epoch design gets).

    Only python programs can be pre-warmed (the worker runs in-process
    via runpy after activation); for anything else `take()` returns None
    and callers fall back to a cold `spawn_worker`.
    """

    def __init__(self, prog: List[str], target: int, quiet: bool = True):
        self.prog = prog
        self.target = max(0, target)
        self.quiet = quiet
        self.enabled = (_is_python_prog(prog)
                        and os.environ.get("KF_PREWARM", "1") != "0")
        # warm interpreters cost ~150 MB RSS and a few seconds of
        # import-time CPU each: cap the pool and spawn ONE per refill
        # call (the supervisor loop ticks ~4x/s) at low priority, so
        # warming never competes with the cluster it serves
        self.cap = int(os.environ.get("KF_PREWARM_MAX", "2"))
        self._warm: List[subprocess.Popen] = []
        # consecutive pre-activation deaths disable the pool: a broken
        # interpreter/env would otherwise respawn ~4x/s forever
        self._failures = 0
        self._max_failures = 3

    def refill(self):
        """Top the pool up (at most one spawn per call); call from the
        supervisor's idle loop."""
        if not self.enabled:
            return
        alive = [p for p in self._warm if p.poll() is None]
        died = len(self._warm) - len(alive)
        self._warm = alive
        if died:
            self._failures += died
            if self._failures >= self._max_failures:
                print(f"[kfrun] prewarm slots died {self._failures}x "
                      "before activation; disabling the warm pool "
                      "(joiners will cold-spawn)", flush=True)
                self.enabled = False
                return
        if len(self._warm) < min(self.target, self.cap):
            env = dict(os.environ)
            # jax freezes this env var at IMPORT time, and prewarm
            # imports jax before the activation env arrives — so the
            # compile-cache dir must be present at spawn, not activation
            env[compile_cache.ENV] = compile_cache.cache_dir()
            p = subprocess.Popen(
                [sys.executable, "-m", "kungfu_tpu.run.prewarm", "--"]
                + self.prog[1:],
                env=env,
                stdin=subprocess.PIPE,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                bufsize=0,
            )
            try:
                # deprioritize AFTER the fork: a preexec_fn would run
                # python between fork and exec in a multithreaded parent
                # (the log pumps), which can deadlock
                os.setpriority(os.PRIO_PROCESS, p.pid, 19)
            except (OSError, AttributeError):
                pass
            self._warm.append(p)

    def take(self) -> Optional[subprocess.Popen]:
        """Pop a warm slot, preferring one whose imports have finished
        (prewarm prints a readiness line once it blocks on stdin)."""
        import select

        self._warm = [p for p in self._warm if p.poll() is None]
        if not self._warm:
            return None
        ready_fds = select.select(
            [p.stdout for p in self._warm], [], [], 0)[0]
        for p in self._warm:
            if p.stdout in ready_fds:
                self._warm.remove(p)
                line = p.stdout.readline()
                if b"KF_WARM_READY" in line:
                    self._failures = 0
                    return p
                # stderr is merged into stdout: early output that isn't
                # the marker means the preimport failed — not a warm slot
                print(f"[kfrun] discarding failed prewarm slot: "
                      f"{line.decode(errors='replace').strip()!r}",
                      flush=True)
                p.kill()
                self._failures += 1
                return self.take()
        return self._warm.pop(0) if self._warm else None  # still importing

    def mark_activation_ok(self):
        """A successful activation proves the pool healthy — also for
        slots popped on take()'s still-importing path, which bypasses
        the marker-read reset. Without this, scattered pre-activation
        deaths over a long run would permanently disable the pool
        despite healthy activations in between."""
        self._failures = 0

    def shutdown(self):
        for p in self._warm:
            try:
                p.stdin.close()  # EOF => prewarm exits 0
            except (OSError, ValueError):  # dead slot / already closed
                pass
        deadline = 2.0
        for p in self._warm:
            try:
                p.wait(timeout=deadline)
            except subprocess.TimeoutExpired:
                p.kill()
        self._warm.clear()


def activate_warm(
    pool: WarmPool,
    self_id: PeerID,
    peers: PeerList,
    version: int,
    strategy: str = "AUTO",
    parent: Optional[PeerID] = None,
    config_server: str = "",
    chip: Optional[int] = None,
    logdir: str = ".",
    quiet: bool = False,
    extra_env: Optional[Dict[str, str]] = None,
) -> Optional[Proc]:
    """Turn a warm slot into a live worker: one JSON env write. Returns
    None when no warm slot is available (caller cold-spawns)."""
    import json

    popen = pool.take()
    if popen is None:
        return None
    try:
        # warming ran at nice 19 to stay off the cluster's CPUs; the
        # activated WORKER must run at normal priority (root only —
        # unprivileged runners keep the inherited niceness)
        os.setpriority(os.PRIO_PROCESS, popen.pid, 0)
    except (OSError, AttributeError):
        pass
    rank = peers.rank(self_id)
    env = _worker_env_delta(self_id, peers, version, strategy, parent,
                            config_server, chip, extra_env)
    os.makedirs(logdir, exist_ok=True)
    log_path = os.path.join(logdir, f"worker-{rank}-{self_id.port}.log")
    try:
        popen.stdin.write((json.dumps(env) + "\n").encode())
        popen.stdin.flush()
        popen.stdin.close()
    except (OSError, ValueError):  # slot died / pipe already closed
        popen.kill()
        return None
    pool.mark_activation_ok()
    popen, pump = _attach_pump(popen, rank, log_path, quiet)
    return Proc(
        peer=self_id,
        rank=rank if rank is not None else -1,
        popen=popen,
        chip=chip,
        log_path=log_path,
        pumps=[pump],
    )
