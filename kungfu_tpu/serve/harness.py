"""Drive a real elastic serving cluster end to end.

One shared entry point for everything that wants the full decode tier
exercised for real — config server with the /serve ledger, kfrun
watcher, `serve.worker` replicas, live requests — with the
request-plane invariant gate applied at the end:
tests/test_serve_elastic.py, `benchmarks/serve.py`, the run-all.sh
serving smoke (stage 4h) and the `spot_serve_kill` scenario replay
all call `run_serve_cluster`.

The harness submits every request BEFORE launching the workers (the
ledger lives on the config server, which boots first), sizes the
token budget so traffic is still in flight when the schedule's
mid-run resize (or the chaos schedule's worker kill) lands, and
asserts afterwards that EVERY submitted request completed and
`RequestLedger.check_invariants()` is empty — the serving analog of
the goodput plane's phases-sum-to-wall gate.
"""

from __future__ import annotations

import os
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional, Sequence, Tuple

from ..plan import free_port

SERVE_MARKERS = (
    ("KF_SERVE_READY", "no decode worker came up"),
    ("KF_SERVE_DONE", "no worker drained the request ledger"),
)

RESIZE_MARKERS = SERVE_MARKERS + (
    ("KF_SERVE_JOINER", "the joining replica never adopted weights"),
    ("KF_SERVE_RESIZED", "no survivor rode the epoch switch"),
)

RECOVERY_MARKERS = SERVE_MARKERS + (
    ("KF_CHAOS_FIRE", "the scheduled worker kill never fired"),
    ("KF_SERVE_RECOVERED", "no survivor recovered the decode tier"),
)

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def default_requests(n: int, gen_len: int = 12,
                     vocab: int = 50257, seed: int = 17
                     ) -> List[Tuple[List[int], int]]:
    """Deterministic request mix: varied prompt lengths (so the paged
    batch is genuinely ragged), seeded token values."""
    import numpy as np

    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        plen = 2 + int(rng.integers(0, 9))
        prompt = rng.integers(0, vocab, size=plen)
        out.append(([int(t) for t in prompt], gen_len))
    return out


def prefix_requests(n: int, prefix_len: int = 48, gen_len: int = 12,
                    vocab: int = 50257, seed: int = 23
                    ) -> List[Tuple[List[int], int]]:
    """Prefix-heavy request mix (system prompt + short user tails):
    every request shares one `prefix_len`-token common prefix and
    diverges only in a 2-4 token tail — the workload CoW prefix
    sharing collapses (`KF_SERVE_SHARE_PREFIX`, docs/serving.md)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    common = [int(t) for t in rng.integers(0, vocab, size=prefix_len)]
    out = []
    for _ in range(n):
        tail = rng.integers(0, vocab, size=2 + int(rng.integers(0, 3)))
        out.append((common + [int(t) for t in tail], gen_len))
    return out


def run_serve_cluster(
        requests: Sequence[Tuple[List[int], int]],
        schedule: str = "",
        start_np: int = 2,
        slots: int = 4,
        port_range: str = "27100-27999",
        timeout: int = 420,
        logdir: Optional[str] = None,
        markers=SERVE_MARKERS,
        extra_env: Optional[Dict[str, str]] = None,
        recover: bool = False,
        policy: str = "",
        warmup: int = 0,
        grow_when_done: Optional[int] = None,
        server=None) -> Dict:
    """Boot config server + kfrun -w + serve workers, submit
    `requests` ([(prompt, max_new), ...]), wait for the tier to drain
    the ledger, and gate on completion + ledger invariants.

    `warmup` > 0 front-loads that many tiny throwaway requests and
    defers the MEASURED batch until they complete — so the reported
    per-request latencies are warm-tier numbers (worker boot + jit
    compile excluded), the way an operator would measure a running
    service. `grow_when_done` (an absolute completed-request count,
    warmup included) POSTs the config server's /addworker once that
    many requests finished — the operator-driven mid-traffic grow the
    resize benchmark cell measures p99 *through*.

    Returns {"logs", "results", "stats", "wall_s", "measured_wall_s"}
    — `results` covers the measured batch in submission order, each
    with per-request latency_ms. Raises AssertionError (with logs) on
    worker failure, missing markers, an incomplete request, or any
    ledger-invariant violation."""
    import threading

    from ..elastic.config_server import ConfigServer

    own_server = server is None
    if own_server:
        from ..env import env_int

        server = ConfigServer(
            port=env_int("KF_SERVE_PORT", 0, minimum=0)).start()
    own_logdir = logdir is None
    tmp = tempfile.TemporaryDirectory() if own_logdir else None
    logdir = tmp.name if own_logdir else logdir
    try:
        ledger = server.serve_ledger
        # the ledger lives in THIS process (the config server's), so
        # ledger knobs riding `extra_env` / a scenario's env block
        # must be applied here — merging them only into the worker
        # subprocess env would make them silent no-ops
        if extra_env:
            from ..env import env_float, env_int

            ledger.lease_ms = env_float("KF_SERVE_LEASE_MS",
                                        ledger.lease_ms, extra_env,
                                        minimum=100.0)
            ledger.max_queue = env_int("KF_SERVE_QUEUE",
                                       ledger.max_queue, extra_env,
                                       minimum=1)
        warmup_ids = [ledger.submit([3, 5, 7], 2)
                      for _ in range(warmup)]
        ids: List[int] = []
        measured_t: Dict[str, float] = {}
        stop = threading.Event()

        def _feeder():
            """Submit the measured batch once warmup drains, fire the
            mid-traffic grow at the progress threshold, and stamp the
            drain instant (so throughput excludes teardown). Errors
            land in measured_t["error"] and re-raise on the MAIN
            thread after the run — a daemon-thread traceback on
            stderr must not decay into a misleading
            'threshold never reached' assertion."""
            submitted = warmup == 0
            grown = grow_when_done is None
            total = warmup + len(requests)
            if submitted:
                ids.extend(ledger.submit(p, m) for p, m in requests)
                measured_t["start"] = time.perf_counter()
            while not stop.is_set():
                st = ledger.stats()
                if not submitted and st["done"] >= warmup:
                    ids.extend(ledger.submit(p, m)
                               for p, m in requests)
                    measured_t["start"] = time.perf_counter()
                    submitted = True
                if submitted and not grown \
                        and st["done"] >= grow_when_done:
                    err = server._resize(+1)
                    if err:
                        raise AssertionError(
                            f"mid-traffic grow failed: {err}")
                    measured_t["grow"] = time.perf_counter()
                    grown = True
                if submitted and grown and st["done"] >= total:
                    measured_t["end"] = time.perf_counter()
                    return
                stop.wait(0.05)

        def _feeder_guarded():
            try:
                _feeder()
            # capture-and-re-raise-on-main-thread, not a swallow: the
            # join below raises measured_t["error"] verbatim
            # kflint: disable=retry-discipline — stashed for the main thread
            except BaseException as e:  # noqa: BLE001
                measured_t["error"] = e

        feeder = threading.Thread(target=_feeder_guarded, daemon=True)
        t0 = time.perf_counter()
        env = dict(os.environ)
        env["PYTHONPATH"] = _REPO + os.pathsep + env.get(
            "PYTHONPATH", "")
        env["KF_TIMEOUT_MS"] = env.get("KF_TIMEOUT_MS", "120000")
        env["KF_LOG_LEVEL"] = "warn"
        env["JAX_PLATFORMS"] = "cpu"
        env["TEST_SCHEDULE"] = schedule
        env["KF_SERVE_EXPECT"] = str(warmup + len(requests))
        env["KF_POLICY"] = policy
        if recover:
            env["KF_RECOVER"] = "1"
            env.setdefault("KF_RECOVERY_DEADLINE_MS", "30000")
        if extra_env:
            env.update(extra_env)
        cmd = [sys.executable, "-m", "kungfu_tpu.run",
               "-np", str(start_np),
               "-H", f"127.0.0.1:{slots}",
               "-port-range", port_range,
               "-runner-port", str(free_port()),
               "-w", "-config-server", server.get_url,
               "-logdir", logdir, "-q"]
        if recover:
            cmd.append("-recover")
        cmd += ["--", sys.executable, "-m", "kungfu_tpu.serve.worker"]
        feeder.start()
        try:
            out = subprocess.run(cmd, cwd=_REPO, env=env,
                                 capture_output=True, text=True,
                                 timeout=timeout)
        finally:
            stop.set()
            feeder.join(timeout=10.0)
            if "error" in measured_t:
                raise measured_t["error"]
            # the feeder can be stopped between the last completion
            # and its next poll: stamp the drain instant at join so
            # measured_wall never silently falls back to boot+teardown
            if "start" in measured_t:
                measured_t.setdefault("end", time.perf_counter())
        wall = time.perf_counter() - t0
        logs = ""
        for f in sorted(os.listdir(logdir)):
            if f.endswith(".log"):
                with open(os.path.join(logdir, f)) as fh:
                    logs += f"--- {f} ---\n" + fh.read()
        logs += f"--- runner ---\n{out.stdout}"
        if out.returncode != 0:
            raise AssertionError(
                f"serve cluster failed rc={out.returncode}:\n"
                f"stdout: {out.stdout[-2000:]}\n"
                f"stderr: {out.stderr[-2000:]}\n{logs[-3000:]}")
        for marker, why in markers:
            if marker not in logs:
                raise AssertionError(
                    f"serve cluster: {why} ({marker} missing):\n"
                    f"{logs[-3000:]}")
        if len(ids) != len(requests):
            raise AssertionError(
                f"feeder submitted {len(ids)}/{len(requests)} "
                f"measured requests (warmup never drained?):\n"
                f"{logs[-3000:]}")
        results = [ledger.result(rid) for rid in warmup_ids + ids]
        for r in results:
            if r["state"] != "done":
                raise AssertionError(
                    f"request {r['id']} ended {r['state']!r} "
                    f"(tokens {len(r['tokens'])}/{r['max_new']}):\n"
                    f"{logs[-3000:]}")
        violations = ledger.check_invariants()
        if violations:
            raise AssertionError(
                f"request-ledger invariants violated: {violations}\n"
                f"{logs[-3000:]}")
        if grow_when_done is not None and "grow" not in measured_t:
            raise AssertionError(
                "the mid-traffic grow threshold was never reached "
                f"(grow_when_done={grow_when_done}):\n{logs[-3000:]}")
        measured_wall = (
            measured_t["end"] - measured_t["start"]
            if "end" in measured_t and "start" in measured_t
            else wall)
        return {"logs": logs, "results": results[len(warmup_ids):],
                "stats": ledger.stats(), "wall_s": round(wall, 3),
                "measured_wall_s": round(measured_wall, 3)}
    finally:
        if tmp is not None:
            tmp.cleanup()
        if own_server:
            server.stop()


def seed_checkpoint(ckpt_dir: str, size: str = "tiny",
                    max_len: int = 64) -> None:
    """Write one sharded checkpoint generation of the serve model's
    params (np=1), so a cluster cold-boots its replicas from the
    durable tier re-sharded to ITS np — the serving side of
    reshard-on-restore."""
    import jax.numpy as jnp

    from ..checkpoint_async import save_sharded
    from .engine import build_lm

    _model, params, _ = build_lm(size, max_position=max_len,
                                 dtype=jnp.float32)
    os.makedirs(ckpt_dir, exist_ok=True)
    save_sharded(ckpt_dir, params, step=1, rank=0, nprocs=1)
