"""Warm worker bootstrap: pay interpreter + import cost before activation.

The reference swaps cluster membership in-process in milliseconds
(reference: srcs/go/kungfu/peer/peer.go:137-159 — one Go peer object is
re-pointed at the new cluster). A Python worker can't do that across
processes: round 2 measured ~6s per elastic resize, dominated by
spawning the joiner (interpreter start + numpy/jax/kungfu_tpu imports)
inside the resize window. This module moves that cost OUT of the window:
the runner keeps a pool of "warm" processes that have already imported
the heavy stack and are blocked reading stdin; activating one is a
single write of the worker's epoch environment.

Protocol (driven by `job.WarmPool` / `job.activate_warm`):

1. Runner spawns `python -m kungfu_tpu.run.prewarm -- <prog tail>` with
   stdin=PIPE at job start / during steady state — NOT during a resize.
2. This process imports numpy, jax, kungfu_tpu (backend init stays
   lazy, so accelerator visibility env vars can still arrive later),
   then blocks on one stdin line.
3. At activation the runner writes one JSON object of env deltas
   (`kungfu_tpu.env.worker_env` + chip visibility) and closes stdin.
4. The line is applied to `os.environ` and the worker program runs
   in-process via runpy — same pid, imports already hot.

An EOF on stdin (runner shutdown before activation) exits 0.
"""

from __future__ import annotations

import json
import os
import runpy
import sys


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "--":
        argv = argv[1:]
    if not argv:
        print("prewarm: no program given", file=sys.stderr)
        return 2

    # Pay the heavy imports now, before activation. jax does NOT
    # initialize a backend at import time, so TPU_VISIBLE_DEVICES /
    # JAX_PLATFORMS from the activation env still take effect.
    try:
        import numpy  # noqa: F401
        import jax  # noqa: F401
        import kungfu_tpu  # noqa: F401
    # third-party import-time side effects can raise anything; a broken
    # optional dep must not kill the warm slot, only cost it the
    # preimport win
    # kflint: disable=retry-discipline
    except Exception as e:
        print(f"prewarm: preimport skipped: {e}", file=sys.stderr)

    # readiness marker: WarmPool.take() prefers slots whose imports are
    # done (it consumes this line); if this slot is activated early the
    # marker just lands as the first line of the worker log
    sys.stdout.write("KF_WARM_READY\n")
    sys.stdout.flush()
    line = sys.stdin.readline()
    if not line.strip():
        return 0  # runner shut down before this slot was needed
    env = json.loads(line)
    os.environ.update({str(k): str(v) for k, v in env.items()})
    if "JAX_COMPILATION_CACHE_DIR" in env:
        # jax froze the env var at import; late-bind via config so an
        # activation-time cache dir still takes effect
        import jax

        jax.config.update("jax_compilation_cache_dir",
                          env["JAX_COMPILATION_CACHE_DIR"])

    if argv[0] == "-m":
        if len(argv) < 2:
            print("prewarm: -m needs a module", file=sys.stderr)
            return 2
        module, rest = argv[1], argv[2:]
        sys.argv = [module] + rest
        # sys.path[0] is already the cwd: this process was itself
        # launched with `python -m`, the same layout cold `python -m
        # <module>` would produce
        try:
            runpy.run_module(module, run_name="__main__", alter_sys=True)
        except SystemExit as e:
            return _exit_code(e)
        return 0
    sys.argv = argv
    # cold `python script.py` puts the SCRIPT'S directory at sys.path[0]
    # (how examples import their sibling common.py) and does NOT expose
    # the cwd; REPLACE the cwd entry this process's own `python -m`
    # launch left there, so warm == cold exactly
    sys.path[0] = os.path.dirname(os.path.abspath(argv[0]))
    try:
        runpy.run_path(argv[0], run_name="__main__")
    except SystemExit as e:
        return _exit_code(e)
    return 0


def _exit_code(e: SystemExit) -> int:
    if e.code is None:
        return 0
    if isinstance(e.code, int):
        return e.code
    print(e.code, file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
