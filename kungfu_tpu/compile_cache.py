"""One persistent XLA compile cache, placed from outside, and the
process's one compile ledger.

The cache's path is part of its key, so a directory that moves never
hits: under a log directory, a temporary name, a pid or a time, every
run compiles from scratch. Every entry point that compiles for the chip
(`bench.py`, `benchmarks/lm.py`, `benchmarks/throughput.py`,
`chip_smoke.py`'s children) and the launcher (`run/job.py`, for its
workers) asks this module, and gets the same answer:

- where `JAX_COMPILATION_CACHE_DIR` is set, that directory — JAX reads
  the variable itself, and nothing here sets another in code;
- where it is not, `<checkout>/.jax-cache` (listed in `.gitignore`).

**The ledger.** JAX times every phase of every program it compiles
(`jax/_src/dispatch.py::LogElapsedTimeContextManager`,
`jax/_src/compiler.py::compile_or_get_cached`) and hands the numbers to
whoever listens. `enable()` registers ONE set of listeners a process,
and `CacheStats` keeps one record a compiled program: tracing the
function to a jaxpr, lowering the jaxpr to MLIR, and the backend (XLA's
compile on a miss; key, read and deserialise on a hit). A listener runs
only when JAX compiles, so a steady step pays nothing. Each closed
record also goes to `/metrics` (`kf_compile_*`) and, under `KF_TRACE`,
to the kftrace ring as `compile.trace` / `compile.lower` /
`compile.backend` spans (docs/observability.md): those names come from
here alone.

JAX-free at import, so the launcher's parent process stays off JAX.
"""

from __future__ import annotations

import collections
import os
import re
import threading
import time
from typing import Dict, List, Optional

from . import trace
from .trace.metrics import REGISTRY

ENV = "JAX_COMPILATION_CACHE_DIR"

# the directory that holds the package: the checkout this repo is run
# from. A pip-installed copy resolves into site-packages, which may not
# be writable: place the cache from outside (`ENV`) there.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: JAX's time-span events -> the ledger's phases
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
#: JAX's cache events, in the order one compile fires them. A request
#: that looked the cache up is `skipped` unless a hit follows or, after
#: the compile, JAX's own `cache_misses`, which fires only where an
#: entry is WRITTEN: a program that compiled in under
#: `jax_persistent_cache_min_compile_time_secs` (1 s unless an entry
#: point says otherwise) is looked up at every boot and never kept, and
#: does not make a warm cache read as cold
_CACHE = {
    "/jax/compilation_cache/compile_requests_use_cache": "skipped",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_COUNTS = {"hit": "hits", "miss": "misses", "skipped": "skipped"}

RECORDS = 256   # newest records kept: an elastic job compiles for days
BY_FUN = 8      # functions `as_dict()` names, by seconds
_FUNS = 256     # distinct names totalled; further ones fold into "(other)"
_PENDING = 1 << 16  # a thread's closed traces waiting for their outer one

_PHASE_S = ("trace_s", "lower_s", "backend_s")  # a record's seconds


def cache_dir() -> str:
    """The one compile-cache directory (see the module docstring)."""
    return os.environ.get(ENV) or os.path.join(_CHECKOUT, ".jax-cache")


def _bare(fun_name: str) -> str:
    """`jit(step)` / `pmap(step)`, as lowering and the backend name a
    program, -> `step`, as tracing names its function."""
    m = re.fullmatch(r"\w+\((.*)\)", fun_name)
    return m.group(1) if m else fun_name


class _Open(threading.local):
    """What one thread has heard of the compile it is in: the events of
    one compile arrive on one thread, in order."""

    def __init__(self):
        # closed traces and records no outer trace has claimed yet,
        # oldest first: (fun, start, end, traces, held_s). `traces` is
        # how many trace events the entry stands for (itself and what
        # it nests; 0 for a record), `held_s` the seconds of whole
        # records inside it, which are in the totals already
        self.stack: List[tuple] = []
        self.lower: Optional[tuple] = None
        self.cache = "off"
        self.load_s = 0.0
        self.saved_s = 0.0


class CacheStats:
    """The compile ledger: one record a compiled program since
    `enable()`, from JAX's own monitoring events. A benchmark prints
    `as_dict()` beside its set-up time, which means nothing without
    knowing what compiled, for how long, and whether the cache was
    warm."""

    def __init__(self, dir: str):
        self.dir = dir
        self._t0 = time.time()
        self._open = _Open()
        self._mu = threading.Lock()
        # kf: guarded_by(_mu)
        self._records: collections.deque = collections.deque(
            maxlen=RECORDS)
        self._by_fun: Dict[str, Dict] = {}  # kf: guarded_by(_mu)
        # kf: guarded_by(_mu)
        self._totals = {"programs": 0, "hits": 0, "misses": 0,
                        "skipped": 0, "nested_traces": 0, "load_s": 0.0,
                        "saved_s": 0.0, **{k: 0.0 for k in _PHASE_S}}

    # -- the listeners (one set a process: `enable`) ---------------------------

    def _on_event(self, event: str, **_kw) -> None:
        cache = _CACHE.get(event)
        if cache is not None:
            self._open.cache = cache

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == _LOAD:
            self._open.load_s = secs
        elif event == _SAVED:
            self._open.saved_s = secs

    def _on_span(self, event: str, start: float, end: float,
                 fun_name: str = "", **_kw) -> None:
        phase = _PHASES.get(event)
        if phase == "trace":
            # a jitted function traced INSIDE another trace closes
            # first and has no backend event: the outer span holds its
            # time, so it is folded into the outer entry, never added
            stack, traces, held = self._open.stack, 1, 0.0
            while stack and stack[-1][1] >= start:
                _, _, _, n, h = stack.pop()
                traces += n
                held += h
            if len(stack) >= _PENDING:
                del stack[:_PENDING // 2]
            stack.append((fun_name, start, end, traces, held))
        elif phase == "lower":
            self._open.lower = (fun_name, start, end)
        elif phase == "backend":
            self._close(_bare(fun_name), start, end)

    def _close(self, fun: str, start: float, end: float) -> None:
        """A record closes at its `backend_compile_duration`."""
        cur = self._open
        spans = {"backend": (start, end)}
        if cur.lower is not None and _bare(cur.lower[0]) == fun:
            spans["lower"] = cur.lower[1:]
        # the program's own trace: the newest pending one of its name
        # (none where jit still held the jaxpr and only lowered again)
        stack, nested, held = cur.stack, 0, 0.0
        for i in range(len(stack) - 1, -1, -1):
            if stack[i][0] == fun and stack[i][2] <= start:
                _, t0, t1, traces, held = stack[i]
                spans["trace"], nested = (t0, t1), traces - 1
                del stack[i:]
                break
        first = min(s for s, _ in spans.values())
        secs = {p: t1 - t0 for p, (t0, t1) in spans.items()}
        rec = {
            "fun": fun, "at_s": first - self._t0,
            # less the whole records inside the trace (a program that
            # an eager operation compiled while this one was traced)
            "trace_s": max(0.0, secs.get("trace", 0.0) - held),
            "lower_s": secs.get("lower", 0.0),
            "backend_s": secs["backend"],
            "load_s": cur.load_s, "saved_s": cur.saved_s,
            "cache": cur.cache, "nested_traces": nested,
        }
        cur.lower, cur.cache = None, "off"
        cur.load_s = cur.saved_s = 0.0
        stack.append((None, first, end, 0,
                      held + sum(rec[k] for k in _PHASE_S)))
        with self._mu:
            self._records.append(rec)
            tot = self._totals
            row = self._by_fun.get(fun)
            if row is None:
                if len(self._by_fun) >= _FUNS:
                    fun = "(other)"
                row = self._by_fun.setdefault(fun, {
                    "n": 0, **{k: 0.0 for k in _PHASE_S},
                    "hits": 0, "misses": 0})
            for into in (tot, row):
                for k in _PHASE_S:
                    into[k] += rec[k]
                count = _COUNTS.get(rec["cache"])
                if count in into:
                    into[count] += 1
            row["n"] += 1
            tot["programs"] += 1
            tot["load_s"] += rec["load_s"]
            tot["saved_s"] += rec["saved_s"]
            tot["nested_traces"] += nested
        self._publish(rec, spans)

    @staticmethod
    def _publish(rec: Dict, spans: Dict) -> None:
        """A closed record to `/metrics` and, under `KF_TRACE`, to the
        ring: on the recorder's clock, under its context now."""
        for phase in spans:
            REGISTRY.inc("kf_compile_seconds_total", rec[f"{phase}_s"],
                         phase=phase)
        REGISTRY.inc("kf_compile_programs_total")
        if rec["cache"] != "off":
            REGISTRY.inc("kf_compile_cache_total", result=rec["cache"])
        if not trace.enabled():
            return
        now_us, now = trace.recorder().now_us(), time.time()
        for phase, (t0, t1) in spans.items():
            trace.complete(f"compile.{phase}",
                           now_us - int((now - t0) * 1e6),
                           int((t1 - t0) * 1e6), cat="compile",
                           fun=rec["fun"], cache=rec["cache"],
                           load_s=rec["load_s"])

    # -- what a reader gets ----------------------------------------------------

    def compile_s(self) -> float:
        """Seconds in all three phases since `enable()`, on every
        thread: a caller that times a span round a jitted call takes
        the difference out, and no more than the span."""
        with self._mu:
            return sum(self._totals[k] for k in _PHASE_S)

    def records(self, fun: Optional[str] = None,
                until_last: Optional[str] = None) -> List[Dict]:
        """The newest `RECORDS` records, oldest first: those whose
        `fun` matches the pattern `fun`, among those up to and
        including the LAST whose `fun` matches `until_last` (none where
        nothing does: a benchmark's set-up ends at its train step's
        last compile, and what it compiles afterwards to check the
        result is not set-up)."""
        with self._mu:
            out = [dict(r) for r in self._records]
        if until_last is not None:
            last = max((i for i, r in enumerate(out)
                        if re.search(until_last, r["fun"])), default=-1)
            out = out[:last + 1]
        if fun is not None:
            out = [r for r in out if re.search(fun, r["fun"])]
        return out

    def as_dict(self) -> dict:
        """JSON-serialisable: the directory, the running totals and the
        `BY_FUN` functions with most seconds."""
        with self._mu:
            rows = sorted(self._by_fun.items(), key=lambda kv: -sum(
                kv[1][k] for k in _PHASE_S))[:BY_FUN]
            return {"dir": self.dir, **self._totals,
                    "by_fun": {fun: dict(row) for fun, row in rows}}


def timed_compile(jitted, *args):
    """Compile `jitted` for `args` ahead of its first call and return
    ``(seconds, compiled)`` — for the compile time and the compiled
    text an entry point prints. `compiled` is for reading, not for
    running: it is fixed to the shardings of `args`, where the jitted
    callable re-specialises when a step hands back its state laid out
    otherwise (the MoE step on a model axis does). The caller goes on
    calling `jitted`, whose first call finds this executable in jit's
    own cache and compiles nothing."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    return time.perf_counter() - t0, compiled


_mu = threading.Lock()
_ledger: Optional[CacheStats] = None  # kf: guarded_by(_mu)


def ledger() -> Optional[CacheStats]:
    """The process's ledger; None until `enable()` has run."""
    return _ledger


def enable() -> CacheStats:
    """Turn the persistent cache on for this process, before its first
    compile, and start the ledger. Every call returns the one ledger;
    the first registers its listeners."""
    global _ledger
    import jax

    with _mu:
        if _ledger is None:
            stats = CacheStats(cache_dir())
            if not os.environ.get(ENV):
                jax.config.update("jax_compilation_cache_dir", stats.dir)
            jax.monitoring.register_event_listener(stats._on_event)
            jax.monitoring.register_event_duration_secs_listener(
                stats._on_duration)
            jax.monitoring.register_event_time_span_listener(
                stats._on_span)
            _ledger = stats
        return _ledger


def _reset_for_tests() -> None:
    """Forget the ledger and take its listeners off JAX (tests only)."""
    global _ledger
    with _mu:
        stats, _ledger = _ledger, None
    if stats is not None:
        from jax import monitoring

        monitoring.unregister_event_listener(stats._on_event)
        monitoring.unregister_event_duration_listener(stats._on_duration)
        monitoring.unregister_event_time_span_listener(stats._on_span)
