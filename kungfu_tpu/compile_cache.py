"""One persistent XLA compile cache, placed from outside.

The cache's path is part of its key, so a directory that moves never
hits: under a log directory, a temporary name, a pid or a time, every
run compiles from scratch. Every entry point that compiles for the chip
(`bench.py`, `benchmarks/lm.py`, `benchmarks/throughput.py`,
`chip_smoke.py`'s children) and the launcher (`run/job.py`, for its
workers) asks this module, and gets the same answer:

- where `JAX_COMPILATION_CACHE_DIR` is set, that directory — JAX reads
  the variable itself, and nothing here sets another in code;
- where it is not, `<checkout>/.jax-cache` (listed in `.gitignore`).

JAX-free at import, so the launcher's parent process stays off JAX.
"""

from __future__ import annotations

import os
import time
from dataclasses import asdict, dataclass

ENV = "JAX_COMPILATION_CACHE_DIR"

# the directory that holds the package: the checkout this repo is run
# from. A pip-installed copy resolves into site-packages, which may not
# be writable: place the cache from outside (`ENV`) there.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cache_dir() -> str:
    """The one compile-cache directory (see the module docstring)."""
    return os.environ.get(ENV) or os.path.join(_CHECKOUT, ".jax-cache")


@dataclass
class CacheStats:
    """Persistent-cache lookups since `enable()`, counted from JAX's own
    monitoring events. A benchmark prints them beside its compile time,
    which means nothing without knowing whether the cache was warm."""

    dir: str
    hits: int = 0
    misses: int = 0

    def _on_event(self, event: str, **_kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def as_dict(self) -> dict:
        return asdict(self)


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


def enable() -> CacheStats:
    """Turn the persistent cache on for this process, before its first
    compile, and start counting its hits and misses."""
    import jax

    stats = CacheStats(cache_dir())
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", stats.dir)
    jax.monitoring.register_event_listener(stats._on_event)
    return stats
