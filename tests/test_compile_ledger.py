"""The compile ledger (`kungfu_tpu/compile_cache.py::CacheStats`): one
record a compiled program from JAX's own monitoring events, on the
kftrace ring and `/metrics`."""

import json
import os
import subprocess
import sys
import time

import pytest

from kungfu_tpu import compile_cache, trace
from kungfu_tpu.trace.metrics import REGISTRY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def ledger(tmp_path, monkeypatch):
    """The persistent cache on in a temporary directory, keeping every
    program however quick its compile, and a fresh ledger."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.delenv(compile_cache.ENV, raising=False)
    monkeypatch.setattr(compile_cache, "cache_dir", lambda: str(tmp_path))
    before = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    compile_cache._reset_for_tests()
    compilation_cache.reset_cache()
    stats = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    yield stats
    compile_cache._reset_for_tests()
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    trace._reset_for_tests()


def _scale():
    """A jitted function no other test has compiled (jit's own cache is
    the process's)."""
    import jax

    @jax.jit
    def ledger_scale(x):
        return x * 3.0 + 1.0

    return ledger_scale


def test_a_miss_then_a_hit(ledger):
    import jax
    import jax.numpy as jnp

    x = jnp.ones((7,), jnp.float32)
    f = _scale()
    f(x)
    (miss,) = ledger.records(fun="^ledger_scale$")
    assert miss["cache"] == "miss" and miss["load_s"] == 0.0
    assert miss["trace_s"] > 0 and miss["lower_s"] > 0
    assert miss["backend_s"] > 0 and miss["at_s"] >= 0
    f(x)                      # jit's own cache: nothing compiles
    assert len(ledger.records(fun="^ledger_scale$")) == 1
    jax.clear_caches()
    _scale()(x)
    _, hit = ledger.records(fun="^ledger_scale$")
    assert hit["cache"] == "hit" and hit["load_s"] > 0
    assert hit["backend_s"] >= hit["load_s"]
    assert hit["at_s"] > miss["at_s"]
    row = ledger.as_dict()["by_fun"]["ledger_scale"]
    assert (row["n"], row["hits"], row["misses"]) == (2, 1, 1)
    assert ledger.as_dict()["hits"] >= 1
    assert ledger.as_dict()["misses"] >= 1


def test_a_program_too_quick_to_keep_is_no_miss(ledger):
    """At JAX's own `jax_persistent_cache_min_compile_time_secs` (1 s:
    every entry point but the benchmark's runner) a one-op program is
    looked up at every boot, compiled and never written. JAX fires no
    `cache_misses` for it, and neither `misses` nor
    `kf_compile_cache_total{result=miss}` may: a warm cache would read
    as cold for ever."""
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

    def read():
        d = ledger.as_dict()
        return (d["misses"], d["skipped"], d["hits"],
                REGISTRY.read("kf_compile_cache_total", result="miss"),
                REGISTRY.read("kf_compile_cache_total", result="skipped"))

    x = jnp.ones((7,), jnp.float32)
    x.block_until_ready()
    before = read()
    for boot in (1, 2):       # the second boot finds nothing either
        _scale()(x)
        jax.clear_caches()
        assert [r["cache"] for r in ledger.records(
            fun="^ledger_scale$")] == ["skipped"] * boot
    after = read()
    assert [b - a for a, b in zip(before, after)] == [0, 2, 0, 0, 2]
    row = ledger.as_dict()["by_fun"]["ledger_scale"]
    assert (row["n"], row["hits"], row["misses"]) == (2, 0, 0)
    assert not os.listdir(ledger.dir)


def test_nested_traces_count_once(ledger):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def ledger_inner(x):
        return jnp.sin(x) * 2

    @jax.jit
    def ledger_outer(x):
        return ledger_inner(x) + ledger_inner(x + 1)

    x = jnp.ones((5,), jnp.float32)
    x.block_until_ready()
    before = ledger.as_dict()
    t0 = time.time()
    ledger_outer(x).block_until_ready()
    wall = time.time() - t0
    after = ledger.as_dict()
    (rec,) = ledger.records(fun="^ledger_outer$")
    assert not ledger.records(fun="^ledger_inner$")   # no program
    assert rec["nested_traces"] >= 1
    assert after["programs"] - before["programs"] == 1
    assert after["nested_traces"] - before["nested_traces"] \
        == rec["nested_traces"]
    spent = sum(after[k] - before[k]
                for k in ("trace_s", "lower_s", "backend_s"))
    assert 0 < spent <= wall
    assert spent == pytest.approx(
        rec["trace_s"] + rec["lower_s"] + rec["backend_s"])


def test_a_program_compiled_inside_a_trace_is_not_counted_twice(ledger):
    """An eager operation on concrete values inside a traced function
    compiles a program of its own while the outer trace is open."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def ledger_eager(x):
        with jax.ensure_compile_time_eval():
            table = jnp.cumsum(jnp.asarray(np.arange(11.0)))
        return x + table[3]

    t0 = time.time()
    jax.jit(ledger_eager)(jnp.float32(1)).block_until_ready()
    wall = time.time() - t0
    (outer,) = ledger.records(fun="^ledger_eager$")
    inside = [r for r in ledger.records()
              if r["at_s"] > outer["at_s"] and r is not outer
              and r["fun"] != "ledger_eager"]
    assert inside, "the eager operation compiled nothing"
    spent = sum(r[k] for r in inside + [outer]
                for k in ("trace_s", "lower_s", "backend_s"))
    assert spent <= wall


def test_enable_twice_registers_one_set_of_listeners(ledger):
    from jax._src import monitoring

    def ours():
        return [sum(getattr(cb, "__self__", None) is ledger for cb in get())
                for get in (monitoring.get_event_listeners,
                            monitoring.get_event_duration_listeners,
                            monitoring.get_event_time_span_listeners)]

    assert compile_cache.enable() is ledger
    assert compile_cache.ledger() is ledger
    assert ours() == [1, 1, 1]
    compile_cache._reset_for_tests()
    assert ours() == [0, 0, 0] and compile_cache.ledger() is None


def test_as_dict_round_trips_through_json(ledger):
    import jax.numpy as jnp

    _scale()(jnp.ones((3,), jnp.float32))
    d = ledger.as_dict()
    assert json.loads(json.dumps(d)) == d
    assert set(d) == {"dir", "hits", "misses", "skipped", "programs",
                      "trace_s",
                      "lower_s", "backend_s", "load_s", "saved_s",
                      "nested_traces", "by_fun"}
    assert d["programs"] >= 1 and len(d["by_fun"]) <= compile_cache.BY_FUN
    assert set(d["by_fun"]["ledger_scale"]) == {
        "n", "trace_s", "lower_s", "backend_s", "hits", "misses"}


def test_records_are_bounded_and_cut(ledger):
    for i in range(compile_cache.RECORDS + 9):
        ledger._on_span("/jax/core/compile/backend_compile_duration",
                        float(i), i + 0.5, fun_name=f"jit(f{i % 3})")
    assert len(ledger.records()) == compile_cache.RECORDS
    assert ledger.as_dict()["programs"] == compile_cache.RECORDS + 9
    assert ledger.as_dict()["backend_s"] == pytest.approx(
        0.5 * (compile_cache.RECORDS + 9))
    cut = ledger.records(until_last="^f0$")
    assert cut[-1]["fun"] == "f0"
    assert len(cut) >= compile_cache.RECORDS - 2
    assert {r["fun"] for r in ledger.records(fun="^f1$")} == {"f1"}
    assert ledger.records(until_last="^nothing$") == []


def test_ring_holds_the_three_spans_under_the_context(ledger):
    import jax.numpy as jnp

    rec = trace.configure(enabled_=True)
    trace.set_context(rank=2, version=5, step=11)
    _scale()(jnp.ones((9,), jnp.float32))
    spans = {e["name"]: e for e in rec.snapshot()
             if e["cat"] == "compile"
             and e["args"]["fun"] == "ledger_scale"}
    assert set(spans) == {"compile.trace", "compile.lower",
                          "compile.backend"}
    (program,) = ledger.records(fun="^ledger_scale$")
    for name, e in spans.items():
        assert (e["rank"], e["version"], e["step"]) == (2, 5, 11)
        assert e["ph"] == "X" and e["args"]["cache"] == program["cache"]
        assert e["dur"] == pytest.approx(
            program[name.split(".")[1] + "_s"] * 1e6, abs=2)
        assert abs(e["ts"] + e["dur"] - rec.now_us()) < 60e6
    assert spans["compile.trace"]["ts"] <= spans["compile.lower"]["ts"] \
        <= spans["compile.backend"]["ts"]


def test_metrics_families_move(ledger):
    import jax.numpy as jnp

    def read():
        return ([REGISTRY.read("kf_compile_seconds_total", phase=p)
                 for p in ("trace", "lower", "backend")]
                + [REGISTRY.read("kf_compile_programs_total"),
                   REGISTRY.read("kf_compile_cache_total", result="miss")])

    before = read()
    _scale()(jnp.ones((2,), jnp.float32))
    assert all(b > a for a, b in zip(before, read()))
    text = "\n".join(REGISTRY.render())
    for family in ('kf_compile_seconds_total{phase="backend"}',
                   "kf_compile_programs_total",
                   'kf_compile_cache_total{result="miss"}'):
        assert family in text


def test_import_loads_no_jax():
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; import kungfu_tpu.compile_cache as c; "
         "assert c.ledger() is None; "
         "print([m for m in sys.modules if m == 'jax' "
         "or m.startswith(('jax.', 'jaxlib'))])"],
        cwd=REPO, capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "[]"
