"""The six compile metrics through their own files' `args`, over a
synthetic ledger fed the events JAX fires (`kungfu_tpu/compile_cache.py`
has the listeners): a `make`, a one-op program, the train step compiled
twice (its second compile without a trace: jit still held the jaxpr),
and a late compile of `verify`'s that is not set-up."""

import os

import pytest

from benchmark.runners.train import read_metrics
from kungfu_tpu import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SIX = ["step_trace_s", "step_lower_s", "step_backend_s", "step_programs",
       "setup_compile_s", "setup_cache_misses"]
TRACE, LOWER, BACKEND = (
    f"/jax/core/compile/{e}_duration"
    for e in ("jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile"))

# (fun, trace seconds or None, lower seconds, backend seconds, cache:
# `skipped` is looked up, compiled and not kept, which is no miss)
PROGRAMS = [
    ("make", 2.0, 1.0, 8.0, "miss"),
    ("convert_element_type", 0.25, 0.25, 0.5, "skipped"),
    ("step", 4.0, 1.5, 30.0, "miss"),
    ("step", None, 1.0, 2.0, "hit"),
    ("of_sub", 3.0, 1.0, 20.0, "miss"),     # verify's: after the window
]


@pytest.fixture
def ledger(monkeypatch):
    stats = compile_cache.CacheStats("nowhere")
    at = 100.0
    for fun, trace_s, lower_s, backend_s, cache in PROGRAMS:
        if trace_s is not None:
            # a function traced inside: its time is the outer's
            stats._on_span(TRACE, at + 0.1, at + 0.2, fun_name="add")
            stats._on_span(TRACE, at, at + trace_s, fun_name=fun)
            at += trace_s
        stats._on_span(LOWER, at, at + lower_s, fun_name=f"jit({fun})")
        at += lower_s
        stats._on_event("/jax/compilation_cache/compile_requests_use_cache")
        if cache == "hit":
            stats._on_event("/jax/compilation_cache/cache_hits")
            stats._on_duration(
                "/jax/compilation_cache/cache_retrieval_time_sec",
                backend_s / 2)
        elif cache == "miss":     # fired where the entry is written
            stats._on_event("/jax/compilation_cache/cache_misses")
        stats._on_span(BACKEND, at, at + backend_s, fun_name=f"jit({fun})")
        at += backend_s + 1.0
    monkeypatch.setattr(compile_cache, "ledger", lambda: stats)
    return stats


def test_the_six_metrics_on_a_synthetic_ledger(ledger):
    got = read_metrics(SIX, None, {}, ROOT)
    assert got == {
        "step_trace_s": 4.0,             # one trace for two programs
        "step_lower_s": 2.5,
        "step_backend_s": 32.0,
        "step_programs": 2,
        "setup_compile_s": 11.0 + 1.0 + 35.5 + 3.0,   # verify's cut
        "setup_cache_misses": 2,         # make and the step's first
    }
    assert (got["step_trace_s"] + got["step_lower_s"]
            + got["step_backend_s"]) <= got["setup_compile_s"]
    assert ledger.as_dict()["programs"] == 5     # the ledger keeps all
    assert ledger.as_dict()["nested_traces"] == 4


def test_a_ledger_that_never_saw_the_step_reads_zero(monkeypatch):
    # set-up ends at the step's last compile: with none, nothing is
    # set-up's, and the metrics read 0 rather than the whole process
    stats = compile_cache.CacheStats("nowhere")
    stats._on_span(BACKEND, 1.0, 9.0, fun_name="jit(make)")
    monkeypatch.setattr(compile_cache, "ledger", lambda: stats)
    got = read_metrics(SIX, None, {}, ROOT)
    assert got == dict.fromkeys(SIX, 0)


@pytest.mark.parametrize("ledger_fn", [None, lambda: None],
                         ids=["no-ledger-function", "never-enabled"])
def test_a_program_without_a_ledger_reads_nothing(monkeypatch, capsys,
                                                  ledger_fn):
    # the parent of the PR that brought the ledger: the metrics are
    # left out and nothing raises
    if ledger_fn is None:
        monkeypatch.delattr(compile_cache, "ledger")
    else:
        monkeypatch.setattr(compile_cache, "ledger", ledger_fn)
    assert read_metrics(SIX, None, {}, ROOT) == {}
    assert "step_trace_s: nothing to read" in capsys.readouterr().err
