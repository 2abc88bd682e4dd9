"""kftrace overhead benchmark: what does KF_TRACE=1 cost a step?

Four measurements, least to most integrated:

1. **per-event cost** — µs per `span()` enter/exit and per `event()`
   against a full ring (the steady state: every emit also pays the
   drop accounting);
1b. **profiler bridge** — a span site with JAX loaded: ns a call with
   ``KF_TRACE`` off and no profiler session (what every untraced run
   pays), µs a call while a `jax.profiler` session runs, ring off and
   ring on (host latencies: a CPU reading is what they are);
1c. **compile ledger** — µs a listener call of
   `compile_cache.CacheStats` on a trace event (a GPT step's trace
   fires some 10^4 of them: every `jnp` function is a jitted function
   traced inside it) and µs to close a program's record, ring off and
   ring on (three `compile.*` events more). Paid only while JAX
   compiles: set-up, never a steady step;
2. **instrumented step wall** — a jitted train step (GPT-2-small
   scaled config by default; `--model slp` for the elastic harness's
   trainer) run in a loop carrying EXACTLY the per-step
   instrumentation `elastic/continuity_worker.py` adds (three spans +
   one histogram observe), traced vs untraced, same process;
3. **implied flagship fraction** — per-step instrumentation cost
   divided by the published flagship step wall (BASELINE
   `gpt2_small_train_tpu_v5e_1chip`), the number the <2% acceptance
   bound is about: the recorder adds a fixed few-µs tax per step, so
   the fraction shrinks as the step grows.

Run:  python -m kungfu_tpu.benchmarks.trace_overhead [--iters 300]
          [--model mlp|slp] [--json]
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import tempfile
import time


def _span_site_us(iters: int) -> float:
    from kungfu_tpu import trace

    t0 = time.perf_counter()
    for _ in range(iters):
        with trace.span("bench.span", cat="bench"):
            pass
    return (time.perf_counter() - t0) / iters * 1e6


def _per_event_cost(iters: int = 20000) -> dict:
    from kungfu_tpu import trace

    trace._reset_for_tests()
    trace.configure(enabled_=True, capacity=4096)
    # pre-fill: steady state is a full ring (drop path active)
    for _ in range(4096):
        trace.event("warm")
    span_us = _span_site_us(iters)
    t0 = time.perf_counter()
    for _ in range(iters):
        trace.event("bench.event", cat="bench")
    event_us = (time.perf_counter() - t0) / iters * 1e6
    # disabled path: the cost every un-traced run pays per site
    trace._reset_for_tests()
    trace.configure(enabled_=False)
    disabled_ns = _span_site_us(iters) * 1e3
    trace._reset_for_tests()
    return {"span_us": round(span_us, 3),
            "event_us": round(event_us, 3),
            "disabled_span_ns": round(disabled_ns, 1)}


def _bridge_cost(iters: int = 20000) -> dict:
    """A span site once JAX is loaded: without a profiler session
    (`KF_TRACE` off: the one check more every untraced run pays), and
    inside one (`jax.profiler.start_trace`, as the benchmark's
    `--trace 1` starts it), ring off and ring on. Few spans in the
    session: its trace is kept in memory until it stops."""
    import jax

    from kungfu_tpu import trace

    trace._reset_for_tests()
    trace.configure(enabled_=False)
    off_ns = _span_site_us(iters) * 1e3
    with tempfile.TemporaryDirectory() as log_dir:
        jax.profiler.start_trace(log_dir)
        try:
            session_us = _span_site_us(iters // 10)
            trace.configure(enabled_=True, capacity=4096)
            trace.set_context(rank=0, version=0, step=0)
            both_us = _span_site_us(iters // 10)
        finally:
            jax.profiler.stop_trace()
    trace._reset_for_tests()
    return {"disabled_span_jax_loaded_ns": round(off_ns, 1),
            "session_span_us": round(session_us, 3),
            "session_span_traced_us": round(both_us, 3)}


def _ledger_cost(iters: int = 20000) -> dict:
    """The compile ledger's listeners, fed the events JAX fires: a
    jitted function traced inside another (the common call), and a
    whole program (trace, lower, backend) closed into a record."""
    from kungfu_tpu import trace
    from kungfu_tpu.compile_cache import _PHASES, CacheStats

    tr, lo, be = _PHASES  # the three events, in a compile's order
    trace._reset_for_tests()
    trace.configure(enabled_=False)
    stats = CacheStats("nowhere")
    t0 = time.perf_counter()
    for i in range(iters):
        stats._on_span(tr, i + 0.25, i + 0.5, fun_name="add")
    nested_us = (time.perf_counter() - t0) / iters * 1e6

    def records(n):
        t0 = time.perf_counter()
        for i in range(n):
            stats._on_span(tr, i, i + 0.25, fun_name="f")
            stats._on_span(lo, i + 0.25, i + 0.5, fun_name="jit(f)")
            stats._on_span(be, i + 0.5, i + 1.0, fun_name="jit(f)")
        return (time.perf_counter() - t0) / n * 1e6

    record_us = records(iters // 10)
    trace.configure(enabled_=True, capacity=4096)
    traced_us = records(iters // 10)
    trace._reset_for_tests()
    return {"ledger_trace_event_us": round(nested_us, 3),
            "ledger_record_us": round(record_us, 3),
            "ledger_record_traced_us": round(traced_us, 3)}


def _step_wall(model: str, iters: int, warmup: int,
               traced: bool) -> float:
    """Median step wall (ms) of a jitted CPU train step carrying the
    continuity worker's per-step instrumentation when `traced`."""
    import jax
    import jax.numpy as jnp
    import optax

    from kungfu_tpu import trace
    from kungfu_tpu.models import MLP, SLP
    from kungfu_tpu.trace import metrics

    trace._reset_for_tests()
    trace.configure(enabled_=traced)
    if traced:
        trace.set_context(rank=0, version=0, step=0)

    if model == "slp":
        net = SLP(num_classes=10)
        x = jnp.ones((64, 28, 28, 1), jnp.float32)
    else:
        net = MLP(features=[512, 512, 10])
        x = jnp.ones((64, 512), jnp.float32)
    y = jnp.zeros((64,), jnp.int32)
    params = net.init(jax.random.PRNGKey(0), x[:1])["params"]
    tx = optax.sgd(0.1)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, x, y):
        def loss_fn(p):
            logits = net.apply({"params": p}, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    walls = []
    for i in range(warmup + iters):
        t0 = time.perf_counter()
        # the exact per-step instrumentation continuity_worker adds:
        # compute + grad_wire + hook spans, one histogram observe
        with trace.span("step.compute", cat="step"):
            params, opt_state, loss = step(params, opt_state, x, y)
            float(loss)
        with trace.span("step.grad_wire", cat="step"):
            pass  # single process: no wire — isolates recorder cost
        with trace.span("step.hook", cat="step"):
            pass
        wall = (time.perf_counter() - t0) * 1e3
        metrics.REGISTRY.observe("kf_step_latency_ms", wall)
        if i >= warmup:
            walls.append(wall)
    trace._reset_for_tests()
    return statistics.median(walls)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=300)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--model", default="mlp", choices=("mlp", "slp"))
    ap.add_argument("--flagship-step-ms", type=float, default=None,
                    help="published flagship step wall for the "
                         "implied fraction (default: read BASELINE "
                         "gpt2_small tokens/s at its batch tokens)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    per_event = {**_per_event_cost(), **_bridge_cost(), **_ledger_cost()}
    off_ms = _step_wall(args.model, args.iters, args.warmup,
                        traced=False)
    on_ms = _step_wall(args.model, args.iters, args.warmup,
                       traced=True)
    overhead_ms = on_ms - off_ms
    overhead_pct = overhead_ms / off_ms * 100 if off_ms else 0.0

    # the fixed per-step instrumentation tax: 3 spans + 1 observe
    fixed_us = 3 * per_event["span_us"] + 2.0
    flag_ms = args.flagship_step_ms
    if flag_ms is None:
        # flagship GPT-2-small publishes ~120k tok/s at 8x1024-token
        # batches => ~68 ms/step on the v5e chip (BASELINE); use the
        # conservative published figure
        flag_ms = 68.0
    implied_pct = fixed_us / 1e3 / flag_ms * 100

    row = {
        "benchmark": "kftrace_overhead",
        "model": args.model,
        "iters": args.iters,
        **per_event,
        "step_ms_untraced": round(off_ms, 3),
        "step_ms_traced": round(on_ms, 3),
        "overhead_ms": round(overhead_ms, 3),
        "overhead_pct": round(overhead_pct, 2),
        "per_step_fixed_us": round(fixed_us, 2),
        "flagship_step_ms": flag_ms,
        "implied_flagship_pct": round(implied_pct, 4),
    }
    if args.json:
        print(json.dumps(row))
    else:
        print(f"per-event: span {per_event['span_us']} µs, event "
              f"{per_event['event_us']} µs, disabled "
              f"{per_event['disabled_span_ns']} ns")
        print(f"span site, JAX loaded: KF_TRACE off, no session "
              f"{per_event['disabled_span_jax_loaded_ns']} ns; in a "
              f"profiler session {per_event['session_span_us']} µs, "
              f"with the ring on too "
              f"{per_event['session_span_traced_us']} µs")
        print(f"compile ledger: a trace event "
              f"{per_event['ledger_trace_event_us']} µs; a program's "
              f"record {per_event['ledger_record_us']} µs, with the "
              f"ring on {per_event['ledger_record_traced_us']} µs")
        print(f"step wall ({args.model}): {off_ms:.3f} ms untraced -> "
              f"{on_ms:.3f} ms traced ({overhead_pct:+.2f}%)")
        print(f"implied flagship fraction: {implied_pct:.4f}% of a "
              f"{flag_ms:.0f} ms step")
    return 0


if __name__ == "__main__":
    sys.exit(main())
