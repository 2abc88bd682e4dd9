"""ResNet-50 step roofline, reconciled from the compiled HLO.

Round 3's docs claimed ~880 GB/s of apparent HBM demand against an
~819 GB/s paper peak — demand at 107% of peak means the hand estimate
was off. This module replaces it with numbers that can close:

1. **Per-op traffic table from the optimized HLO** (not aggregate cost
   analysis): walk the entry computation's instructions, charge each
   fusion/custom-call its operand + output bytes (operands deduped
   within an instruction — one HBM read feeds every in-fusion use),
   and bucket by kind (convolution, BN/reduce, elementwise, copy).
   Parameters and constants are charged on read like any operand.
2. **Achieved-bandwidth suite**: streaming kernels over ~0.5 GiB in
   several access patterns (f32 add, bf16 add, bf16 copy, bf16 4-way
   fan-in) measure what this chip actually sustains through the same
   jit/dispatch path. The max over patterns is the honest denominator
   for "at roofline" — a single f32 add underestimates what a step
   full of concurrent bf16 DMA streams can pull.

Prints the table plus ONE JSON line with the reconciliation:
demand GB/step, step ms, implied GB/s, achieved GB/s by pattern, the
best-pattern fraction, and a `reconciles` verdict.

  python -m kungfu_tpu.benchmarks.roofline            # full (TPU)
  python -m kungfu_tpu.benchmarks.roofline --no-bench # HLO table only
"""

from __future__ import annotations

import argparse
import json
import re
import time

_SHAPE = re.compile(r"([a-z]+[0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8,
}


def shape_bytes(type_str: str) -> int:
    """Total bytes of an HLO type string, tuples included:
    '(bf16[8,128]{1,0}, f32[64]{0})' -> sum of parts."""
    total = 0
    for dtype, dims in _SHAPE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(\([^)]*\)|\S+)\s+"
    r"([\w\-]+)\((.*)$")
_OPERAND = re.compile(r"%[\w.\-]+")


def parse_entry_traffic(hlo_text: str):
    """[(name, opcode, kind, out_bytes, in_bytes)] for the ENTRY
    computation's instructions (post-fusion: each one is an HBM
    round-trip; fusion internals live in VMEM/registers)."""
    # first pass: every defined value's type, module-wide (operands of
    # entry instructions are defined in the entry computation)
    types = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            types[m.group(1)] = m.group(2)

    rows = []
    in_entry = False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            in_entry = True
            continue
        if in_entry and line.startswith("}"):
            break
        if not in_entry:
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, type_str, opcode, rest = m.groups()
        if opcode in ("parameter", "constant", "tuple",
                      "get-tuple-element", "bitcast"):
            continue  # no data movement of their own
        # operand list ends at the first unbalanced ')': good enough to
        # find the %refs, which cannot appear in attributes after it
        args = rest.split("), ")[0] if "), " in rest else rest
        operands = _OPERAND.findall(args)
        in_bytes = sum(shape_bytes(types.get(o, ""))
                       for o in dict.fromkeys(operands))
        out_bytes = shape_bytes(type_str)
        low = line.lower()
        if "convolution" in low or "conv" in name:
            kind = "convolution"
        elif opcode == "fusion" and ("reduce" in low or "rsqrt" in low):
            kind = "bn_reduce"
        elif opcode in ("copy", "copy-start", "copy-done"):
            kind = "copy"
        elif opcode == "custom-call":
            kind = "custom_call"
        elif opcode == "all-reduce" or "all-reduce" in low:
            kind = "collective"
        else:
            kind = "elementwise"
        rows.append((name, opcode, kind, out_bytes, in_bytes))
    return rows


def build_resnet_step():
    import jax
    import jax.numpy as jnp
    import optax

    from kungfu_tpu.models import ResNet50
    from kungfu_tpu.optimizers import sync_sgd
    from kungfu_tpu.parallel import (build_train_step_with_state,
                                     data_mesh, init_worker_state,
                                     replicate_to_workers, shard_batch)

    n = jax.device_count()
    platform = jax.devices()[0].platform
    batch = 128 if platform != "cpu" else 8
    size = 224 if platform != "cpu" else 64
    mesh = data_mesh(n)
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                     space_to_depth=True)
    x = jnp.ones((batch * n, size, size, 3), jnp.float32)
    y = jnp.zeros((batch * n,), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), x[:2], train=True)

    def loss_fn(params, batch_stats, batch):
        logits, updated = model.apply(
            {"params": params, "batch_stats": batch_stats},
            batch["x"], train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()
        return loss, updated["batch_stats"]

    tx = sync_sgd(optax.sgd(0.1, momentum=0.9))
    params_s = replicate_to_workers(variables["params"], mesh)
    stats_s = replicate_to_workers(variables["batch_stats"], mesh)
    opt_s = init_worker_state(tx, params_s, mesh)
    step = build_train_step_with_state(loss_fn, tx, mesh)
    batch_s = shard_batch({"x": x, "y": y}, mesh)
    return step, (params_s, stats_s, opt_s, batch_s), platform


def measure_achieved_bandwidth(gib: float = 0.5, iters: int = 20):
    """Sustained HBM GB/s of a pure f32 streaming add (2 reads + 1
    write).

    Slope-timed through `measure_bandwidth_suite`: t(k_hi) - t(k_lo)
    over the iteration delta, which by construction cancels every
    fixed per-call cost (dispatch, the closing fence). Round-4 figures
    used a single fenced run that folded that fixed cost into the
    rate, so they UNDERSTATE bandwidth and are not comparable to what
    this returns — the published round-5 reconciliation
    (docs/benchmarks.md) retired them."""
    return measure_bandwidth_suite(gib, iters, patterns=("f32_add",)
                                   )["f32_add"]


def measure_bandwidth_suite(gib: float = 0.5, iters: int = 20,
                            patterns=("f32_add", "bf16_add", "bf16_copy",
                                      "pallas_stream")):
    """GB/s by access pattern, slope-timed (t(k_hi) - t(k_lo) over the
    iteration delta cancels the fixed per-call cost, which a single
    fenced run folds into the rate).

    The elementwise patterns (f32/bf16 add, bf16 copy) measure what an
    XLA fusion loop sustains; `pallas_stream` measures what BLOCK-DMA
    streaming sustains (a Pallas kernel negating [block, 1024] tiles —
    pure DMA in/out with one VPU op). Round-5 profiling showed real
    kernels (fused-CE d-kernel, big adam fusions) streaming at
    ~650-715 GB/s while the chained f32 add plateaus near ~280: the
    elementwise loops are VPU-issue-bound, not DMA-bound, so the
    honest "delivered bandwidth" ceiling for a roofline claim is the
    max over patterns INCLUDING the Pallas stream."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    k_lo, k_hi = 2, max(iters, 20) * 3

    def timed(make_run, *args, nbytes_per_iter, reps=3):
        run = jax.jit(make_run)
        for k in (k_lo, k_hi):
            float(run(*args, k).reshape(-1)[0].astype(jnp.float32))
        pers = []
        for _ in range(reps):
            t0 = time.perf_counter()
            float(run(*args, k_lo).reshape(-1)[0].astype(jnp.float32))
            tl = time.perf_counter() - t0
            t0 = time.perf_counter()
            float(run(*args, k_hi).reshape(-1)[0].astype(jnp.float32))
            th = time.perf_counter() - t0
            pers.append((th - tl) / (k_hi - k_lo))
        pers.sort()
        return nbytes_per_iter / pers[len(pers) // 2] / 1e9

    results = {}
    if "f32_add" in patterns:
        n = int(gib * (1 << 30) / 4)
        x = jnp.arange(n, dtype=jnp.float32)
        y = jnp.ones((n,), jnp.float32)
        results["f32_add"] = timed(
            lambda x, y, k: lax.fori_loop(0, k, lambda i, z: z + y, x),
            x, y, nbytes_per_iter=3 * n * 4)
    n = int(gib * (1 << 30) / 2)
    if "bf16_add" in patterns:
        xb = jnp.ones((n,), jnp.bfloat16)
        yb = jnp.ones((n,), jnp.bfloat16) * 1.0078125  # 1+2^-7: exact
        results["bf16_add"] = timed(
            lambda x, y, k: lax.fori_loop(0, k, lambda i, z: z + y, x),
            xb, yb, nbytes_per_iter=3 * n * 2)
    if "bf16_copy" in patterns:
        # z = -z: reads and rewrites every element with no second
        # operand — 1r + 1w, the lightest VPU load XLA won't fold away
        xc = jnp.ones((n,), jnp.bfloat16)
        results["bf16_copy"] = timed(
            lambda x, k: lax.fori_loop(0, k, lambda i, z: -z, x),
            xc, nbytes_per_iter=2 * n * 2)
    if "pallas_stream" in patterns:
        rows = (n // 1024) // 512 * 512
        xp = jnp.ones((rows, 1024), jnp.bfloat16)

        def neg_kernel(x_ref, o_ref):
            o_ref[:] = -x_ref[:]

        stream = pl.pallas_call(
            neg_kernel,
            grid=(rows // 512,),
            in_specs=[pl.BlockSpec((512, 1024), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((512, 1024), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((rows, 1024), jnp.bfloat16),
            interpret=jax.default_backend() != "tpu",
        )
        results["pallas_stream"] = timed(
            lambda x, k: lax.fori_loop(0, k, lambda i, z: stream(z), x),
            xp, nbytes_per_iter=2 * rows * 1024 * 2)
    return {k: round(v, 1) for k, v in results.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--no-bench", action="store_true",
                    help="skip device runs; HLO table only")
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args(argv)
    import jax

    step, step_args, platform = build_resnet_step()
    compiled = jax.jit(step).lower(*step_args).compile()
    hlo = compiled.as_text()
    rows = parse_entry_traffic(hlo)

    by_kind = {}
    for _, _, kind, out_b, in_b in rows:
        agg = by_kind.setdefault(kind, [0, 0, 0])
        agg[0] += 1
        agg[1] += out_b
        agg[2] += in_b
    total_gb = sum(v[1] + v[2] for v in by_kind.values()) / 1e9

    print(f"{'kind':<14} {'ops':>5} {'write GB':>9} {'read GB':>9}")
    for kind, (cnt, ob, ib) in sorted(by_kind.items(),
                                      key=lambda kv: -(kv[1][1]
                                                       + kv[1][2])):
        print(f"{kind:<14} {cnt:>5} {ob / 1e9:>9.2f} {ib / 1e9:>9.2f}")
    biggest = sorted(rows, key=lambda r: -(r[3] + r[4]))[:args.top]
    print("\nheaviest instructions:")
    for name, opcode, kind, ob, ib in biggest:
        print(f"  {(ob + ib) / 1e6:>8.1f} MB  {kind:<12} {name}")

    result = {"metric": "resnet50_hlo_traffic_gb_per_step",
              "value": round(total_gb, 2), "unit": "GB/step",
              "platform": platform}
    if not args.no_bench and platform != "cpu":
        suite = measure_bandwidth_suite()
        achieved = suite["f32_add"]
        best = max(suite.values())
        iters = 20
        p, s, o, loss = step(*step_args)          # compile
        for _ in range(2):                        # warm (match bench.py)
            p, s, o, loss = step(p, s, o, step_args[3])
        float(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            p, s, o, loss = step(p, s, o, step_args[3])
        # one fence through a scalar readback at the end: the chained
        # donated-buffer dependency serializes the steps
        float(loss)
        dt = (time.perf_counter() - t0) / iters
        implied = total_gb / dt
        result.update({
            "step_ms": round(dt * 1000, 2),
            "implied_gb_per_s": round(implied, 1),
            "achieved_streaming_gb_per_s": round(achieved, 1),
            "achieved_by_pattern_gb_per_s": suite,
            "best_achieved_gb_per_s": round(best, 1),
            "fraction_of_best_achieved": round(implied / best, 3),
            "reconciles": bool(implied <= best * 1.05),
        })
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
