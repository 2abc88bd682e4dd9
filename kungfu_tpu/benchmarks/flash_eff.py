"""Flash-attention kernel efficiency: achieved FLOP/s vs chip peak.

Round 5's step attribution showed the flash kernels eating 31% of the
flagship GPT step at ~20% kernel efficiency (docs/benchmarks.md) — a
number that lived only in a profiling session. This module makes it a
published, regression-guarded artifact: it times `flash_attention`
forward and fwd+bwd in isolation at a given shape, divides by the
VISIBLE-pair FLOP count (`flash_attention_flops` — masked score area
is overhead, not work), and reports achieved TFLOP/s plus efficiency
against the chip's bf16 peak where the device kind is known. The
execution plan (`flash_plan`: per-kernel scheme, block sizes, visited
vs grid blocks, and under "bwd" which backward ran — a fused
kernel (`stream_fused` window-less, `resident_fused` windowed), the
head kernel or a dq + dkv pair — with its tiles and block matmuls a
step) rides along so a published row names exactly
which kernel configuration produced it.

  python -m kungfu_tpu.benchmarks.flash_eff --seq 1024 --heads 12
  python -m kungfu_tpu.benchmarks.flash_eff --seq 16384 --window 512

`benchmarks/lm.py --attention flash` embeds the same measurement in
its meta (key `flash_kernel`), so the flagship flash row and its
kernel efficiency publish together.

`--paged` measures the serving-side paged-attention DECODE kernel
instead (`ops/paged_attn.py`). Decode attention is memory-bound, so
its roofline axis is bytes/s, not FLOP/s: the traffic model is the
block-pool bytes the table-chasing kernel actually VISITS
(`paged_traffic_bytes` — the visible blocks of each ragged row, K and
V), and the report divides that by the measured per-call time. The
point of the paged kernel is exactly that visited bytes, not
B * max_blocks * block_tokens, is what moves.

  python -m kungfu_tpu.benchmarks.flash_eff --paged --max-len 2048
"""

from __future__ import annotations

import argparse
import json
import time


def measure_flash_efficiency(batch: int = 8, seq: int = 1024,
                             heads: int = 12, head_dim: int = 64,
                             causal: bool = True, window: int | None = None,
                             dtype: str = "bfloat16", iters: int = 20,
                             warmup: int = 3,
                             block_q: int | None = None,
                             block_k: int | None = None,
                             kv_heads: int | None = None,
                             repeat_kv: bool = False):
    """Achieved flash-kernel FLOP/s at one attention shape
    (`block_q` / `block_k`: explicit tiles, for a sweep; None = the
    auto pick the models run). `kv_heads` < `heads`: grouped K/V heads,
    k and v made at that count; with `repeat_kv` the timed call repeats
    them to `heads` first, as a caller without grouped kernels would
    (the comparison of PERF.md section 6, PR 34).

    Returns a meta dict: fwd_ms / fwdbwd_ms (per call), achieved
    TFLOP/s for both, `efficiency_vs_bf16_peak` (fwd+bwd — the number
    the training step actually sees; None off known TPU kinds), and
    the `flash_plan` that ran."""
    import jax
    import jax.numpy as jnp

    from kungfu_tpu.benchmarks.lm import _BF16_PEAK_BY_KIND
    from kungfu_tpu.ops.flash import (flash_attention,
                                      flash_attention_flops, flash_plan)

    platform = jax.devices()[0].platform
    if platform == "cpu":  # interpret-mode smoke: keep the shape tiny
        batch, seq, heads = min(batch, 2), min(seq, 256), min(heads, 4)
        iters, warmup = min(iters, 2), min(warmup, 1)
    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    kv_heads = kv_heads or heads
    q, k, v = (jax.random.normal(kk, (batch, seq, n, head_dim), dt)
               for kk, n in zip(ks, (heads, kv_heads, kv_heads)))

    def attend(q, k, v):
        if repeat_kv:
            k, v = (jnp.repeat(x, heads // kv_heads, axis=2)
                    for x in (k, v))
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k)

    fwd = jax.jit(attend)
    grad = jax.jit(jax.grad(
        lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))

    def timed(fn):
        """Slope-timed per-call seconds: (t(k_hi) - t(k_lo)) over the
        call-count delta, the round-5 roofline discipline — the single
        end-of-loop fence and the dispatch ramp are a constant that
        cancels in the difference instead of deflating the published
        efficiency (the round-4 artifact `measure_achieved_bandwidth`'s
        docstring retired)."""
        k_lo, k_hi = max(iters, 1), 3 * max(iters, 1)

        def run(n):
            t0 = time.perf_counter()
            for _ in range(n):
                out = fn(q, k, v)
            jax.block_until_ready(out)
            return time.perf_counter() - t0

        for _ in range(max(warmup, 1)):
            out = fn(q, k, v)
        jax.block_until_ready(out)
        run(k_lo)  # settle caches/dispatch before the measured pair
        t_lo = min(run(k_lo) for _ in range(2))
        t_hi = min(run(k_hi) for _ in range(2))
        slope = (t_hi - t_lo) / (k_hi - k_lo)
        # a loaded host can time the short loop longer than the long
        # one (CPU smoke runs of a few calls): the mean call then
        return slope if slope > 0 else t_hi / k_hi

    t_fwd = timed(fwd)
    t_both = timed(grad)
    f_fwd = flash_attention_flops(batch, seq, heads, head_dim, causal,
                                  window)
    f_both = flash_attention_flops(batch, seq, heads, head_dim, causal,
                                   window, backward=True)
    # the headline key names the bf16 peak, so only bf16 runs report
    # it — an f32 run divided by the bf16 peak could never approach 1
    # and would not be comparable to the published bf16 rows
    peak = (_BF16_PEAK_BY_KIND.get(jax.devices()[0].device_kind)
            if dtype == "bfloat16" else None)
    meta = {
        "platform": platform, "batch": batch, "seq": seq,
        "heads": heads, "kv_heads": kv_heads, "repeat_kv": repeat_kv,
        "head_dim": head_dim, "causal": causal,
        "window": window, "dtype": dtype, "iters": iters,
        "fwd_ms": round(t_fwd * 1000, 3),
        "fwdbwd_ms": round(t_both * 1000, 3),
        "fwd_tflops": round(f_fwd / t_fwd / 1e12, 3),
        "fwdbwd_tflops": round(f_both / t_both / 1e12, 3),
        # fwd+bwd is what a train step pays, so it is THE efficiency
        # number. On a v5e at the defaults, 2026-10-01: 0.096 before
        # PR 25's head kernels, 0.164 with them (the [B*H, T, D]
        # transposes round the kernels included); d = 64 halves the
        # MXU's rate and recomputed matmuls are not counted
        "efficiency_vs_bf16_peak": (
            round(f_both / t_both / peak, 4) if peak else None),
        "device_kind": jax.devices()[0].device_kind,
        "plan": flash_plan(seq, head_dim, dtype=dt, causal=causal,
                           window=window, block_q=block_q,
                           block_k=block_k,
                           q_per_kv=1 if repeat_kv
                           else heads // kv_heads),
    }
    return meta


def measure_paged_bandwidth(batch: int = 8, max_len: int = 2048,
                            block_tokens: int = 16, heads: int = 12,
                            head_dim: int = 64,
                            dtype: str = "bfloat16", iters: int = 20,
                            warmup: int = 3):
    """Achieved bandwidth of the paged-attention decode kernel at one
    serving shape.

    Traffic = `paged_traffic_bytes` over the (ragged) batch lengths:
    the visible K/V pool blocks each row's table chase actually DMAs.
    Reports per-call ms, visited bytes, achieved GB/s, and the
    visited fraction of the whole pool (the saving over a dense
    gather) plus the `paged_plan` that ran."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from kungfu_tpu.ops.paged_attn import (paged_attention, paged_plan,
                                           paged_traffic_bytes)

    platform = jax.devices()[0].platform
    if platform == "cpu":  # interpret-mode smoke: keep the pool tiny
        batch, max_len, heads = min(batch, 2), min(max_len, 64), \
            min(heads, 4)
        block_tokens = min(block_tokens, 8)
        iters, warmup = min(iters, 2), min(warmup, 1)
    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    bt = block_tokens
    max_blocks = -(-max_len // bt)
    plan = paged_plan(max_blocks, bt, heads, head_dim, dtype=dt)
    meta = {
        "platform": platform, "batch": batch, "max_len": max_len,
        "block_tokens": bt, "heads": heads, "head_dim": head_dim,
        "dtype": dtype, "iters": iters, "plan": plan,
        "device_kind": jax.devices()[0].device_kind,
    }
    if plan["scheme"] == "functional":
        meta["skipped"] = ("paged_plan chose the functional fallback "
                           "at this shape — nothing to time")
        return meta
    num_pool = 1 + batch * max_blocks      # + the scratch block 0
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (batch, heads, head_dim), dt)
    k_pool = jax.random.normal(kk, (num_pool, bt, heads, head_dim), dt)
    v_pool = jax.random.normal(kv, (num_pool, bt, heads, head_dim), dt)
    # ragged lengths (the traffic model's point); disjoint tables
    rng = np.random.default_rng(0)
    lengths = rng.integers(max_len // 2, max_len - 1,
                           size=batch).astype(np.int32)
    tables = (1 + np.arange(batch * max_blocks, dtype=np.int32)
              .reshape(batch, max_blocks))
    fn = jax.jit(lambda q, kp, vp, tb, ln: paged_attention(
        q, kp, vp, tb, ln, scheme=plan["scheme"]))
    args = (q, k_pool, v_pool, jnp.asarray(tables),
            jnp.asarray(lengths))

    # the same slope-timing discipline as the flash measurement: the
    # end-of-loop fence is a constant that cancels in the difference
    k_lo, k_hi = max(iters, 1), 3 * max(iters, 1)

    def run(n):
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    for _ in range(max(warmup, 1)):
        out = fn(*args)
    jax.block_until_ready(out)
    run(k_lo)
    t_lo = min(run(k_lo) for _ in range(2))
    t_hi = min(run(k_hi) for _ in range(2))
    t = max((t_hi - t_lo) / (k_hi - k_lo), 1e-9)

    isz = jnp.dtype(dt).itemsize
    visited = paged_traffic_bytes(lengths, bt, heads, head_dim, isz)
    pool_bytes = 2 * (num_pool - 1) * bt * heads * head_dim * isz
    meta.update({
        "lengths": [int(n) for n in lengths],
        "decode_ms": round(t * 1000, 3),
        "visited_bytes": int(visited),
        "visited_fraction_of_pool": round(visited / pool_bytes, 4),
        "achieved_gbps": round(visited / t / 1e9, 3),
    })
    return meta


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="grouped K/V heads (default: --heads)")
    ap.add_argument("--repeat-kv", action="store_true",
                    help="repeat K/V to --heads before the call")
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--no-causal", action="store_true")
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("bfloat16", "float32"))
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--block-q", type=int, default=None,
                    help="explicit flash tiles (default: the auto pick)")
    ap.add_argument("--block-k", type=int, default=None)
    ap.add_argument("--paged", action="store_true",
                    help="measure the paged-attention decode kernel's "
                         "achieved bandwidth instead")
    ap.add_argument("--max-len", type=int, default=2048,
                    help="--paged: per-sequence pool reservation")
    ap.add_argument("--block-tokens", type=int, default=16,
                    help="--paged: KV block size in tokens")
    args = ap.parse_args(argv)
    if args.paged:
        meta = measure_paged_bandwidth(
            args.batch, args.max_len, args.block_tokens, args.heads,
            args.head_dim, dtype=args.dtype, iters=args.iters)
        print(json.dumps({
            "metric": "paged_decode_achieved_gbps",
            "value": meta.get("achieved_gbps"),
            "unit": "GB/s of visited block-pool bytes",
            "details": meta,
        }))
        return 0
    meta = measure_flash_efficiency(
        args.batch, args.seq, args.heads, args.head_dim,
        causal=not args.no_causal, window=args.window,
        dtype=args.dtype, iters=args.iters, block_q=args.block_q,
        block_k=args.block_k, kv_heads=args.kv_heads,
        repeat_kv=args.repeat_kv)
    print(json.dumps({
        "metric": "flash_kernel_efficiency_vs_bf16_peak",
        "value": meta["efficiency_vs_bf16_peak"],
        "unit": "fraction_of_peak",
        "details": meta,
    }))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
