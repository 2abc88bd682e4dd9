"""Language-model training throughput (tokens/sec), dp x tp composed.

The image trio (`benchmarks/throughput.py`) mirrors the reference's
headline plot; this module covers the transformer-LM axis the framework
adds: GPT under one jitted train step with Megatron-sharded weights.

  python -m kungfu_tpu.benchmarks.lm                 # gpt-small, 1 chip
  python -m kungfu_tpu.benchmarks.lm --seq 2048 --attention flash
  python -m kungfu_tpu.benchmarks.lm --tp 4          # 4-way tensor split

Prints one JSON line: tokens/sec (global), ms/step, config.
"""

from __future__ import annotations

import argparse
import functools
import json
import time

# the canonical GPT size table lives with the serving tier
# (kungfu_tpu/serve/engine.py) — one model/params setup serves both
# the decode benchmark and the decode tier, so they cannot drift;
# re-exported here for the historical import path
from kungfu_tpu.serve.engine import SIZES

# Peak bf16 FLOP/s per chip, keyed by jax device_kind. MFU is only
# reported for kinds listed here — a hard-coded peak on an unknown
# accelerator would print a wrong-by-construction number.
_BF16_PEAK_BY_KIND = {
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5e": 197e12,      # alternate kind string some stacks report
}


def _device_meta():
    """The device a number was taken on — in every row this module
    prints, so that none can be read without it."""
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "devices": jax.device_count()}


def _train_mfu(cfg, tokens_per_sec, seq, n_chips):
    """Model FLOPs utilization of a train step vs the chip's bf16 peak
    across `n_chips` chips; None when the peak for this device kind is
    unknown (CPU, or a TPU generation not in `_BF16_PEAK_BY_KIND`).

    Standard accounting (PaLM appendix B): 6 FLOPs per ACTIVE matmul
    parameter per token (fwd+bwd) — attention projections, the MLP (one
    expert's worth under Switch top-1 routing, however many experts
    exist), the lm_head — plus the causal attention term
    6 * L * h * T per token. Embedding lookups are not matmuls and are
    not counted."""
    import jax

    peak_per_chip = _BF16_PEAK_BY_KIND.get(
        jax.devices()[0].device_kind)
    if peak_per_chip is None:
        return None
    h, inter = cfg.hidden_size, cfg.intermediate_size
    per_layer = 4 * h * h + 2 * h * inter  # qkvo + one expert's MLP
    if cfg.num_experts:
        per_layer += h * cfg.num_experts   # router projection
    n_mat = cfg.num_layers * per_layer + h * cfg.vocab_size
    flops_per_tok = 6 * n_mat + 6 * cfg.num_layers * h * seq
    peak = peak_per_chip * max(n_chips, 1)
    return round(tokens_per_sec * flops_per_tok / peak, 4)


def measure_lm_rate(size: str = "small", batch: int = 8, seq: int = 1024,
                    tp: int = 1, attention: str = "local",
                    iters: int = 10, warmup: int = 2, experts: int = 0,
                    moe_group: int = 0, moe_bf16: bool = False,
                    remat: bool = False, ce_variant: str = "residual"):
    """Tokens/sec of LM training. Returns (tokens_per_sec, meta).

    `experts` > 0 swaps the dense FFN for the Switch MoE (global expert
    stacks, GSPMD-sharded over the model axis) and trains through
    `gpt_loss_with_aux` so the measured step includes the router's
    load-balance + z losses — the real trainable-MoE path, not a
    routing demo.
    """
    import numpy as np

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, NamedSharding

    from kungfu_tpu.models import (GPTConfig, GPTLM, gpt_fused_loss,
                                   gpt_loss_with_aux)
    from kungfu_tpu.parallel import (build_gspmd_train_step,
                                     gpt_moe_rules, gpt_tp_rules,
                                     shard_params)
    from kungfu_tpu.parallel.rules import stacked

    n = jax.device_count()
    platform = jax.devices()[0].platform
    if platform == "cpu":  # smoke path
        size, batch, seq = "tiny", 2, 128
        iters, warmup = min(iters, 3), min(warmup, 1)
    if n % tp:
        raise SystemExit(f"--tp {tp} must divide device count {n}")
    hidden, layers, heads, inter = SIZES[size]
    cfg = GPTConfig(vocab_size=50257, hidden_size=hidden,
                    num_layers=layers, num_heads=heads,
                    intermediate_size=inter,
                    max_position=max(1024, seq), dtype=jnp.bfloat16,
                    attention=attention, num_experts=experts,
                    moe_group_size=moe_group,
                    moe_param_dtype=jnp.bfloat16 if moe_bf16 else None,
                    remat=remat)
    model = GPTLM(cfg)

    d_data = n // tp
    mesh = Mesh(np.array(jax.devices()).reshape(d_data, tp),
                ("data", "model"))
    # non-degenerate synthetic corpus: seeded uniform over the vocab.
    # The old all-zeros tokens made the published MoE row an
    # untrained-router artifact (identical tokens all route to one
    # expert -> 78% dropped at capacity, VERDICT round 5); dense-path
    # timing is token-value-independent, so every row keeps comparing.
    tokens = jax.random.randint(jax.random.PRNGKey(17),
                                (batch * d_data, seq), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:1, :seq])["params"]
    rules = gpt_moe_rules() if experts else gpt_tp_rules()
    params = shard_params(jax.device_get(params), mesh, rules)
    tokens = jax.device_put(tokens, NamedSharding(mesh, stacked("data")))

    # bf16 expert storage: upcast gradients to f32 BEFORE adam so both
    # moments stay f32 (optax moments follow the update dtype; a bf16
    # nu freezes once 0.001*g^2 rounds below bf16's 8 mantissa bits).
    # optax.apply_updates casts the final update back to each param's
    # dtype, so the params themselves stay bf16.
    upcast = optax.stateless(
        lambda updates, _: jax.tree_util.tree_map(
            lambda u: u.astype(jnp.float32), updates))
    # per-leaf adamw: a whole-tree flat-buffer variant was profiled
    # and REGRESSED the step 108.5 -> 131.1 ms on v5e
    # (concat lowers to a serial DUS loop + per-leaf relayouts); see
    # docs/benchmarks.md round-5 attribution
    tx = optax.chain(upcast, optax.adamw(1e-4))
    # init the moments from f32-cast shapes: zeros_like(bf16 params)
    # would give bf16 mu/nu avals that flip to f32 after the first
    # (upcast) update and force a retrace inside the timed loop
    opt = tx.init(jax.tree_util.tree_map(
        lambda p: p.astype(jnp.float32), params))
    residual = ce_variant == "residual"
    # ce variant: "residual" (default, measured faster — 113.2k vs
    # 105.5k tok/s at small-b12) or "recompute" (no [N, V] array at
    # all; the long-context memory-bound variant). Every branch below
    # runs the fused head+CE — sharded meshes vocab-shard it through
    # parallel/vocab_ce.py (the old `fused=(n == 1)` guard silently
    # degraded every multi-chip config to the unfused f32-logits head).
    if experts:
        # multi-chip MoE: GSPMD shards the Switch expert stacks over
        # "model" while the vocab-sharded head runs via shard_map —
        # the two compose inside one jitted step
        step = build_gspmd_train_step(
            lambda p, t: gpt_loss_with_aux(
                model, p, t, fused=True,
                mesh=mesh if n > 1 else None),
            tx, has_aux=True)
    elif n == 1:
        step = build_gspmd_train_step(
            lambda p, t: gpt_fused_loss(model, p, t, residual=residual),
            tx)
    elif tp == 1:
        # multi-chip dp: shard_map keeps the fused Pallas kernel inside
        # the per-shard region (the GSPMD partitioner has no rule for
        # pallas_call and would all-gather its operands)
        from kungfu_tpu.parallel import build_dp_replicated_train_step

        step = build_dp_replicated_train_step(
            lambda p, t: gpt_fused_loss(model, p, t, residual=residual),
            tx, mesh)
    else:
        # tp > 1: vocab-sharded fused CE — each device owns a vocab
        # shard of the lm_head, runs the Pallas kernel on it, and a
        # psum-logsumexp combine recovers the exact loss (Megatron
        # vocab-parallel loss, parallel/vocab_ce.py)
        step = build_gspmd_train_step(
            lambda p, t: gpt_fused_loss(
                model, p, t, residual=residual, mesh=mesh), tx)

    from kungfu_tpu.compile_cache import timed_compile

    compile_s, compiled = timed_compile(step, params, opt, tokens)
    # Pallas kernels in the compiled step. Flash and the fused head
    # both fall back to plain XLA without a word where a shape does
    # not tile; on a TPU a zero here says the row timed the fallbacks
    # (interpret mode on a CPU inlines the kernels: always zero there)
    n_kernels = compiled.as_text().count("tpu_custom_call")
    del compiled  # read, never run: the loops call the jitted `step`

    def one(params, opt, tokens):
        out = step(params, opt, tokens)
        return out[0], out[1], out[2], (out[3] if len(out) > 3 else None)

    losses = []
    for _ in range(max(warmup, 1)):
        params, opt, loss, aux = one(params, opt, tokens)
        losses.append(loss)
    float(loss)  # fence: async dispatch must drain before timing
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt, loss, aux = one(params, opt, tokens)
        losses.append(loss)
    float(loss)
    dt = (time.perf_counter() - t0) / iters
    global_tokens = batch * d_data * seq
    meta = {
        **_device_meta(), "tp": tp, "size": size,
        "per_data_batch": batch, "seq": seq, "attention": attention,
        "step_time_ms": round(dt * 1000, 2), "iters": iters,
        # key name is historical; the denominator is the peak for
        # device_kind (non-v5e kinds report None until listed)
        "mfu_vs_v5e_bf16_peak": _train_mfu(
            cfg, global_tokens / dt, seq, n),
        "compile_s": round(compile_s, 2),
        "pallas_kernels": n_kernels,
        # the same batch every step, warmup included: a working
        # optimizer makes this sequence fall
        "losses": [round(float(x), 4) for x in losses],
    }
    if attention == "flash":
        # per-kernel achieved-FLOPs efficiency of the flash fwd+bwd at
        # THIS row's attention shape (isolated micro-measure, cheap
        # next to the training loop) — publishes the step-attribution
        # "~20% kernel efficiency" number with the row it explains,
        # plus the block/scheme plan that produced it (flash_eff.py).
        from kungfu_tpu.benchmarks.flash_eff import (
            measure_flash_efficiency)

        meta["flash_kernel"] = measure_flash_efficiency(
            batch=batch, seq=seq, heads=heads,
            head_dim=hidden // heads, causal=True, dtype="bfloat16",
            iters=min(iters, 10), warmup=2)
    if remat:
        meta["remat"] = True
    # every branch runs the fused head (see step selection); the dense
    # branches plumb --ce-variant, MoE keeps the default residual
    # backward. Refuse a non-default --ce-variant where it is not
    # plumbed instead of mislabeling the row.
    variant_plumbed = not experts
    if ce_variant != "residual" and not variant_plumbed:
        raise SystemExit(
            "--ce-variant selects the fused-CE backward, but the MoE "
            "path does not plumb it; this configuration would run the "
            "default backward and the row would be mislabeled")
    meta["fused_ce"] = ce_variant if variant_plumbed else "residual"
    if (tp > 1) or (experts and n > 1):
        # the head is vocab-sharded over the model axis with the
        # psum-logsumexp combine (parallel/vocab_ce.py)
        meta["fused_ce_sharding"] = f"vocab/{tp}"
    if experts:
        from kungfu_tpu.models.gpt import effective_moe_group

        meta["num_experts"] = experts
        # the EFFECTIVE group MoEMLP runs, not the requested one
        meta["moe_group_size"] = effective_moe_group(
            cfg, batch * d_data, seq)
        meta["loss_includes_router_aux"] = True
        meta["moe_param_dtype"] = "bfloat16" if moe_bf16 else "float32"
        if aux is not None and "dropped_frac" in aux:
            # capacity-overflow tokens dropped in the LAST measured
            # step — the quality cost of this cf/group configuration
            meta["moe_dropped_frac"] = round(
                float(aux["dropped_frac"]), 4)
    return global_tokens / dt, meta


def measure_pp_rate(size: str = "small", batch: int = 8, seq: int = 1024,
                    pp: int = 1, microbatches: int = 8, iters: int = 10,
                    warmup: int = 2):
    """Tokens/sec of GPT training under the 1F1B pipeline schedule.

    With pp devices each holding layers/pp blocks; at pp=1 this measures
    the schedule's overhead against the plain GSPMD step (the 1F1B loop
    is then gradient accumulation over `microbatches`), which is the
    honest single-chip row — multi-stage speedup needs >= 2 devices.
    """
    import numpy as np

    import jax
    import jax.numpy as jnp
    import optax

    from jax import shard_map
    from jax.sharding import Mesh

    from kungfu_tpu.models import GPTConfig, GPTLM, stack_gpt_blocks
    from kungfu_tpu.models.gpt import gpt_pipeline_train_step
    from kungfu_tpu.parallel.rules import replicated, stacked

    n = jax.device_count()
    platform = jax.devices()[0].platform
    if platform == "cpu":  # smoke path
        size, batch, seq, microbatches = "tiny", 4, 128, 2
        iters, warmup = min(iters, 3), min(warmup, 1)
        pp = min(pp, SIZES[size][1])  # tiny has 2 layers
    if pp > n:
        raise SystemExit(f"--pp {pp} exceeds device count {n}")
    hidden, layers, heads, inter = SIZES[size]
    # flash mixer inside the pipeline stages too (same kernel as the
    # dense rows; tiny CPU smoke shapes fall back to plain attention)
    cfg = GPTConfig(vocab_size=50257, hidden_size=hidden,
                    num_layers=layers, num_heads=heads,
                    intermediate_size=inter,
                    max_position=max(1024, seq), dtype=jnp.bfloat16,
                    attention="flash" if platform != "cpu" else "local")
    model = GPTLM(cfg)
    tokens = jnp.zeros((batch, seq), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]
    outer, stacked = stack_gpt_blocks(params, pp)
    mesh = Mesh(np.array(jax.devices()[:pp]), ("pipe",))
    mapped = shard_map(
        lambda o, s, t: gpt_pipeline_train_step(
            cfg, o, s, t, "pipe", num_microbatches=microbatches),
        mesh=mesh, in_specs=(replicated(), stacked("pipe"), replicated()),
        out_specs=(replicated(), replicated(), stacked("pipe")),
        check_vma=False)
    tx = optax.adamw(1e-4)  # stateless transformation: one serves both
    so, ss = tx.init(outer), tx.init(stacked)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def step(outer, stacked, so, ss, t):
        loss, g_o, g_s = mapped(outer, stacked, t)
        uo, so = tx.update(g_o, so, outer)
        us, ss = tx.update(g_s, ss, stacked)
        return (optax.apply_updates(outer, uo),
                optax.apply_updates(stacked, us), so, ss, loss)

    for _ in range(max(warmup, 1)):
        outer, stacked, so, ss, loss = step(outer, stacked, so, ss,
                                            tokens)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        outer, stacked, so, ss, loss = step(outer, stacked, so, ss,
                                            tokens)
    float(loss)
    dt = (time.perf_counter() - t0) / iters
    meta = {
        **_device_meta(), "pp": pp, "size": size,
        "batch": batch, "seq": seq, "microbatches": microbatches,
        "schedule": "1F1B", "step_time_ms": round(dt * 1000, 2),
        "iters": iters,
        "mfu_vs_v5e_bf16_peak": _train_mfu(
            cfg, batch * seq / dt, seq, pp),
    }
    return batch * seq / dt, meta


def measure_decode_rate(size: str = "small", batch: int = 8,
                        prompt_len: int = 128, gen_len: int = 128,
                        iters: int = 3, tp: int = 1):
    """Generated tokens/sec of KV-cached autoregressive decoding.

    `tp` > 1 serves with Megatron-sharded weights: gpt_generate is pure
    traced JAX, so jitting it over serve-table-sharded params lets
    GSPMD propagate the head sharding into the KV caches and insert the
    ICI collectives — the standard TPU serving layout
    (token-exact parity with tp=1: tests/test_gpt.py::TestGenerate).

    Model/params(+sharding) setup is `serve.engine.build_lm` — the
    SAME entry point the continuous-batching decode tier boots from,
    so this published row and the serving tier cannot drift.
    """
    import jax
    import jax.numpy as jnp

    from kungfu_tpu.models import gpt_generate
    from kungfu_tpu.serve.engine import build_lm

    platform = jax.devices()[0].platform
    if platform == "cpu":  # smoke path
        size, batch, prompt_len, gen_len = "tiny", 2, 8, 8
        iters = 1
    model, params, _mesh = build_lm(size,
                                    max_position=prompt_len + gen_len,
                                    tp=tp)
    prompt = jnp.zeros((batch, prompt_len), jnp.int32)

    run = jax.jit(lambda p, t: gpt_generate(model, p, t, gen_len))
    out = run(params, prompt)            # compile + warmup
    int(out[0, -1])                      # fence
    t0 = time.perf_counter()
    for _ in range(iters):
        out = run(params, prompt)
        int(out[0, -1])
    dt = (time.perf_counter() - t0) / iters
    # the timed region is one batched prefill forward + gen_len decode
    # steps; ms_per_token divides by gen_len, so it slightly overstates
    # per-decode-step cost by the (single) prefill pass
    meta = {**_device_meta(), "size": size, "batch": batch,
            "prompt_len": prompt_len, "gen_len": gen_len, "tp": tp,
            "ms_per_token": round(dt * 1000 / gen_len, 3)}
    return batch * gen_len / dt, meta


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="small", choices=sorted(SIZES))
    ap.add_argument("--batch", type=int, default=8,
                    help="per-data-shard batch")
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--attention", default="local",
                    choices=["local", "flash"])
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--experts", type=int, default=0,
                    help="Switch-MoE FFN with this many experts "
                         "(trains via gpt_loss_with_aux)")
    ap.add_argument("--moe-group", type=int, default=0,
                    help="(--experts) routing group size, 0 = auto 512")
    ap.add_argument("--moe-bf16", action="store_true",
                    help="(--experts) store expert stacks in bfloat16 "
                         "instead of f32 master weights")
    ap.add_argument("--remat", action="store_true",
                    help="checkpoint each Block (recompute activations "
                         "in the backward)")
    ap.add_argument("--ce-variant", default="residual",
                    choices=("residual", "recompute"),
                    help="fused-CE backward: bf16-logits residual "
                         "(default, faster at GPT-2 scale) or full "
                         "recompute (memory-independent of N*V)")
    ap.add_argument("--pp", type=int, default=0,
                    help="1F1B pipeline over this many stages")
    ap.add_argument("--microbatches", type=int, default=8,
                    help="(--pp) microbatches in flight")
    ap.add_argument("--microbatch-bound", action="store_true",
                    help="measure the plain (non-pipelined) step at "
                         "batch = --batch / --microbatches: the "
                         "inherent small-batch bound on 1F1B "
                         "throughput at the same global batch, so the "
                         "pp=1 gap splits into inherent-microbatch "
                         "loss vs schedule overhead (VERDICT r5 "
                         "item 5)")
    ap.add_argument("--decode", action="store_true",
                    help="measure KV-cached generation instead of "
                         "training")
    ap.add_argument("--prompt-len", type=int, default=128,
                    help="(--decode) prompt length")
    ap.add_argument("--gen-len", type=int, default=128,
                    help="(--decode) generated tokens")
    args = ap.parse_args()
    from kungfu_tpu import compile_cache

    cache = compile_cache.enable()

    def emit(metric, rate, meta):
        meta["compile_cache"] = cache.as_dict()
        print(json.dumps({"metric": metric, "value": round(rate, 1),
                          "unit": "tokens/sec", "details": meta}))

    if (args.decode or args.pp) and (args.remat
                                     or args.ce_variant != "residual"):
        raise SystemExit(
            "--remat/--ce-variant only apply to the dense/MoE train "
            "path (measure_lm_rate); they are not plumbed through "
            "--pp or --decode and would be silently ignored")
    if args.decode:
        if args.attention != "local":
            raise SystemExit(
                "--decode uses the KV-cached local path; "
                "--attention does not apply")
        rate, meta = measure_decode_rate(args.size, args.batch,
                                         args.prompt_len, args.gen_len,
                                         iters=args.iters, tp=args.tp)
        emit("gpt_decode_tokens_per_sec", rate, meta)
        return
    if args.microbatch_bound:
        # the 1F1B pipeline cuts the global batch into `microbatches`
        # slices of b = batch/microbatches and runs each as its own
        # fwd/bwd; a perfectly-overlapped schedule can therefore never
        # beat the PLAIN step measured at that microbatch size. This
        # row publishes that bound, so (plain @ global b) - (bound) is
        # the inherent small-batch cost and (bound) - (1F1B row) is
        # the schedule's own overhead.
        if args.pp or args.decode:
            raise SystemExit("--microbatch-bound is itself the "
                             "non-pipelined reference; drop --pp/"
                             "--decode")
        if args.batch % args.microbatches:
            raise SystemExit(
                f"--microbatches {args.microbatches} must divide "
                f"--batch {args.batch} (the pipeline's own slicing "
                "constraint)")
        mb = args.batch // args.microbatches
        # plumb the full model configuration: a bound row measured on
        # a different model (dense vs MoE, remat on/off) would make
        # the gap decomposition wrong-by-construction
        rate, meta = measure_lm_rate(args.size, mb, args.seq,
                                     args.tp, args.attention,
                                     args.iters,
                                     experts=args.experts,
                                     moe_group=args.moe_group,
                                     moe_bf16=args.moe_bf16,
                                     remat=args.remat,
                                     ce_variant=args.ce_variant)
        meta["global_batch"] = args.batch
        meta["microbatches"] = args.microbatches
        meta["microbatch"] = mb
        emit("gpt_microbatch_bound_tokens_per_sec", rate, meta)
        return
    if args.pp:
        rate, meta = measure_pp_rate(args.size, args.batch, args.seq,
                                     args.pp, args.microbatches,
                                     iters=args.iters)
        emit("gpt_pp_tokens_per_sec", rate, meta)
        return
    rate, meta = measure_lm_rate(args.size, args.batch, args.seq,
                                 args.tp, args.attention, args.iters,
                                 experts=args.experts,
                                 moe_group=args.moe_group,
                                 moe_bf16=args.moe_bf16,
                                 remat=args.remat,
                                 ce_variant=args.ce_variant)
    emit("gpt_tokens_per_sec", rate, meta)


if __name__ == "__main__":
    main()
