"""`models/afmoe.py`'s train step on one chip, through the path the GPT,
glm and ouro adapters take: one jitted `make` for parameters, optimizer
state and the ring; `shard_params` with the model's rules table on a
("data", "model") mesh; `build_gspmd_train_step(has_aux=True)` over the
fused loss.

What is this adapter's own:

- the optimizer is the configuration's adamw for every leaf but the
  routers' selection biases, which take `sgd(load_balance_coeff)` on the
  load sign the loss hands them (`glm_moe_optimizer`, the expert layer's
  own): one `tx`, the shared step builder as it is;
- a `plan` line at build time: `flash_plan` of BOTH kinds of call a step
  makes (the full layers' and the sliding layers', grouped K/V heads),
  `layer_plan` (each layer's kinds, visible pairs a head, what the
  recomputation keeps) and the row buffer's size;
- the loss returns counters beside the scalar (`has_aux`). The step
  keeps every step's as device arrays and nothing fetches them inside
  the window; `verify` reads them afterwards and prints a `counters`
  line;
- `verify` compares, at the final parameters and on the ring's first
  batch, the timed loss function with `benchmark/reference_afmoe.py`
  (float32, "highest"): the CE, the gradients of the leaves the
  configuration names, and each expert layer's counts against the
  reference's router run on the very input the program's router saw.
  Each limit is in the configuration's file with its readings and its
  reason. The optimizer state is released first: nothing reads it after
  the window, and the reference's working set then stays under the
  window's own peak, so `memory_peak_bytes` remains the timed step's.
"""

from __future__ import annotations

import math


def model_config(config):
    """The configuration's file -> `AfmoeConfig`: the router keeps its
    published width (`router_width`), `num_experts` counts the experts
    held here, `layer_types` is kept whole and its first
    `num_hidden_layers` entries run."""
    import jax.numpy as jnp

    from kungfu_tpu.models.afmoe import AfmoeConfig

    return AfmoeConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        sliding_window=config["sliding_window"],
        layer_types=tuple(
            config["layer_types"][:config["num_hidden_layers"]]),
        num_dense_layers=config["num_dense_layers"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        n_routed_experts=config["router_width"],
        num_experts_per_tok=config["num_experts_per_tok"],
        n_shared_experts=config["num_shared_experts"],
        routed_scaling_factor=config["route_scale"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        mup_enabled=config["mup_enabled"],
        held=(config["held_first_expert"], config["num_experts"]),
        dtype=jnp.dtype(config["dtype"]),
        attention=config["attention"], remat=config["remat"])


def reference_config(config):
    """What `reference_afmoe.reference_loss` reads: the source's keys as
    the file has them (`layer_types` cut to the layers that run) and
    `held`."""
    keys = ("num_attention_heads", "num_key_value_heads", "sliding_window",
            "num_dense_layers", "num_experts_per_tok", "route_scale",
            "rope_theta", "rms_norm_eps", "mup_enabled")
    return {**{k: config[k] for k in keys},
            "layer_types": tuple(
                config["layer_types"][:config["num_hidden_layers"]]),
            "held": (config["held_first_expert"], config["num_experts"])}


def build(config, traffic, devs, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from benchmark.runners.train import Job, log, optimizer
    from kungfu_tpu.models.afmoe import (AfmoeLM, afmoe_fused_loss,
                                         layer_plan)
    from kungfu_tpu.models.glm_moe import glm_moe_optimizer
    from kungfu_tpu.ops.flash import flash_plan
    from kungfu_tpu.parallel import (afmoe_rules, build_gspmd_train_step,
                                     shard_params)
    from kungfu_tpu.parallel.grouped_moe import buffer_rows
    from kungfu_tpu.parallel.rules import replicated, stacked

    if len(devs) != 1:
        raise SystemExit("adapters/afmoe.py runs one chip's share; the "
                         "expert axis across chips has no cell yet")
    batch, seq = traffic["batch_per_chip"], traffic["seq"]
    cfg = model_config(config)
    model = AfmoeLM(cfg)
    tx = glm_moe_optimizer(optimizer(config["optimizer"]),
                           config["load_balance_coeff"])

    def make(key):
        k_params, k_data = jax.random.split(key)
        # no leaf's shape depends on the length: a short one traces fast
        params = model.init(
            k_params, jnp.zeros((1, 16), jnp.int32))["params"]
        ring = tuple(
            jax.random.randint(k, (batch, seq), 0, cfg.vocab_size,
                               dtype=jnp.int32)
            for k in jax.random.split(k_data, traffic["n_batches"]))
        return params, tx.init(params), ring

    params, opt_state, ring = jax.jit(make)(jax.random.PRNGKey(seed))
    mesh = Mesh(np.array(devs).reshape(1, 1), ("data", "model"))
    params = shard_params(params, mesh, afmoe_rules())
    # the jitted step hands its state back as replicated on the mesh: a
    # first call on any other spelling compiles the step a second time
    # (adapters/glm_moe.py; PERF.md section 7)
    params, opt_state = jax.device_put(
        (params, opt_state), NamedSharding(mesh, replicated()))
    tokens = NamedSharding(mesh, stacked("data"))
    ring = [jax.device_put(t, tokens) for t in ring]

    def loss_fn(p, t):
        return afmoe_fused_loss(model, p, t,
                                residual=config["fused_ce_residual"])

    gspmd_step = build_gspmd_train_step(loss_fn, tx, has_aux=True)
    history = []  # every step's counters, on the device until `verify`

    def step(p, o, t):
        p, o, loss, metrics = gspmd_step(p, o, t)
        history.append(metrics)
        return p, o, loss

    tokens_a_step = batch * seq
    group = cfg.num_heads // cfg.num_kv_heads
    plans = None
    if cfg.attention == "flash":
        plans = {
            kind: flash_plan(seq, cfg.head_dim, dtype=cfg.dtype,
                             causal=True, window=window, q_per_kv=group)
            for kind, window in (("full", None),
                                 ("sliding", cfg.sliding_window - 1))}
    log(phase="plan",
        params=sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params)),
        flash_plan=plans, layer_plan=layer_plan(cfg, batch, seq),
        buffer_rows=buffer_rows(tokens_a_step, cfg.num_experts_per_tok,
                                cfg.held),
        expected_rows=tokens_a_step * cfg.num_experts_per_tok
        * cfg.held[1] // cfg.n_routed_experts)

    def verify(state):
        final_params, final_opt = state
        return _verify(config, cfg, loss_fn, final_params, final_opt,
                       ring[0], history, log)

    return Job(step=step, state=(params, opt_state), batches=ring,
               unit="tokens", units_per_step=tokens_a_step,
               loss_at_init=math.log(cfg.vocab_size), verify=verify)


def _verify(config, cfg, loss_fn, params, opt_state, tokens, history,
            log):
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_afmoe as ref
    from benchmark.adapters.glm_moe import _leaf, _with_leaf
    from kungfu_tpu.models.afmoe import AfmoeLM
    from kungfu_tpu.parallel.grouped_moe import route_sigmoid_topk

    limits = config["verify"]
    steps = jax.device_get(history)

    def over_steps(name, fn):
        return fn(np.stack([s[name] for s in steps])) if steps else None

    dropped = over_steps("dropped", lambda a: int(a.sum()))
    log(phase="counters", steps=len(steps),
        held_assignments=over_steps(
            "held_assignments",
            lambda a: [int(a.min()), float(a.mean()), int(a.max())]),
        max_load_over_mean=over_steps("max_load_over_mean",
                                      lambda a: float(a.max())),
        buffer_rows_used=over_steps("buffer_rows_used",
                                    lambda a: int(a.max())),
        dropped=dropped,
        last_step_counts=steps[-1]["counts"].tolist() if steps else None)

    for leaf in jax.tree_util.tree_leaves(opt_state):
        leaf.delete()  # see the module docstring

    rcfg = reference_config(config)

    def ref_loss(p, t):
        return ref.reference_loss(p, t, rcfg, remat=True,
                                  q_block=min(512, t.shape[1]))

    # the loss and the gradients of the named leaves in one program a
    # side: the backward still runs the whole depth above each leaf, the
    # other leaves' dW are never formed
    paths = limits["grad_leaves"]
    sub = {p: _leaf(params, p) for p in paths}

    def value_and_grads(fn):
        def of_sub(s, p, t):
            for path, value in s.items():
                p = _with_leaf(p, path, value)
            return fn(p, t)

        return jax.jit(jax.value_and_grad(of_sub, has_aux=True))(
            sub, params, tokens)

    (loss, got), g_got = value_and_grads(loss_fn)
    (want_loss, want), g_want = value_and_grads(ref_loss)
    loss_err = {"ce": abs(float(got["ce"]) - float(want["ce"])),
                "objective": abs(float(loss) - float(want_loss))}
    grad_err = {
        p: float(jnp.linalg.norm((g_got[p] - g_want[p]).ravel())
                 / jnp.linalg.norm(g_want[p].ravel())) for p in paths}

    # each expert layer's counts against the reference's router on the
    # input the program's own router saw: the block's `ffn_norm` output,
    # captured from ONE forward pass of its own (no recomputation in it)
    # that also gives that pass's counts (adapters/glm_moe.py has the
    # reason: end to end the bf16 stream flips some 2% of the choices
    # whatever the router's precision). That pass is compiled WITHOUT
    # XLA's licence to carry a bf16 value on in f32
    # (`xla_allow_excess_precision`): with it the router inside the
    # fused program reads the norm's output BEFORE its rounding to
    # bf16, another input than the captured one, and 0.17% of the
    # choices differ for that alone (1274-1450 of the sum below on the
    # chip, PR 34, where the program's router run alone on the captured
    # input reads 0: `route_count_own_router`, logged beside it)
    plain = AfmoeLM(dataclasses.replace(cfg, remat=False))

    def forward(p, t):
        return plain.apply(
            {"params": p}, t, mutable=["intermediates"],
            capture_intermediates=lambda m, _: m.name == "ffn_norm")

    (_, seen), captured = jax.jit(forward).lower(params, tokens).compile(
        compiler_options={"xla_allow_excess_precision": False})(
        params, tokens)
    captured = captured["intermediates"]
    blocks = [f"Block_{i}" for i in range(cfg.num_dense_layers,
                                          cfg.num_layers)]

    @jax.jit
    def ref_counts(x, router, bias):
        with jax.default_matmul_precision("highest"):
            return ref.route(
                x.reshape(-1, x.shape[-1]).astype(jnp.float32), router,
                bias, cfg.num_experts_per_tok,
                cfg.routed_scaling_factor)[1]

    @jax.jit
    def own_counts(x, router, bias):
        return route_sigmoid_topk(
            x.reshape(-1, x.shape[-1]), router, bias,
            cfg.num_experts_per_tok, cfg.routed_scaling_factor).counts

    mismatch = own_mismatch = 0
    for i, path in enumerate(blocks):
        (x,) = _leaf(captured, path)["ffn_norm"]["__call__"]
        moe = _leaf(params, path)["moe"]
        want_counts = ref_counts(x, moe["router"], moe["router_bias"])
        mismatch += int(jnp.abs(seen["counts"][i] - want_counts).sum())
        own_mismatch += int(jnp.abs(own_counts(
            x, moe["router"], moe["router_bias"]) - want_counts).sum())

    log(phase="reference", loss=float(loss), reference_loss=float(want_loss),
        loss_abs_err=loss_err, grad_rel_err=grad_err,
        route_count_mismatch=mismatch,
        route_count_own_router=own_mismatch, limits=limits)
    return {
        "dropped_is_zero": dropped == 0,
        "reference_objective": all(
            err <= limits["loss_abs_tol"] for err in loss_err.values()),
        "reference_gradients": all(
            err <= limits["grad_rel_tol"] for err in grad_err.values()),
        "reference_route_counts":
            mismatch <= limits["route_count_mismatch_max"],
    }
