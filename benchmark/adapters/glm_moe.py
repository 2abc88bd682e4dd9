"""`models/glm_moe.py`'s train step on one chip, through the path the
GPT adapter takes: one jitted `make` for parameters, optimizer state
and the ring; `shard_params` with the model's rules table on a
("data", "model") mesh; `build_gspmd_train_step` over the fused loss.

What is this adapter's own:

- the optimizer is the configuration's adamw for every leaf but the
  routers' selection biases, which take `sgd(router_bias_gamma)` on the
  load sign the loss hands them (`glm_moe_optimizer`): one `tx`, the
  shared step builder as it is;
- the loss returns counters beside the scalar (`has_aux`). The step
  keeps every step's as device arrays and nothing fetches them inside
  the window; `verify` reads them afterwards and prints a `counters`
  line (a `plan` line at build time holds `flash_plan`'s reading and
  the row buffer's size);
- `verify` compares, at the final parameters and on the ring's first
  batch, the timed loss function with `benchmark/reference_glm.py`
  (float32, "highest"): both CE terms, the gradients of the leaves the
  configuration names, and each expert layer's counts against the
  reference's router run on the very input the program's router saw.
  Each limit is in the configuration's file with its reason. The
  optimizer state is released first: nothing reads it after the window,
  and the reference's working set then stays under the window's own
  peak, so `memory_peak_bytes` remains the timed step's.
"""

from __future__ import annotations

import math


def model_config(config):
    """The configuration's file -> `GlmMoeConfig`: the router keeps its
    published width, `n_routed_experts` counts the experts held here."""
    import jax.numpy as jnp

    from kungfu_tpu.models.glm_moe import GlmMoeConfig

    return GlmMoeConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        n_routed_experts=config["router_width"],
        num_experts_per_tok=config["num_experts_per_tok"],
        n_shared_experts=config["n_shared_experts"],
        routed_scaling_factor=config["routed_scaling_factor"],
        first_k_dense_replace=config["first_k_dense_replace"],
        num_layers=config["num_hidden_layers"],
        num_nextn_predict_layers=config["num_nextn_predict_layers"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        held=(config["held_first_expert"], config["n_routed_experts"]),
        mtp_lambda=config["mtp_lambda"],
        dtype=jnp.dtype(config["dtype"]),
        attention=config["attention"], remat=config["remat"])


def reference_config(config):
    """What `reference_glm.reference_loss` reads: the source's keys as
    the file has them, `held` and `mtp_lambda`."""
    keys = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "num_experts_per_tok", "routed_scaling_factor",
            "first_k_dense_replace", "num_hidden_layers",
            "num_nextn_predict_layers", "rope_theta", "rms_norm_eps",
            "mtp_lambda")
    return {**{k: config[k] for k in keys},
            "held": (config["held_first_expert"],
                     config["n_routed_experts"])}


def _leaf(tree, path):
    for key in path.split("/"):
        tree = tree[key]
    return tree


def _with_leaf(tree, path, value):
    key, _, rest = path.partition("/")
    return {**tree, key: _with_leaf(tree[key], rest, value)
            if rest else value}


def build(config, traffic, devs, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from benchmark.runners.train import Job, log, optimizer
    from kungfu_tpu.models.glm_moe import (GlmMoeLM, glm_moe_fused_loss,
                                           glm_moe_optimizer)
    from kungfu_tpu.ops.flash import flash_plan
    from kungfu_tpu.parallel import (build_gspmd_train_step, glm_moe_rules,
                                     shard_params)
    from kungfu_tpu.parallel.grouped_moe import buffer_rows
    from kungfu_tpu.parallel.rules import replicated, stacked

    if len(devs) != 1:
        raise SystemExit("adapters/glm_moe.py runs one chip's share; the "
                         "expert axis across chips has no cell yet")
    batch, seq = traffic["batch_per_chip"], traffic["seq"]
    cfg = model_config(config)
    model = GlmMoeLM(cfg)
    tx = glm_moe_optimizer(optimizer(config["optimizer"]),
                           config["router_bias_gamma"])

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
    params = shard_params(params, mesh, glm_moe_rules())
    # on one chip every spec of the table means "whole", and the jitted
    # step hands its state back as replicated on the mesh: a first call
    # on any other spelling compiles the step a second time (45 s here;
    # PERF.md section 7 has the repair `parallel/train.py` owes)
    params, opt_state = jax.device_put(
        (params, opt_state), NamedSharding(mesh, replicated()))
    tokens = NamedSharding(mesh, stacked("data"))
    ring = [jax.device_put(t, tokens) for t in ring]

    def loss_fn(p, t):
        return glm_moe_fused_loss(model, p, t,
                                  residual=config["fused_ce_residual"])

    gspmd_step = build_gspmd_train_step(loss_fn, tx, has_aux=True)
    history = []  # every step's counters, on the device until `verify`

    def step(p, o, t):
        p, o, loss, metrics = gspmd_step(p, o, t)
        history.append(metrics)
        return p, o, loss

    tokens_a_step = batch * seq
    log(phase="plan",
        params=sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params)),
        flash_plan=flash_plan(seq, cfg.v_head_dim, dtype=cfg.dtype,
                              causal=True)
        if cfg.attention == "flash" else None,
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
               loss_at_init=(1 + cfg.mtp_lambda)
               * math.log(cfg.vocab_size),
               verify=verify)


def _verify(config, cfg, loss_fn, params, opt_state, tokens, history,
            log):
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_glm as ref
    from kungfu_tpu.models.glm_moe import GlmMoeLM

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

    # the objective and the gradients of the named leaves in one program
    # a side: the backward still runs the whole depth above each leaf,
    # the other leaves' dW are never formed
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
    loss_err = {k: abs(float(got[k]) - float(want[k]))
                for k in ("ce", "ce_mtp") if k in want}
    loss_err["objective"] = abs(float(loss) - float(want_loss))
    grad_err = {
        p: float(jnp.linalg.norm((g_got[p] - g_want[p]).ravel())
                 / jnp.linalg.norm(g_want[p].ravel())) for p in paths}

    # each expert layer's counts against the reference's router on the
    # input the program's own router saw: the block's `ffn_norm` output,
    # captured from ONE forward pass of its own (no recomputation in it)
    # that also gives that pass's counts. End to
    # end the bf16 stream flips some 2% of the choices whatever the
    # router's precision (and a second program rounds the stream
    # otherwise: 802 of 163840 on the chip); on the same input only ties
    # in f32 rounding are left
    plain = GlmMoeLM(dataclasses.replace(cfg, remat=False))
    (_, _, seen), captured = jax.jit(lambda p, t: plain.apply(
        {"params": p}, t, mutable=["intermediates"],
        capture_intermediates=lambda m, _: m.name == "ffn_norm"))(
        params, tokens)
    captured = captured["intermediates"]
    blocks = [f"Block_{i}" for i in range(cfg.first_k_dense_replace,
                                          cfg.num_layers)]
    blocks += ["mtp/block"] * cfg.num_nextn_predict_layers

    @jax.jit
    def ref_counts(x, router, bias):
        with jax.default_matmul_precision("highest"):
            return ref.route(
                x.reshape(-1, x.shape[-1]).astype(jnp.float32), router,
                bias, cfg.num_experts_per_tok,
                cfg.routed_scaling_factor)[1]

    mismatch = 0
    for i, path in enumerate(blocks):
        (x,) = _leaf(captured, path)["ffn_norm"]["__call__"]
        moe = _leaf(params, path)["moe"]
        want_counts = ref_counts(x, moe["router"], moe["router_bias"])
        mismatch += int(jnp.abs(seen["counts"][i] - want_counts).sum())

    log(phase="reference", loss=float(loss), reference_loss=float(want_loss),
        loss_abs_err=loss_err, grad_rel_err=grad_err,
        route_count_mismatch=mismatch, limits=limits)
    return {
        "dropped_is_zero": dropped == 0,
        "reference_objective": all(
            err <= limits["loss_abs_tol"] for err in loss_err.values()),
        "reference_gradients": all(
            err <= limits["grad_rel_tol"] for err in grad_err.values()),
        "reference_route_counts":
            mismatch <= limits["route_count_mismatch_max"],
    }
