"""`models/granite_hybrid.py`'s train step on one chip, through the path
the GPT, glm, ouro and afmoe adapters take: one jitted `make` for
parameters, optimizer state and the ring; `shard_params` with the
model's rules table on a ("data", "model") mesh;
`build_gspmd_train_step` over the fused loss.

What is this adapter's own:

- a `plan` line at build time: `flash_plan` of the attention layer's
  call (grouped K/V heads, its scale), `layer_plan` (each layer's
  mixer, what the recomputation keeps) and `ssd_plan` (the scan's
  chunks, its form, the state's bytes and the largest array it forms);
- `verify` compares, at the final parameters and on the ring's first
  batch, the timed loss function with `benchmark/reference_granite.py`
  (float32, "highest", the state-space layers one position at a time):
  the CE and the gradients of the leaves the configuration names. Each
  limit is in the configuration's file with its readings and its
  reason. The optimizer state is released first: nothing reads it after
  the window, and the reference's working set then stays under the
  window's own peak, so `memory_peak_bytes` remains the timed step's.
"""

from __future__ import annotations

import math


def model_config(config):
    """The configuration's file -> `GraniteHybridConfig`: `layer_types`
    is kept whole and its first `num_hidden_layers` entries run."""
    import jax.numpy as jnp

    from kungfu_tpu.models.granite_hybrid import GraniteHybridConfig

    return GraniteHybridConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        layer_types=tuple(
            config["layer_types"][:config["num_hidden_layers"]]),
        intermediate_size=config["shared_intermediate_size"],
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_n_groups=config["mamba_n_groups"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_expand=config["mamba_expand"],
        mamba_chunk_size=config["mamba_chunk_size"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=config["residual_multiplier"],
        attention_multiplier=config["attention_multiplier"],
        logits_scaling=float(config["logits_scaling"]),
        rms_norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]),
        attention=config["attention"], remat=config["remat"])


def reference_config(config):
    """What `reference_granite.reference_loss` reads: the source's keys
    as the file has them, `layer_types` cut to the layers that run."""
    keys = ("num_attention_heads", "num_key_value_heads", "mamba_n_heads",
            "mamba_d_head", "mamba_d_state", "rms_norm_eps",
            "residual_multiplier", "attention_multiplier",
            "embedding_multiplier", "logits_scaling")
    return {**{k: config[k] for k in keys},
            "layer_types": tuple(
                config["layer_types"][:config["num_hidden_layers"]])}


def build(config, traffic, devs, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from benchmark.runners.train import Job, log, optimizer
    from kungfu_tpu.models.granite_hybrid import (
        ATTENTION, GraniteHybridLM, granite_fused_loss, layer_plan)
    from kungfu_tpu.ops.flash import flash_plan
    from kungfu_tpu.parallel import (build_gspmd_train_step,
                                     granite_hybrid_rules, shard_params)
    from kungfu_tpu.parallel.rules import replicated, stacked

    if len(devs) != 1:
        raise SystemExit("adapters/granite_hybrid.py runs one pipeline "
                         "stage on one chip; no cell spans chips yet")
    batch, seq = traffic["batch_per_chip"], traffic["seq"]
    cfg = model_config(config)
    model = GraniteHybridLM(cfg)
    tx = optimizer(config["optimizer"])

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
    params = shard_params(params, mesh, granite_hybrid_rules())
    # the jitted step hands its state back as replicated on the mesh: a
    # first call on any other spelling compiles the step a second time
    # (adapters/glm_moe.py; PERF.md section 7)
    params, opt_state = jax.device_put(
        (params, opt_state), NamedSharding(mesh, replicated()))
    tokens = NamedSharding(mesh, stacked("data"))
    ring = [jax.device_put(t, tokens) for t in ring]

    def loss_fn(p, t):
        return granite_fused_loss(model, p, t)

    step = build_gspmd_train_step(loss_fn, tx)
    plan = None
    if cfg.attention == "flash" and ATTENTION in cfg.layer_types:
        plan = flash_plan(seq, cfg.head_dim, dtype=cfg.dtype, causal=True,
                          q_per_kv=cfg.num_heads // cfg.num_kv_heads)
    layers = layer_plan(cfg, batch, seq)
    log(phase="plan",
        params=sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params)),
        flash_plan=plan, ssd_plan=layers.pop("ssd"), layer_plan=layers)

    def verify(state):
        final_params, final_opt = state
        return _verify(config, loss_fn, final_params, final_opt, ring[0],
                       log)

    return Job(step=step, state=(params, opt_state), batches=ring,
               unit="tokens", units_per_step=batch * seq,
               loss_at_init=math.log(cfg.vocab_size), verify=verify)


def _verify(config, loss_fn, params, opt_state, tokens, log):
    import jax
    import jax.numpy as jnp

    from benchmark import reference_granite as ref
    from benchmark.adapters.glm_moe import _leaf, _with_leaf

    limits = config["verify"]
    for leaf in jax.tree_util.tree_leaves(opt_state):
        leaf.delete()  # see the module docstring

    rcfg = reference_config(config)
    seq = tokens.shape[1]

    def ref_loss(p, t):
        return ref.reference_loss(p, t, rcfg, remat=True,
                                  q_block=min(512, seq),
                                  segment=min(256, seq))[0]

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

        return jax.jit(jax.value_and_grad(of_sub))(sub, params, tokens)

    loss, g_got = value_and_grads(loss_fn)
    want, g_want = value_and_grads(ref_loss)
    loss_err = abs(float(loss) - float(want))
    grad_err = {
        p: float(jnp.linalg.norm((g_got[p] - g_want[p]).ravel())
                 / jnp.linalg.norm(g_want[p].ravel())) for p in paths}
    log(phase="reference", loss=float(loss), reference_loss=float(want),
        loss_abs_err=loss_err, grad_rel_err=grad_err, limits=limits)
    return {
        "reference_loss": loss_err <= limits["loss_abs_tol"],
        "reference_gradients": all(
            err <= limits["grad_rel_tol"] for err in grad_err.values()),
    }
