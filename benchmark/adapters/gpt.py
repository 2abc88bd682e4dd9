"""GPT train step on one chip, through what
`kungfu_tpu/benchmarks/lm.py::measure_lm_rate` calls on its dense
one-chip branch: `GPTConfig`, `GPTLM`, `gpt_fused_loss(residual=True)`,
`build_gspmd_train_step`, `gpt_tp_rules`, `shard_params`.

Where it departs from `lm.py`: parameters, optimizer state and the ring
of token batches are made on the device from the seed by ONE jitted
program (no `model.init` op by op, no round trip through the host), and
each step takes the next batch of the ring.
"""

from __future__ import annotations

import math


def build(config, traffic, devs, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from benchmark.runners.train import Job, optimizer
    from kungfu_tpu.models import GPTConfig, GPTLM, gpt_fused_loss
    from kungfu_tpu.parallel import (build_gspmd_train_step, gpt_tp_rules,
                                     shard_params)
    from kungfu_tpu.parallel.rules import stacked

    if len(devs) != 1:
        raise SystemExit("adapters/gpt.py drives lm.py's one-chip branch; "
                         "a data-parallel LM cell brings its own adapter")
    batch, seq = traffic["batch_per_chip"], traffic["seq"]
    if seq > config["n_positions"]:
        raise SystemExit(f"seq {seq} exceeds n_positions")
    cfg = GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["n_embd"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        intermediate_size=config["n_inner"],
        max_position=config["n_positions"],
        dtype=jnp.dtype(config["dtype"]), attention=config["attention"])
    model = GPTLM(cfg)
    tx = optimizer(config["optimizer"])

    def make(key):
        k_params, k_data = jax.random.split(key)
        params = model.init(
            k_params, jnp.zeros((1, seq), jnp.int32))["params"]
        ring = tuple(
            jax.random.randint(k, (batch, seq), 0, cfg.vocab_size,
                               dtype=jnp.int32)
            for k in jax.random.split(k_data, traffic["n_batches"]))
        return params, tx.init(params), ring

    params, opt_state, ring = jax.jit(make)(jax.random.PRNGKey(seed))
    mesh = Mesh(np.array(devs).reshape(1, 1), ("data", "model"))
    params = shard_params(params, mesh, gpt_tp_rules())
    tokens = NamedSharding(mesh, stacked("data"))
    ring = [jax.device_put(t, tokens) for t in ring]
    step = build_gspmd_train_step(
        lambda p, t: gpt_fused_loss(model, p, t,
                                    residual=config["fused_ce_residual"]),
        tx)
    return Job(step=step, state=(params, opt_state), batches=ring,
               unit="tokens", units_per_step=batch * seq,
               loss_at_init=math.log(cfg.vocab_size))
