"""`models/ouro.py`'s train step on one chip, through the path the GPT
and glm adapters take: one jitted `make` for parameters, optimizer
state and the ring; `shard_params` with the model's rules table on a
("data", "model") mesh; `build_gspmd_train_step(has_aux=True)` over the
fused loss; the configuration's adamw.

What is this adapter's own:

- a `plan` line at build time: `flash_plan` at the cell's shape and
  `loop_plan` (layer applications and head + CE calls a step, what the
  recomputation keeps, the shared weights' gradient bytes);
- the loss returns the four passes' CEs, the mean exit distribution and
  its entropy beside the scalar. The step keeps the last step's as
  device arrays and nothing fetches them inside the window; `verify`
  prints them on a `counters` line;
- `verify` compares, at the final parameters and on the ring's first
  batch, the timed loss function with `benchmark/reference_ouro.py`
  (float32, "highest"): every pass's CE and the objective, the mean
  exit distribution, and the gradients of the leaves the configuration
  names. Each limit is in the configuration's file with its readings
  and its reason. The optimizer state is released first: nothing reads
  it after the window, and the reference's working set then stays
  under the window's own peak, so `memory_peak_bytes` remains the
  timed step's.
"""

from __future__ import annotations

import math


def model_config(config):
    """The configuration's file -> `OuroConfig`."""
    import jax.numpy as jnp

    from kungfu_tpu.models.ouro import OuroConfig

    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise SystemExit("models/ouro.py has no grouped KV heads")
    return OuroConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        head_dim=config["head_dim"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        total_ut_steps=config["total_ut_steps"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        entropy_beta=config["entropy_beta"],
        dtype=jnp.dtype(config["dtype"]),
        attention=config["attention"], remat=config["remat"])


def reference_config(config):
    """What `reference_ouro.reference_loss` reads: the source's keys as
    the file has them, and `entropy_beta`."""
    keys = ("num_attention_heads", "head_dim", "num_hidden_layers",
            "total_ut_steps", "rope_theta", "rms_norm_eps", "entropy_beta")
    return {k: config[k] for k in keys}


def build(config, traffic, devs, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from benchmark.runners.train import Job, log, optimizer
    from kungfu_tpu.models.ouro import OuroLM, loop_plan, ouro_fused_loss
    from kungfu_tpu.ops.flash import flash_plan
    from kungfu_tpu.parallel import (build_gspmd_train_step, ouro_rules,
                                     shard_params)
    from kungfu_tpu.parallel.rules import replicated, stacked

    if len(devs) != 1:
        raise SystemExit("adapters/ouro.py runs one pipeline stage's "
                         "share on one chip")
    batch, seq = traffic["batch_per_chip"], traffic["seq"]
    cfg = model_config(config)
    model = OuroLM(cfg)
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
    params = shard_params(params, mesh, ouro_rules())
    # the jitted step hands its state back as replicated on the mesh: a
    # first call on any other spelling compiles the step a second time
    # (adapters/glm_moe.py; PERF.md section 7)
    params, opt_state = jax.device_put(
        (params, opt_state), NamedSharding(mesh, replicated()))
    tokens = NamedSharding(mesh, stacked("data"))
    ring = [jax.device_put(t, tokens) for t in ring]

    def loss_fn(p, t):
        return ouro_fused_loss(model, p, t)

    gspmd_step = build_gspmd_train_step(loss_fn, tx, has_aux=True)
    last = {}  # the last step's counters, on the device until `verify`

    def step(p, o, t):
        p, o, loss, last["metrics"] = gspmd_step(p, o, t)
        return p, o, loss

    log(phase="plan",
        params=sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params)),
        flash_plan=flash_plan(seq, cfg.head_dim, dtype=cfg.dtype,
                              causal=True)
        if cfg.attention == "flash" else None,
        loop_plan=loop_plan(cfg, batch, seq))

    def verify(state):
        final_params, final_opt = state
        return _verify(config, loss_fn, final_params, final_opt, ring[0],
                       last.get("metrics"), log)

    return Job(step=step, state=(params, opt_state), batches=ring,
               unit="tokens", units_per_step=batch * seq,
               loss_at_init=math.log(cfg.vocab_size), verify=verify)


def _verify(config, loss_fn, params, opt_state, tokens, counters, log):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_ouro as ref
    from benchmark.adapters.glm_moe import _leaf, _with_leaf

    limits = config["verify"]
    log(phase="counters", **{k: np.asarray(v).tolist()
                             for k, v in (counters or {}).items()})

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
    diff = lambda k: np.abs(np.asarray(got[k], np.float64)  # noqa: E731
                            - np.asarray(want[k], np.float64))
    loss_err = {f"ce_{i + 1}": float(e) for i, e in enumerate(diff("ce"))}
    loss_err["objective"] = abs(float(loss) - float(want_loss))
    exit_err = {f"p_{i + 1}": float(e)
                for i, e in enumerate(diff("exit_p"))}
    grad_err = {
        p: float(jnp.linalg.norm((g_got[p] - g_want[p]).ravel())
                 / jnp.linalg.norm(g_want[p].ravel())) for p in paths}
    log(phase="reference", loss=float(loss),
        reference_loss=float(want_loss),
        ce=np.asarray(got["ce"]).tolist(),
        exit_p=np.asarray(got["exit_p"]).tolist(),
        exit_entropy=float(got["exit_entropy"]), loss_abs_err=loss_err,
        exit_abs_err=exit_err, grad_rel_err=grad_err, limits=limits)
    return {
        "reference_objective": all(
            err <= limits["loss_abs_tol"] for err in loss_err.values()),
        "reference_exit_distribution": all(
            err <= limits["exit_abs_tol"] for err in exit_err.values()),
        "reference_gradients": all(
            err <= limits["grad_rel_tol"] for err in grad_err.values()),
    }
