"""The names the compiled step carries (`kungfu_tpu/trace/scopes.py`).

The benchmark's per-layer metrics select device operations by the JAX
scope path XLA copies into every operation's metadata (`tf_op` in a
profiler trace). These tests read the same paths on the CPU, from the
lowered text's debug locations, and hold what the metrics lean on:
the optimizer's arithmetic under `kf.opt_update`, everything a
data-parallel step does to agree across workers under `kf.grad_sync`,
head + cross-entropy forward and backward under `kf.fused_ce`, and the
flash kernels left exactly where `benchmark/metrics/flash_roofline.json`
looks for them.
"""

import re

import jax
import jax.numpy as jnp
import optax
import pytest

from kungfu_tpu.models import GPTConfig, GPTLM, ResNet50, gpt_fused_loss
from kungfu_tpu.optimizers import sync_sgd, sync_sgd_bucketed
from kungfu_tpu.parallel import (build_dp_replicated_train_step,
                                 build_gspmd_train_step,
                                 build_train_step_with_state, data_mesh)
from kungfu_tpu.trace.scopes import (FUSED_CE, GRAD_SYNC, MLA, MOE_EXPERTS,
                                     MOE_ROUTE, MTP, OPT_UPDATE)

# what JAX itself writes round the model's forward and backward, and
# `benchmark/metrics/fwd_bwd_ms.json` selects by
MODEL = re.compile(r"jvp\(|transpose\(")


def scope_paths(step, *args, calls=()):
    """Every equation of the traced step as "<scopes>/<primitive>":
    the name stack JAX hands XLA as the operation's metadata, which a
    device trace shows as `tf_op`. Nested programs (`jit`, `shard_map`,
    control flow) are walked with their caller's scopes in front; a
    `pallas_call`'s kernel body is one operation on the device and is
    not entered. The primitives in `calls` (a `cond` is one event in a
    trace, spanning its branch) are listed as well as walked."""
    paths = set()

    def walk(jaxpr, prefix):
        for eqn in jaxpr.eqns:
            scopes = str(eqn.source_info.name_stack)
            here = "/".join(x for x in (prefix, scopes) if x)
            subs = [] if eqn.primitive.name == "pallas_call" else [
                getattr(sub, "jaxpr", sub)
                for value in eqn.params.values()
                for sub in (value if isinstance(value, (tuple, list))
                            else (value,))]
            subs = [sub for sub in subs if hasattr(sub, "eqns")]
            for sub in subs:
                walk(sub, here)
            if not subs or eqn.primitive.name in calls:
                paths.add(f"{here}/{eqn.primitive.name}")

    walk(jax.make_jaxpr(step)(*args).jaxpr, "")
    return sorted(paths)


def primitive(path):
    return path.rsplit("/", 1)[-1]


@pytest.fixture(scope="module")
def gpt():
    """The benchmark's LM step at a tiny size: flash attention, fused
    head + CE with the bf16 residual, adamw, the GSPMD builder."""
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                    num_heads=2, intermediate_size=256, max_position=128,
                    dtype=jnp.bfloat16, attention="flash")
    model = GPTLM(cfg)
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 128), jnp.int32))["params"])
    tx = optax.adamw(1e-4)

    def loss_fn(p, t):
        return gpt_fused_loss(model, p, t, residual=True)

    return loss_fn, tx, params, jax.eval_shape(tx.init, params), tokens


@pytest.fixture(scope="module")
def gpt_paths(gpt):
    loss_fn, tx, params, opt_state, tokens = gpt
    return scope_paths(build_gspmd_train_step(loss_fn, tx), params,
                       opt_state, tokens)


def dp_resnet_paths(wrap):
    """The benchmark's vision step at a tiny size: batch-norm state,
    `wrap(sgd)` on a 4-device data mesh, the worker-stacked builder."""
    model = ResNet50(stage_sizes=[1, 1], num_classes=10, num_filters=8,
                     dtype=jnp.float32)
    mesh = data_mesh(4, devices=jax.devices()[:4])
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 32, 32, 3)), train=True))

    def loss_fn(params, batch_stats, batch):
        logits, updated = model.apply(
            {"params": params, "batch_stats": batch_stats}, batch["x"],
            train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()
        return loss, updated["batch_stats"]

    tx = wrap(optax.sgd(0.1, momentum=0.9))

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((4,) + x.shape, x.dtype), tree)

    params = variables["params"]
    batch = {"x": jax.ShapeDtypeStruct((8, 32, 32, 3), jnp.float32),
             "y": jax.ShapeDtypeStruct((8,), jnp.int32)}
    step = build_train_step_with_state(loss_fn, tx, mesh)
    return scope_paths(step, stack(params),
                       stack(variables["batch_stats"]),
                       stack(jax.eval_shape(tx.init, params)), batch)


@pytest.fixture(scope="module")
def dp_paths():
    return dp_resnet_paths(sync_sgd)


def test_optimizer_arithmetic_is_under_opt_update(gpt_paths):
    outside_model = [p for p in gpt_paths if not MODEL.search(p)]
    assert outside_model, gpt_paths
    stray = [p for p in outside_model if OPT_UPDATE not in p.split("/")]
    assert not stray, stray
    # adamw's own arithmetic is there, not just something
    assert {"sqrt", "integer_pow", "add", "mul"} <= {
        primitive(p) for p in outside_model}


def test_no_program_scope_inside_the_model(gpt_paths):
    under_model = [p for p in gpt_paths if "GPTLM" in p]
    assert under_model
    assert not [p for p in under_model if "kf." in p]


def test_fused_ce_kernels_and_backward_matmuls_are_under_fused_ce(
        gpt_paths):
    ce = [p for p in gpt_paths if FUSED_CE in p]
    fwd = [p for p in ce if "transpose(" not in p]
    bwd = [p for p in ce if "transpose(" in p]
    # forward: the kernel; backward: the d kernel and dW, dx beside it
    assert "pallas_call" in {primitive(p) for p in fwd}
    assert {"pallas_call", "dot_general"} <= {primitive(p) for p in bwd}
    # and nothing of the head or the loss runs outside it
    for p in gpt_paths:
        if "pallas_call" in p.split("/") and "GPTLM" not in p:
            assert FUSED_CE in p, p
        if primitive(p) == "dot_general" and "GPTLM" not in p:
            assert FUSED_CE in p, p


def test_flash_kernels_keep_the_module_directly_before_pallas_call(
        gpt_paths):
    # the adjacency benchmark/metrics/flash_roofline.json selects by
    flash = [p for p in gpt_paths
             if "GPTLM" in p and "pallas_call" in p.split("/")]
    assert len(flash) >= 4  # two layers, forward and backward
    for p in flash:
        assert re.search(r"CausalSelfAttention_\d+/pallas_call", p), p


def test_every_psum_of_the_dp_step_is_under_grad_sync(dp_paths):
    psums = [p for p in dp_paths if primitive(p) == "psum"]
    assert psums
    assert not [p for p in psums if GRAD_SYNC not in p.split("/")]
    # gradients (inside the optimizer, under sync_sgd), model state
    # and loss (outside it): both kinds are there
    assert any(OPT_UPDATE in p for p in psums)
    assert any(OPT_UPDATE not in p for p in psums)


def test_dp_step_optimizer_is_under_opt_update(dp_paths):
    outside_model = [p for p in dp_paths if not MODEL.search(p)
                     and primitive(p) in ("add", "mul", "sub")]
    assert outside_model
    assert not [p for p in outside_model
                if OPT_UPDATE not in p and GRAD_SYNC not in p]


def test_bucketed_all_reduce_carries_grad_sync():
    paths = dp_resnet_paths(sync_sgd_bucketed)
    sync = [p for p in paths if GRAD_SYNC in p.split("/")]
    # the whole function: the concatenate and the slices round the pmean
    assert {"psum", "concatenate"} <= {primitive(p) for p in sync}
    assert not [p for p in paths if primitive(p) == "psum"
                and GRAD_SYNC not in p]


def test_dp_replicated_step_carries_grad_sync_and_opt_update(gpt):
    loss_fn, tx, params, opt_state, _ = gpt
    mesh = data_mesh(4, devices=jax.devices()[:4])
    tokens = jax.ShapeDtypeStruct((4, 128), jnp.int32)
    paths = scope_paths(
        build_dp_replicated_train_step(loss_fn, tx, mesh), params,
        opt_state, tokens)
    psums = [p for p in paths if primitive(p) == "psum"]
    assert psums and not [p for p in psums if GRAD_SYNC not in p]
    # gradients agree BEFORE the update here: the sync is beside the
    # optimizer's scope, not inside it
    assert not [p for p in psums if OPT_UPDATE in p]
    assert any(OPT_UPDATE in p and primitive(p) == "sqrt" for p in paths)
    assert any(FUSED_CE in p and "pallas_call" in p.split("/")
               for p in paths)


def test_vocab_sharded_head_is_under_fused_ce():
    # parallel/vocab_ce.py drives the same kernels per vocab shard
    import numpy as np
    from jax.sharding import Mesh

    from kungfu_tpu.parallel.vocab_ce import vocab_sharded_fused_ce

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((512,), jnp.float32)
    t = jax.ShapeDtypeStruct((64,), jnp.int32)
    paths = scope_paths(
        jax.grad(lambda x, w, b, t: vocab_sharded_fused_ce(
            x, w, b, t, mesh=mesh), argnums=(0, 1, 2)), x, w, b, t)
    kernels = [p for p in paths if primitive(p) == "pallas_call"]
    assert len(kernels) >= 2  # forward and backward
    assert not [p for p in kernels if FUSED_CE not in p]
    matmuls = [p for p in paths if primitive(p) == "dot_general"]
    assert matmuls and not [p for p in matmuls if FUSED_CE not in p]


# -- the latent-attention / expert model's four scopes ------------------------


@pytest.fixture(scope="module")
def glm_paths():
    """`models/glm_moe.py`'s step at a tiny size with every ratio kept:
    flash attention (nope + rope == v), one dense block, one expert
    block holding 2 of 8 experts, the MTP module, per-block
    recomputation, the bias' sgd beside adamw, the GSPMD builder."""
    from kungfu_tpu.models.glm_moe import (GlmMoeConfig, GlmMoeLM,
                                           glm_moe_fused_loss,
                                           glm_moe_optimizer)

    cfg = GlmMoeConfig(
        vocab_size=512, hidden_size=128, num_heads=2, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8,
        v_head_dim=32, intermediate_size=256, moe_intermediate_size=64,
        n_routed_experts=8, num_experts_per_tok=2, num_layers=2,
        held=(0, 2), dtype=jnp.bfloat16, attention="flash", remat=True)
    model = GlmMoeLM(cfg)
    tokens = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 16), jnp.int32))["params"])
    tx = glm_moe_optimizer(optax.adamw(1e-4), 0.001)
    step = build_gspmd_train_step(
        lambda p, t: glm_moe_fused_loss(model, p, t), tx, has_aux=True)
    return scope_paths(step, params, jax.eval_shape(tx.init, params),
                       tokens, calls=("cond",))


@pytest.mark.parametrize("scope, forward, backward", [
    # what must lie under each name, forward and backward
    (MLA, {"pallas_call", "dot_general", "cos"},
     {"pallas_call", "dot_general"}),
    (MOE_ROUTE, {"dot_general", "top_k", "sort", "gather"},
     {"gather", "dot_general"}),
    (MOE_EXPERTS, {"ragged_dot_general", "dot_general", "logistic"},
     {"ragged_dot_general", "dot_general"}),
    (MTP, {"pallas_call", "ragged_dot_general", "top_k", "concatenate"},
     {"pallas_call", "ragged_dot_general"}),
])
def test_glm_scopes_hold_their_layers(glm_paths, scope, forward,
                                      backward):
    under = [p for p in glm_paths if scope in re.split(r"[/()]", p)]
    fwd = {primitive(p) for p in under if "transpose(" not in p}
    bwd = {primitive(p) for p in under if "transpose(" in p}
    assert forward <= fwd, sorted(fwd)
    assert backward <= bwd, sorted(bwd)


def switches_stand_outside(paths):
    """The expert layers' switches over the row buffer's rungs (a share
    of the experts held: more than one rung), forward and backward:
    what a branch runs carries `kf.moe_route` or `kf.moe_experts`,
    opened INSIDE the branches, and the switch itself neither, so that
    a reader that adds up a trace's events under a name counts leaves
    only (`benchmark/trace_reduce.py` counts an event that spans its
    body with the body: PERF.md section 7 B (l))."""
    names = {MOE_ROUTE, MOE_EXPERTS}
    conds = [p for p in paths if primitive(p) == "cond" and "/moe/" in p]
    assert {"transpose(" in p for p in conds} == {False, True}, conds
    assert not [p for p in conds if names & set(re.split(r"[/()]", p))]
    rows = [p for p in paths if "/moe/" in p
            and primitive(p) in ("gather", "ragged_dot_general")]
    assert rows and not [p for p in rows
                         if not names & set(re.split(r"[/()]", p))]


def test_glm_switches_carry_neither_expert_scope(glm_paths):
    switches_stand_outside(glm_paths)


def test_glm_flash_kernels_sit_directly_under_the_attention_module(
        glm_paths):
    # the adjacency benchmark/metrics/mla_flash_roofline.json selects by
    flash = [p for p in glm_paths if "pallas_call" in p.split("/")
             and FUSED_CE not in p]
    assert len(flash) >= 6  # three attention layers, forward and backward
    for p in flash:
        assert re.search(r"MLAttention_\d+/pallas_call", p), p
        assert MLA in re.split(r"[/()]", p), p
    # both heads go through the fused kernel, outside kf.mtp
    ce = [p for p in glm_paths if FUSED_CE in p and "pallas_call" in p]
    assert ce and not [p for p in ce if MTP in p]


def test_glm_expert_matmuls_are_nowhere_else(glm_paths):
    grouped = [p for p in glm_paths
               if primitive(p) == "ragged_dot_general"]
    assert grouped
    assert not [p for p in grouped
                if MOE_EXPERTS not in re.split(r"[/()]", p)]
    # the optimizer's arithmetic, the bias' sgd included, stays under
    # kf.opt_update and out of the model's scopes
    outside_model = [p for p in glm_paths if not MODEL.search(p)]
    assert outside_model
    assert not [p for p in outside_model
                if OPT_UPDATE not in p.split("/")]
