"""`models/ouro.py` against the plain reference
(`models/ouro_reference.py`), on the CPU at small widths with the
published shape kept: heads of one size and no grouping, a SwiGLU wider
than the hidden state, an untied head, a stack of blocks run four times
on one set of weights with the exit gate read after every pass.
"""

import dataclasses
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax._src.ad_checkpoint import remat_p, saved_residuals

from kungfu_tpu.models import ouro
from kungfu_tpu.models import ouro_reference as ref
from kungfu_tpu.models.ouro import (OuroConfig, OuroLM, RotaryAttention,
                                    exit_distribution, loop_plan,
                                    ouro_forward, ouro_fused_loss,
                                    ouro_logits)
from kungfu_tpu.ops import flash
from kungfu_tpu.ops.flash import FLASH_LSE, FLASH_OUT
from kungfu_tpu.ops.fused_ce_rows import (fused_cross_entropy_rows,
                                          reference_cross_entropy_rows)
from kungfu_tpu.parallel import (build_gspmd_train_step, ouro_rules,
                                 shard_params)
from kungfu_tpu.parallel import rules as R
from kungfu_tpu.trace.scopes import FUSED_CE, LOOP_EXIT, LOOP_STACK

from test_device_scopes import primitive, scope_paths
from test_glm_moe import kernel_calls, leaves_with_names, rel_err

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small(**kw):
    base = dict(vocab_size=256, hidden_size=64, num_heads=4, head_dim=16,
                intermediate_size=160, num_layers=2, dtype=jnp.float32)
    base.update(kw)
    return OuroConfig(**base)


def ref_cfg(c):
    return dict(num_attention_heads=c.num_heads, head_dim=c.head_dim,
                num_hidden_layers=c.num_layers,
                total_ut_steps=c.total_ut_steps, rope_theta=c.rope_theta,
                rms_norm_eps=c.rms_norm_eps, entropy_beta=c.entropy_beta)


def tokens_for(c, shape=(2, 32), seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                              c.vocab_size)


def init(c, tokens, seed=0, gate=0.3):
    """Seeded parameters; the exit gate, which the model initialises to
    zero, gets random values so that p is no constant."""
    params = OuroLM(c).init(jax.random.PRNGKey(seed), tokens)["params"]
    if gate:
        k = jax.random.PRNGKey(seed + 100)
        params = {**params, "exit_gate": {
            "kernel": gate * jax.random.normal(k, (c.hidden_size, 1)),
            "bias": jnp.array([0.2])}}
    return params


@pytest.fixture(scope="module")
def f32_case():
    c = small()
    tokens = tokens_for(c)
    return c, tokens, init(c, tokens)


# -- (a) the forward pass: every pass's logits, lambda, p ---------------------


def test_every_passes_logits_lambda_and_p_match_the_reference(f32_case):
    c, tokens, params = f32_case
    with jax.default_matmul_precision("highest"):
        logits, lam = ouro_forward(OuroLM(c), params, tokens)
        last = ouro_logits(OuroLM(c), params, tokens)
    want_logits, want_lam, want_p = ref.reference_logits(
        params, tokens, ref_cfg(c))
    assert logits.shape == (4, 2, 32, c.vocab_size)
    np.testing.assert_allclose(logits, jnp.swapaxes(want_logits, 0, 1),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(lam, jnp.swapaxes(want_lam, 0, 1),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(last, logits[-1])
    # the passes differ: the loop is no fixed point at random weights
    assert float(jnp.abs(logits[0] - logits[-1]).max()) > 0.1
    _, gate_logits = OuroLM(c).apply({"params": params}, tokens)
    p, entropy = exit_distribution(gate_logits)
    np.testing.assert_allclose(p, jnp.swapaxes(want_p, 0, 1), rtol=1e-5,
                               atol=1e-7)
    np.testing.assert_allclose(
        entropy, -(want_p * jnp.log(want_p)).sum(axis=1), rtol=1e-5)


@pytest.mark.parametrize("passes", [1, 2, 4, 6])
def test_p_sums_to_one_and_a_zero_gate_halves_what_is_left(passes):
    z = 3 * jax.random.normal(jax.random.PRNGKey(passes), (passes, 5, 7))
    p, entropy = exit_distribution(z)
    np.testing.assert_allclose(p.sum(axis=0), 1.0, rtol=1e-6)
    assert bool((p >= 0).all()) and bool((entropy >= 0).all())
    p0, h0 = exit_distribution(jnp.zeros((passes, 3)))
    want = [0.5 ** (t + 1) for t in range(passes - 1)]
    want.append(0.5 ** (passes - 1))  # the last pass takes the rest
    np.testing.assert_allclose(p0[:, 0], want, rtol=1e-6)
    if passes == 4:  # 0.5, 0.25, 0.125, 0.125: 1.75 bits
        assert float(h0[0]) == pytest.approx(1.75 * np.log(2), rel=1e-6)
    # a gate that is certain one way or the other breaks nothing
    sure, h = exit_distribution(jnp.full((passes, 2), 80.0).at[:, 1]
                                .set(-80.0))
    assert bool(jnp.isfinite(sure).all()) and bool(jnp.isfinite(h).all())
    assert float(sure[0, 0]) == pytest.approx(1.0)
    assert float(sure[-1, 1]) == pytest.approx(1.0)


def test_an_untrained_model_starts_at_half_quarter_eighth_eighth():
    c = small()
    tokens = tokens_for(c)
    params = init(c, tokens, gate=0)
    _, metrics = ouro_fused_loss(OuroLM(c), params, tokens)
    np.testing.assert_allclose(metrics["exit_p"],
                               [0.5, 0.25, 0.125, 0.125], rtol=1e-6)
    assert float(metrics["exit_entropy"]) == pytest.approx(
        1.75 * np.log(2), rel=1e-6)


# -- (b) objective and gradients ----------------------------------------------


@pytest.mark.parametrize("remat", [False, True], ids=["kept", "remat"])
def test_objective_and_gradients_match_the_reference_in_f32(f32_case,
                                                            remat):
    c, tokens, params = f32_case
    model = OuroLM(dataclasses.replace(c, remat=remat))
    with jax.default_matmul_precision("highest"):
        (loss, metrics), grads = jax.jit(jax.value_and_grad(
            lambda p: ouro_fused_loss(model, p, tokens),
            has_aux=True))(params)
    (want, want_metrics), want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.reference_loss(p, tokens, ref_cfg(c), remat=remat),
        has_aux=True))(params)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    for key in ("ce", "exit_p", "exit_entropy"):
        np.testing.assert_allclose(metrics[key], want_metrics[key],
                                   rtol=1e-5)
    assert metrics["ce"].shape == metrics["exit_p"].shape == (4,)
    for (name, got), (_, exp) in zip(leaves_with_names(grads),
                                     leaves_with_names(want_grads)):
        assert rel_err(got, exp) < 2e-5, name
    # the exit path is alive: the gate has a gradient of its own
    assert float(jnp.abs(grads["exit_gate"]["kernel"]).max()) > 1e-4


def test_a_shared_layers_gradient_is_the_sum_over_four_untied_copies(
        f32_case):
    """The mechanism against plain autodiff: unroll the network with
    FOUR untied copies of the stack (the reference's equations, a copy
    a pass, all four holding the same values), differentiate with
    respect to each copy, and add. Every leaf of the looped model's
    stack must equal that sum; so must the gate's and the head's, which
    are read after every pass too."""
    c, tokens, params = f32_case
    rc = ref_cfg(c)
    shared = ("stack", "exit_gate", "lm_head")

    def untied(copies, embed):
        with jax.default_matmul_precision("highest"):
            per_seq = []
            for ids in tokens:
                x = embed["embedding"][ids]
                ces, lam = [], []
                for copy in copies:   # pass t reads copy t alone
                    for i in range(c.num_layers):
                        x = ref.block(copy["stack"][f"Block_{i}"], x, rc,
                                      512, False)
                    x = ref.rms_norm(
                        x, copy["stack"]["final_norm"]["scale"],
                        c.rms_norm_eps)
                    ces.append(ref.cross_entropy_rows(
                        x[:-1], copy["lm_head"], ids[1:], 2048, False))
                    gate = copy["exit_gate"]
                    lam.append(jax.nn.sigmoid(
                        (x @ gate["kernel"])[:, 0] + gate["bias"][0]))
                p = ref.exit_distribution(jnp.stack(lam)[:, :-1])
                entropy = -jnp.sum(p * jnp.log(p), axis=0)
                per_seq.append(jnp.mean(
                    jnp.sum(p * jnp.stack(ces), axis=0)
                    - c.entropy_beta * entropy))
            return sum(per_seq) / len(per_seq)

    copies = [{k: params[k] for k in shared}] * c.total_ut_steps
    value, per_copy = jax.jit(jax.value_and_grad(untied))(
        copies, params["embed"])
    assert len(per_copy) == 4
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *per_copy)
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.jit(jax.value_and_grad(
            lambda p: ouro_fused_loss(OuroLM(c), p, tokens),
            has_aux=True))(params)
    assert float(loss) == pytest.approx(float(value), rel=2e-6)
    for (name, got), (_, exp) in zip(
            leaves_with_names({k: grads[k] for k in shared}),
            leaves_with_names(summed)):
        assert rel_err(got, exp) < 2e-5, name
    # and no single use carries it: even the first pass, which every
    # later CE reaches back to and which has most of it, is short of the
    # sum by far more than the tolerance above
    q = lambda g: g["stack"]["Block_0"]["RotaryAttention_0"][  # noqa: E731
        "q"]["kernel"]
    shares = [rel_err(q(g), q(summed)) for g in per_copy]
    assert min(shares) > 0.05, shares
    # the last pass's gate enters nothing
    assert float(jnp.abs(per_copy[-1]["exit_gate"]["kernel"]).max()) == 0


def test_bf16_compute_stays_near_the_f32_reference():
    c = small(dtype=jnp.bfloat16)
    tokens = tokens_for(c)
    params = init(c, tokens)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p: ouro_fused_loss(OuroLM(c), p, tokens),
        has_aux=True))(params)
    (want, want_metrics), want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.reference_loss(p, tokens, ref_cfg(c)),
        has_aux=True))(params)
    assert float(loss) == pytest.approx(float(want), abs=0.03)
    np.testing.assert_allclose(metrics["ce"], want_metrics["ce"],
                               atol=0.05)
    np.testing.assert_allclose(metrics["exit_p"], want_metrics["exit_p"],
                               atol=0.01)
    for (name, got), (_, exp) in zip(leaves_with_names(grads),
                                     leaves_with_names(want_grads)):
        assert got.dtype == jnp.float32, name
        assert rel_err(got, exp) < 0.15, name
    # f32 where the model says: the gate's logit and the states' dtype
    states, gate_logits = OuroLM(c).apply({"params": params}, tokens)
    assert states.dtype == jnp.bfloat16
    assert gate_logits.dtype == jnp.float32


# -- (c) the head + CE of every row, on the fused kernels ---------------------


@pytest.mark.parametrize("n, v, sure", [(96, 384, 0.0), (200, 1000, 0.0),
                                        (200, 1000, 4.0)],
                         ids=["tiles", "padded", "sure-rows"])
def test_row_cross_entropy_kernels_match_plain_xla(n, v, sure):
    """`fused_cross_entropy_rows` through the kernels (H a multiple of
    128, interpret mode) against f32 logits: every row's value, and the
    gradients under a cotangent that differs row by row, which is what
    the mean-returning `fused_cross_entropy` cannot take. `sure` pulls
    every row towards its target's column until its CE is ~0.003: there
    the kernel's `p - 1` from bf16 logits is 3.5 times off (so is
    `fused_cross_entropy(residual=True)`'s), and the target column set
    from the row's own f32 loss is not."""
    h = 128
    kx, kw, kt, kr = jax.random.split(jax.random.PRNGKey(n), 4)
    w = jax.random.normal(kw, (h, v)) * h ** -0.5
    t = jax.random.randint(kt, (n,), 0, v)
    x = jax.random.normal(kx, (n, h)) + 4 * sure * w[:, t].T
    r = jax.random.uniform(kr, (n,))

    def run(fn):
        return jax.jit(jax.value_and_grad(
            lambda x, w: (lambda rows: ((rows * r).sum(), rows))(
                fn(x, w, t)), argnums=(0, 1), has_aux=True))(x, w)

    (_, rows), (dx, dw) = run(fused_cross_entropy_rows)
    with jax.default_matmul_precision("highest"):
        (_, want), (dx_want, dw_want) = run(reference_cross_entropy_rows)
    assert rows.shape == (n,) and rows.dtype == jnp.float32
    if sure:
        assert float(want.mean()) < 0.01
    # bf16 operands, f32 accumulation
    np.testing.assert_allclose(rows, want, atol=0.03)
    assert rel_err(dx, dx_want) < 0.02 and rel_err(dw, dw_want) < 0.02
    # H that does not tile takes the plain path, exactly
    x64, w64 = x[:, :64], w[:64]
    np.testing.assert_array_equal(
        fused_cross_entropy_rows(x64, w64, t),
        reference_cross_entropy_rows(x64, w64, t))


# -- (d) attention through the flash kernels at head size 128 -----------------


@pytest.mark.parametrize("scheme, seq", [(None, 512), ("resident", 1024)],
                         ids=["head", "resident"])
def test_flash_at_head_size_128_matches_the_plain_path(monkeypatch, scheme,
                                                       seq):
    """The published head size, rotary included, through
    `flash_attention` in interpret mode against the plain path on the
    same parameters: by the head kernels and, forced, by the resident
    loops over several blocks with the ONE fused backward kernel behind
    them, which is what the cell's T 4096 runs."""
    if scheme is None:  # what the cell's call reads, unforced
        cell = flash.flash_plan(4096, 128, dtype=jnp.bfloat16, causal=True)
        assert (cell["block_q"], cell["block_k"]) == (1024, 512)
        assert cell["fwd"] == {"scheme": "resident", "visited_blocks": 20,
                               "masked_blocks": 20, "grid_blocks": 32}
        assert cell["bwd"]["scheme"] == "stream_fused"
        assert cell["bwd"]["block_matmuls"] == 5
        assert cell["bwd"]["vmem_bytes"] <= flash._BWD_STREAM_VMEM_LIMIT
    monkeypatch.setattr(flash, "_FORCE_SCHEME", scheme)
    plan = flash.flash_plan(seq, 128, causal=True)
    assert plan["fwd"]["scheme"] == (scheme or "head")
    assert plan["nq"] == seq // 256
    assert plan["bwd"]["scheme"] == ("stream_fused" if scheme else "head")
    c = small(hidden_size=128, num_heads=2, head_dim=128)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, seq, c.hidden_size))
    params = RotaryAttention(c).init(jax.random.PRNGKey(8), x)["params"]

    def run(attention):
        mod = RotaryAttention(dataclasses.replace(c, attention=attention))
        return jax.jit(jax.value_and_grad(
            lambda p: (mod.apply({"params": p}, x) ** 2).sum()))(params)

    (plain, g_plain), (fused, g_flash) = run("local"), run("flash")
    assert float(fused) == pytest.approx(float(plain), rel=1e-5)
    for (name, a), (_, b) in zip(leaves_with_names(g_flash),
                                 leaves_with_names(g_plain)):
        assert rel_err(a, b) < 1e-4, name
    # and against the reference's own attention and rotary
    with jax.default_matmul_precision("highest"):
        want = ref.attention(params, x[0], ref_cfg(c), 128, False)
        got = RotaryAttention(dataclasses.replace(
            c, attention="flash")).apply({"params": params}, x)[0]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


# -- (e) recomputation inside the loop ----------------------------------------


def flash_case(seq=512, **kw):
    """Two blocks, four passes, through the flash kernels (T 512: the
    head scheme), each layer application recomputed. At hidden 64 the
    head + CE takes its plain path, so every `pallas_call` in the
    program is flash's."""
    c = small(**{**dict(attention="flash", remat=True, hidden_size=64,
                        num_heads=2, head_dim=32), **kw})
    tokens = tokens_for(c, (1, seq))
    model = OuroLM(c)
    return c, tokens, lambda p: ouro_fused_loss(model, p, tokens)[0]


@pytest.fixture(scope="module")
def flash_params():
    c, tokens, _ = flash_case()
    return init(c, tokens)


@pytest.mark.parametrize("policy", ["names", "bare"])
@pytest.mark.parametrize("scheme, seq, fwd, bwd", [
    (None, 512, "_fwd_head_kernel", "_bwd_head_kernel"),
    # past the head kernels (the cell's T 4096, or forced): the resident
    # forward and ONE backward kernel an application, where a dq + dkv
    # pair ran until PR 33
    ("resident", 1024, "_fwd_res_kernel", "_bwd_stream_kernel"),
    (None, 4096, "_fwd_res_kernel", "_bwd_stream_kernel"),
], ids=["head", "resident", "t4096"])
def test_recomputation_runs_flash_forward_once_an_application(
        monkeypatch, flash_params, scheme, seq, fwd, bwd, policy):
    monkeypatch.setattr(flash, "_FORCE_SCHEME", scheme)
    if policy == "bare":  # `jax.checkpoint` with no policy keeps no name
        monkeypatch.setattr(ouro, "_KEPT", ())
    c, _, loss = flash_case(seq)
    applications = c.num_layers * c.total_ut_steps
    assert loop_plan(c, 1, seq)["layer_applications"] == applications == 8
    calls = kernel_calls(
        jax.make_jaxpr(jax.grad(loss))(flash_params).jaxpr)
    recomputed = [k for k, inside in calls if remat_p.name in inside]
    first = [k for k, inside in calls if remat_p.name not in inside]
    assert first == [fwd] * applications
    again = [fwd] if policy == "bare" else []
    assert sorted(recomputed) == sorted((again + [bwd]) * applications)


@pytest.mark.parametrize("case, names", [
    ("flash", ("input", FLASH_OUT, FLASH_LSE)),
    ("local", ("input",)),
    ("kept", ()),
])
def test_loop_plan_is_what_jax_keeps(monkeypatch, flash_params, case,
                                     names):
    """`loop_plan` against `saved_residuals`: each of the 8 layer
    applications keeps its input and, through the kernels, flash's
    output and lse, and NOTHING else beyond what the same program keeps
    with no name asked for."""
    kw = {"local": dict(attention="local"), "kept": dict(remat=False)}
    c, tokens, loss = flash_case(**kw.get(case, {}))
    plan = loop_plan(c, *tokens.shape)
    assert plan["kept"] == names
    assert plan["layer_applications"] == 8 and plan["head_ce_calls"] == 4
    assert plan["kept_bytes"] == 8 * plan["kept_bytes_per_application"]
    layer = sum(x.size for x in jax.tree_util.tree_leaves(
        flash_params["stack"]["Block_0"]))
    assert plan["shared_grad_bytes"] == 4 * (2 * layer + c.hidden_size)
    if not names:
        assert plan["kept_bytes"] == 0
        return
    res = saved_residuals(loss, flash_params)
    assert sum(f"named '{FLASH_LSE}'" in why for _, why in res) == (
        8 if FLASH_LSE in names else 0)

    def held(res):
        return sorted((a.str_short(), a.size * a.dtype.itemsize)
                      for a, why in res if "from the argument" not in why)

    # against the same program keeping no name: the difference is the
    # two names' bytes, 8 applications of them
    monkeypatch.setattr(ouro, "_KEPT", ())
    bare = held(saved_residuals(loss, flash_params))
    extra = held(res)
    for item in bare:
        extra.remove(item)
    state = tokens.size * c.hidden_size * 4
    named = plan["kept_bytes_per_application"] - state
    assert sum(size for _, size in extra) == 8 * named
    # and the inputs: 8 arrays of the state's size among what both keep
    inputs = [size for text, size in bare
              if size == state and "float32[1,512,64]" in text]
    assert len(inputs) >= 8


# -- (f) the rules table ------------------------------------------------------


def test_rules_table_covers_every_leaf_and_splits_what_it_says():
    from kungfu_tpu.analysis.shard_rules import check_coverage, check_mesh

    registered = {"ouro": R.REGISTRY["ouro"]}
    assert check_coverage(registered) == []
    assert check_mesh(registered) == []
    c = small()
    params = init(c, tokens_for(c))
    specs = R.plan(ouro_rules(), params, {"data": 1, "model": 2})
    flat = {R.path_str(p): s for p, s in
            jax.tree_util.tree_flatten_with_path(specs)[0]}
    assert len(flat) == len(jax.tree_util.tree_leaves(params))
    split = {p for p, s in flat.items() if "model" in str(s)}
    assert split == {
        f"stack/Block_{i}/{leaf}" for i in range(c.num_layers)
        for leaf in ("RotaryAttention_0/q/kernel",
                     "RotaryAttention_0/k/kernel",
                     "RotaryAttention_0/v/kernel",
                     "RotaryAttention_0/o/kernel", "mlp/gate/kernel",
                     "mlp/up/kernel", "mlp/down/kernel")}
    # on the one-chip mesh the adapter builds, placement is a no-op
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    placed = shard_params(params, mesh, ouro_rules())
    assert jax.tree_util.tree_structure(placed) == \
        jax.tree_util.tree_structure(params)


# -- (g) the two scopes -------------------------------------------------------


@pytest.fixture(scope="module")
def ouro_paths():
    """The step at a tiny size: flash attention, the fused head + CE
    (hidden 128), recomputation, adamw, the GSPMD builder."""
    c = small(vocab_size=512, hidden_size=128, num_heads=2, head_dim=64,
              intermediate_size=256, dtype=jnp.bfloat16,
              attention="flash", remat=True)
    model = OuroLM(c)
    tokens = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 16), jnp.int32))["params"])
    tx = optax.adamw(1e-4)
    step = build_gspmd_train_step(
        lambda p, t: ouro_fused_loss(model, p, t), tx, has_aux=True)
    return scope_paths(step, params, jax.eval_shape(tx.init, params),
                       tokens)


@pytest.mark.parametrize("scope, forward, backward", [
    (LOOP_STACK, {"pallas_call", "dot_general", "cos", "rsqrt"},
     {"pallas_call", "dot_general"}),
    (LOOP_EXIT, {"dot_general", "log1p", "exp", "cumsum"},
     {"dot_general", "mul"}),
])
def test_loop_scopes_hold_their_layers(ouro_paths, scope, forward,
                                       backward):
    under = [p for p in ouro_paths if scope in re.split(r"[/()]", p)]
    fwd = {primitive(p) for p in under if "transpose(" not in p}
    bwd = {primitive(p) for p in under if "transpose(" in p}
    assert forward <= fwd, sorted(fwd)
    assert backward <= bwd, sorted(bwd)


def test_flash_kernels_sit_directly_under_the_attention_module(ouro_paths):
    # the adjacency benchmark/metrics/loop_flash_roofline.json selects by
    kernels = [p for p in ouro_paths if "pallas_call" in p.split("/")]
    flash_calls = [p for p in kernels if FUSED_CE not in p]
    assert len(flash_calls) >= 4  # two blocks, forward and backward
    for p in flash_calls:
        assert re.search(r"RotaryAttention_\d+/pallas_call", p), p
        assert LOOP_STACK in re.split(r"[/()]", p), p
    # the heads go through the fused kernels, outside both loop scopes
    ce = [p for p in kernels if FUSED_CE in p]
    assert ce and not [p for p in ce if LOOP_STACK in p or LOOP_EXIT in p]
    # every matmul of a block is inside a pass
    matmuls = [p for p in ouro_paths if primitive(p) == "dot_general"
               and "Block_" in p]
    assert matmuls and not [p for p in matmuls if LOOP_STACK not in p]


# -- (h) the cell's rehearsal twin through the benchmark's command ------------


def test_rehearsal_twin_runs_through_the_benchmark_command(tmp_path):
    """Control flow and finite numbers, not a limit read at however
    many steps a busy CPU fits into the window (PERF.md section 7 B
    (h)): the three reference checks are THERE and their readings are
    finite."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "ouro-2.6b.train-b1-t4096", "--seed", "3000000007",
         "--seconds", "2", "--trace", "0", "--rehearse", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(line) for line in out.stdout.splitlines()
             if line.startswith("{")]
    result = lines[-1]
    assert result["correct"] is False  # a rehearsal never is
    assert result["failed"] == 0 and result["attempted"] > 0
    window = next(x for x in lines if x.get("phase") == "window")
    assert {"reference_objective", "reference_exit_distribution",
            "reference_gradients"} <= set(window["checks"])
    assert window["checks"]["losses_finite"]
    assert window["checks"]["no_compile_in_window"]
    reference = next(x for x in lines if x.get("phase") == "reference")
    readings = [*reference["loss_abs_err"].values(),
                *reference["exit_abs_err"].values(),
                *reference["grad_rel_err"].values()]
    assert "lm_head" in reference["grad_rel_err"]  # `_rows_bwd`'s dW
    assert len(readings) == 5 + 4 + 4
    assert all(np.isfinite(r) for r in readings), reference
    plan = next(x for x in lines if x.get("phase") == "plan")
    assert plan["loop_plan"]["layer_applications"] == 2 * 4
    assert plan["loop_plan"]["head_ce_calls"] == 4
    counters = next(x for x in lines if x.get("phase") == "counters")
    assert len(counters["ce"]) == len(counters["exit_p"]) == 4
    assert sum(counters["exit_p"]) == pytest.approx(1.0, abs=1e-4)
