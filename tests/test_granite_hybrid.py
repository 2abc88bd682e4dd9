"""`models/granite_hybrid.py` against the plain reference
(`models/granite_hybrid_reference.py`, its state-space layers one
position at a time): on the CPU at small widths with the published
shape kept — Mamba-2 mixers of several heads sharing one group of B and
C, a position-less attention layer with query heads on fewer K/V heads,
T past several chunks and no multiple of the chunk, the four
multipliers, a tied head.
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
import pytest
from jax._src.ad_checkpoint import remat_p, saved_residuals

from kungfu_tpu.models import granite_hybrid_reference as ref
from kungfu_tpu.models.granite_hybrid import (
    ATTENTION, MAMBA, GraniteHybridConfig, GraniteHybridLM, causal_conv,
    granite_fused_loss, granite_logits, layer_plan)
from kungfu_tpu.ops import flash as flash_ops
from kungfu_tpu.ops.flash import FLASH_LSE, FLASH_OUT
from kungfu_tpu.ops.ssd import ssd
from kungfu_tpu.parallel import granite_hybrid_rules, shard_params
from kungfu_tpu.parallel import rules as R
from kungfu_tpu.trace.scopes import FUSED_CE, SSD, SSM

from test_device_scopes import primitive, scope_paths
from test_glm_moe import kernel_calls, rel_err

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PATTERN = (MAMBA, ATTENTION, MAMBA)


def small(**kw):
    base = dict(
        vocab_size=256, hidden_size=64, num_heads=4, num_kv_heads=2,
        layer_types=PATTERN, intermediate_size=96, mamba_n_heads=8,
        mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=16,
        dtype=jnp.float32)
    base.update(kw)
    return GraniteHybridConfig(**base)


def ref_cfg(c):
    return dict(
        num_attention_heads=c.num_heads, num_key_value_heads=c.num_kv_heads,
        layer_types=c.layer_types, mamba_n_heads=c.mamba_n_heads,
        mamba_d_head=c.mamba_d_head, mamba_d_state=c.mamba_d_state,
        rms_norm_eps=c.rms_norm_eps,
        residual_multiplier=c.residual_multiplier,
        attention_multiplier=c.attention_multiplier,
        embedding_multiplier=c.embedding_multiplier,
        logits_scaling=c.logits_scaling)


def tokens_for(c, shape=(2, 56), seed=1):
    # 56 = three chunks of 16 and a part of a fourth
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                              c.vocab_size)


def init(c, tokens, seed=0):
    """Seeded weights with every vector leaf moved off its initial
    values (norm scales, dt_bias, D, A_log), so that it matters."""
    params = GraniteHybridLM(c).init(jax.random.PRNGKey(seed),
                                     tokens)["params"]
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
        for x, k in zip(leaves, keys)])


def ref_logits(params, tokens, cfg):
    return jnp.stack([ref.reference_logits(params, ids, cfg, segment=8,
                                           q_block=8) for ids in tokens])


# -- (a) the model against the reference --------------------------------------


@pytest.fixture(scope="module")
def f32_case():
    c = small()
    tokens = tokens_for(c)
    return c, tokens, init(c, tokens)


def test_logits_match_the_reference_in_f32(f32_case):
    c, tokens, params = f32_case
    logits = granite_logits(GraniteHybridLM(c), params, tokens)
    want = ref_logits(params, tokens, ref_cfg(c))
    assert logits.shape == (*tokens.shape, c.vocab_size)
    np.testing.assert_allclose(logits, want, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("attention", ["local", "flash"])
@pytest.mark.parametrize("remat", [False, True], ids=["kept", "remat"])
def test_loss_and_every_gradient_match_the_reference_in_f32(
        f32_case, attention, remat):
    """Two Mamba-2 layers and an attention layer, 4 query heads on 2
    K/V heads, T 56 over four chunks of 16: f32 against f32, summation
    order only. The loss takes the fused rows' path (its reference
    fallback at hidden 64), the tied head's gradient adds into the
    embedding's."""
    c, tokens, params = f32_case
    c = dataclasses.replace(c, attention=attention, remat=remat)
    loss, grads = jax.value_and_grad(
        lambda p: granite_fused_loss(GraniteHybridLM(c), p, tokens))(params)
    (want, _), want_grads = jax.value_and_grad(
        lambda p: ref.reference_loss(p, tokens, ref_cfg(c), segment=8,
                                     q_block=8), has_aux=True)(params)
    assert abs(float(loss) - float(want)) < 1e-5
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    want_flat = jax.tree_util.tree_leaves(want_grads)
    assert len(flat) == len(want_flat)
    for (path, g), w in zip(flat, want_flat):
        assert rel_err(g, w) < 2e-4, (jax.tree_util.keystr(path),
                                      rel_err(g, w))


def test_bf16_compute_stays_near_the_f32_reference(f32_case):
    c, tokens, params = f32_case
    logits = granite_logits(
        GraniteHybridLM(dataclasses.replace(c, dtype=jnp.bfloat16)),
        params, tokens)
    want = ref_logits(params, tokens, ref_cfg(c))
    assert rel_err(logits, want) < 0.03


def test_the_fused_kernels_take_the_tied_head():
    """Hidden 128: `fused_cross_entropy_rows`' kernels (interpret mode
    here) on the table transposed, against the plain tied head's CE;
    the table's gradient is the lookup's and the head's together."""
    c = small(hidden_size=128, num_heads=2, num_kv_heads=1, mamba_n_heads=4,
              mamba_d_head=64, vocab_size=384)
    tokens = tokens_for(c, (1, 48))
    params = init(c, tokens)
    model = GraniteHybridLM(c)

    def plain(p):
        logits = granite_logits(model, p, tokens)[:, :-1]
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
        return jnp.mean(lse - picked)

    loss, g = jax.value_and_grad(
        lambda p: granite_fused_loss(model, p, tokens))(params)
    want, g_want = jax.value_and_grad(plain)(params)
    assert "lm_head" not in params
    assert abs(float(loss) - float(want)) < 2e-2   # bf16 logits inside
    table = g["embed_tokens"]["embedding"]
    assert rel_err(table, g_want["embed_tokens"]["embedding"]) < 0.05
    # rows of tokens the sequence never holds move through the head alone
    unseen = np.setdiff1d(np.arange(c.vocab_size), np.asarray(tokens))
    assert float(jnp.abs(table[unseen]).max()) > 0


# -- (b) what each piece of the equations does --------------------------------


@pytest.mark.parametrize("key, neutral", [
    ("embedding_multiplier", 1.0),
    ("residual_multiplier", 1.0),
    ("attention_multiplier", 16 ** -0.5),
    ("logits_scaling", 1.0),
])
def test_each_multiplier_is_applied(f32_case, key, neutral):
    """The reference with the multiplier set to what dropping it would
    leave (1, or 1/sqrt(d) for attention) is far from the model; the
    reference as configured is the model."""
    c, tokens, params = f32_case
    logits = granite_logits(GraniteHybridLM(c), params, tokens)
    dropped = ref_logits(params, tokens, {**ref_cfg(c), key: neutral})
    assert rel_err(logits, dropped) > 1e-2
    np.testing.assert_allclose(
        logits, ref_logits(params, tokens, ref_cfg(c)), rtol=1e-4,
        atol=2e-5)


def test_the_layer_pattern_is_read_from_layer_types():
    for kinds in (PATTERN, (ATTENTION, MAMBA, MAMBA, ATTENTION)):
        c = small(layer_types=kinds)
        params = GraniteHybridLM(c).init(
            jax.random.PRNGKey(0), tokens_for(c))["params"]
        assert [("mamba" in params[f"Block_{i}"],
                 "self_attn" in params[f"Block_{i}"])
                for i in range(len(kinds))] == [
            (k == MAMBA, k == ATTENTION) for k in kinds]
        assert set(params) == {f"Block_{i}" for i in range(len(kinds))} | {
            "embed_tokens", "norm"}
    with pytest.raises(ValueError, match="layer_types"):
        small(layer_types=(MAMBA, "linear_attention"))


def test_the_head_is_the_embedding_table(f32_case):
    """Logits are the normed state against the table's rows, over
    `logits_scaling`: moving one row of the table moves that token's
    logit everywhere."""
    c, tokens, params = f32_case
    model = GraniteHybridLM(c)
    hidden = model.apply({"params": params}, tokens)
    table = params["embed_tokens"]["embedding"]
    np.testing.assert_allclose(
        granite_logits(model, params, tokens),
        jnp.einsum("bth,vh->btv", hidden, table) / c.logits_scaling,
        rtol=1e-5, atol=1e-6)
    unseen = int(np.setdiff1d(np.arange(c.vocab_size),
                              np.asarray(tokens))[0])
    moved = jax.tree_util.tree_map(lambda x: x, params)
    moved["embed_tokens"]["embedding"] = table.at[unseen].add(1.0)
    delta = (granite_logits(model, moved, tokens)
             - granite_logits(model, params, tokens))
    np.testing.assert_allclose(
        delta[..., unseen], hidden.sum(-1) / c.logits_scaling, rtol=1e-4,
        atol=1e-5)
    others = np.delete(np.asarray(delta), unseen, axis=-1)
    assert float(np.abs(others).max()) < 1e-5


def test_no_position_sees_a_later_one(f32_case):
    """Changing the token at position 30 changes no logit before it:
    the conv, the scan and attention are all causal."""
    c, tokens, params = f32_case
    model = GraniteHybridLM(c)
    other = tokens.at[:, 30].set((tokens[:, 30] + 1) % c.vocab_size)
    a = granite_logits(model, params, tokens)
    b = granite_logits(model, params, other)
    np.testing.assert_allclose(a[:, :30], b[:, :30], rtol=0, atol=1e-6)
    assert float(jnp.abs(a[:, 30:] - b[:, 30:]).max()) > 1e-3


def test_the_conv_sees_three_earlier_positions_and_its_own():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 10, 3))
    kernel = jax.random.normal(jax.random.PRNGKey(1), (4, 3))
    bias = jnp.arange(3.0)
    out = causal_conv(x, kernel, bias)
    for t in range(10):
        want = bias + sum(kernel[3 - k] * x[0, t - k]
                          for k in range(4) if t - k >= 0)
        np.testing.assert_allclose(out[0, t], want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("chunk", [16, 256])
def test_the_init_carries_state_across_chunks(chunk):
    """At the initial step sizes and decay rates (the Mamba-2 authors':
    `_dt_bias_init`, `_a_log_init`), at 64 heads as published, some
    heads keep a large share of their state over a whole chunk, of 16
    as the tests and the rehearsal twin run it or of 256 as the cell
    does: the state that enters a chunk moves its outputs, so every
    comparison with the reference sees the pass between chunks."""
    c = small(mamba_n_heads=64, mamba_d_head=2, mamba_chunk_size=chunk)
    p = GraniteHybridLM(c).init(jax.random.PRNGKey(0), tokens_for(c))[
        "params"]["Block_0"]["mamba"]
    dt = jnp.broadcast_to(jax.nn.softplus(p["dt_bias"]), (1, 2 * chunk, 64))
    A = -jnp.exp(p["A_log"])
    kept = jnp.exp(chunk * dt[0, 0] * A)
    assert int((kept > 0.1).sum()) >= 2 and int((kept > 1e-3).sum()) >= 8
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(k[0], (1, 2 * chunk, 64, 2))
    B, C = (jax.random.normal(key, (1, 2 * chunk, 16)) for key in k[1:])
    D = jnp.zeros((64,))
    y, _ = ssd(x, dt, A, B, C, D, chunk=chunk)
    # the second chunk run alone, entering with no state
    alone, _ = ssd(x[:, chunk:], dt[:, chunk:], A, B[:, chunk:],
                   C[:, chunk:], D, chunk=chunk)
    assert rel_err(y[:, chunk:], alone) > 0.1


def test_config_checks_the_mixer_widths():
    with pytest.raises(ValueError, match="mamba_expand"):
        small(mamba_n_heads=4)
    with pytest.raises(ValueError, match="one group"):
        small(mamba_n_groups=2)


# -- (c) recomputation: what a block keeps ------------------------------------


def flash_case(**kw):
    c = small(attention="flash", remat=True, hidden_size=128, num_heads=2,
              num_kv_heads=1, mamba_n_heads=4, mamba_d_head=64,
              layer_types=(MAMBA, ATTENTION, MAMBA, ATTENTION), **kw)
    tokens = tokens_for(c, (1, 256))
    model = GraniteHybridLM(c)
    return c, tokens, lambda p: granite_fused_loss(model, p, tokens)


@pytest.fixture(scope="module")
def flash_params():
    c, tokens, _ = flash_case()
    return init(c, tokens)


def test_recomputed_blocks_run_no_flash_forward_twice(flash_params):
    """Two attention layers (T 256, d 64: the resident loops): with the
    policy that keeps `kf.flash_out` and `kf.flash_lse` each forward
    kernel runs once, outside the checkpoints, and the backward kernels
    inside; the fused head + CE's kernels beside them."""
    _, _, loss = flash_case()
    calls = kernel_calls(jax.make_jaxpr(jax.grad(loss))(flash_params).jaxpr)
    flash = [(k, inside) for k, inside in calls if hasattr(flash_ops, k)]
    first = [k for k, inside in flash if remat_p.name not in inside]
    again = [k for k, inside in flash if remat_p.name in inside]
    assert sorted(first) == ["_fwd_res_kernel"] * 2, calls
    assert again and not [k for k in again if "fwd" in k], calls


def test_layer_plan_is_what_jax_keeps(flash_params):
    """Each of the four blocks keeps its input, each attention block
    flash's output and lse as well, and the SSD keeps nothing past its
    own backward."""
    c, tokens, loss = flash_case()
    plan = layer_plan(c, *tokens.shape)
    assert plan["kept"] == ("input", FLASH_OUT, FLASH_LSE)
    assert plan["layers"] == c.layer_types
    state = tokens.size * c.hidden_size * 4
    assert plan["kept_bytes_per_block"] == state
    assert plan["kept_bytes"] == 2 * state + 2 * plan[
        "kept_bytes_per_attention_block"]
    res = saved_residuals(loss, flash_params)
    assert sum(f"named '{FLASH_LSE}'" in why for _, why in res) == 2
    # what the model's own file leaves to the backward: the four blocks'
    # inputs, flash's output and lse in both attention blocks, and the
    # final norm's input (no block's: that norm is not recomputed)
    model_file = sum(a.size * a.dtype.itemsize for a, why in res
                     if "models/granite_hybrid.py" in why)
    assert model_file == plan["kept_bytes"] + state
    assert plan["ssd"]["chunks"] == 16
    assert plan["ssd"]["fwd"]["form"] == plan["ssd"]["bwd"]["form"] == (
        "pallas_fused")
    kept = layer_plan(dataclasses.replace(c, remat=False), *tokens.shape)
    assert kept["kept"] == () and kept["kept_bytes"] == 0


# -- (d) the rules table and the names a trace reader selects by --------------


def test_rules_table_covers_every_leaf_and_splits_what_it_says():
    from kungfu_tpu.analysis.shard_rules import check_coverage, check_mesh

    registered = {"granite_hybrid": R.REGISTRY["granite_hybrid"]}
    assert check_coverage(registered) == []
    assert check_mesh(registered) == []
    c = small()
    params = init(c, tokens_for(c))
    specs = R.plan(granite_hybrid_rules(), params, {"data": 1, "model": 2})
    flat = {R.path_str(p): s for p, s in
            jax.tree_util.tree_flatten_with_path(specs)[0]}
    assert len(flat) == len(jax.tree_util.tree_leaves(params))
    split = {p for p, s in flat.items() if "model" in str(s)}
    assert split == {
        "Block_1/self_attn/q_proj/kernel", "Block_1/self_attn/k_proj/kernel",
        "Block_1/self_attn/v_proj/kernel", "Block_1/self_attn/o_proj/kernel",
        *(f"Block_{i}/shared_mlp/{m}/kernel" for i in range(3)
          for m in ("gate", "up", "down"))}
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    placed = shard_params(params, mesh, granite_hybrid_rules())
    assert jax.tree_util.tree_structure(placed) == \
        jax.tree_util.tree_structure(params)


@pytest.fixture(scope="module")
def granite_paths():
    """The step at a tiny size: the SSD, flash attention, the fused
    head + CE (hidden 128), recomputation, adamw, the GSPMD builder."""
    import optax

    from kungfu_tpu.parallel import build_gspmd_train_step

    c, _, _ = flash_case(dtype=jnp.bfloat16)
    model = GraniteHybridLM(c)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 16), jnp.int32))["params"])
    tx = optax.adamw(1e-4)
    step = build_gspmd_train_step(
        lambda p, t: granite_fused_loss(model, p, t), tx)
    return scope_paths(step, params, jax.eval_shape(tx.init, params),
                       jax.ShapeDtypeStruct((1, 256), jnp.int32))


def _under(paths, scope):
    return [p for p in paths if scope in re.split(r"[/()]", p)]


def test_ssm_scope_holds_the_mamba_sublayer_both_ways(granite_paths):
    under = _under(granite_paths, SSM)
    fwd = {primitive(p) for p in under if "transpose(" not in p}
    bwd = {primitive(p) for p in under if "transpose(" in p}
    # in_proj / out_proj, the norms, the conv's SiLU and softplus, the
    # decays
    assert {"dot_general", "rsqrt", "logistic", "exp", "log1p"} <= fwd, \
        sorted(fwd)
    assert {"dot_general", "exp"} <= bwd, sorted(bwd)
    assert not [p for p in under if "self_attn" in p or FUSED_CE in p]


def test_ssd_scope_nests_inside_the_ssm_scope(granite_paths):
    inner = _under(granite_paths, SSD)
    # the two kernels and the running sums, forward and backward
    assert {primitive(p) for p in inner} >= {"pallas_call", "cumsum"}
    assert {"transpose(" in p for p in inner} == {False, True}
    assert set(inner) <= set(_under(granite_paths, SSM))
    # what is outside the SSD in the mixer: the projections
    outside = set(_under(granite_paths, SSM)) - set(inner)
    assert [p for p in outside if "in_proj" in p]


def test_every_flash_kernel_sits_under_the_attention_module(granite_paths):
    """Flash's kernels under the attention module; the other kernels are
    the fused head + CE's and the SSD's, which sit inside the Mamba
    sublayer's scope: the forward kernel in the forward and its
    recomputation, the backward kernel in the backward's own work."""
    kernels = [p for p in granite_paths if primitive(p) == "pallas_call"]
    scan = _under(kernels, SSD)
    assert scan and set(scan) <= set(_under(kernels, SSM))
    recomputed = [p for p in scan if "rematted_computation" in p]
    backward = [p for p in scan if "transpose(" in p and p not in recomputed]
    assert recomputed and backward
    flash = [p for p in kernels if FUSED_CE not in p and p not in scan]
    assert flash and [p for p in kernels if FUSED_CE in p]
    for p in flash:
        assert "NoPEAttention_0/pallas_call" in p or (
            "self_attn/pallas_call" in p), p
        assert SSM not in p


# -- (e) the cell's rehearsal twin through the benchmark's command ------------


@pytest.mark.parametrize("seconds", [2, 30])
def test_rehearsal_twin_holds_its_limits_at_any_length(tmp_path, seconds):
    """The tiny twin computes in f32, so its reference readings are
    summation order alone whatever number of steps a busy CPU fits into
    the window: the checks hold at 2 s and at 30 s alike."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "granite-4.0-h-micro.train-b1-t8192", "--seed", "3000000011",
         "--seconds", str(seconds), "--trace", "0", "--rehearse", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(line) for line in out.stdout.splitlines()
             if line.startswith("{")]
    result = lines[-1]
    assert result["correct"] is False  # a rehearsal never is
    assert result["failed"] == 0 and result["attempted"] > 0
    window = next(x for x in lines if x.get("phase") == "window")
    assert all(window["checks"].values()), window["checks"]
    assert {"reference_loss", "reference_gradients"} <= set(window["checks"])
    reference = next(x for x in lines if x.get("phase") == "reference")
    assert len(reference["grad_rel_err"]) == 3
    plan = next(x for x in lines if x.get("phase") == "plan")
    assert plan["layer_plan"]["layers"] == ["mamba", "mamba", "attention",
                                            "mamba"]
    assert plan["ssd_plan"]["chunks"] == 4
