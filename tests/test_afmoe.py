"""`models/afmoe.py` against the plain reference
(`models/afmoe_reference.py`), and `ops/flash.py`'s grouped K/V heads
and windows against plain attention on repeated K/V: on the CPU at
small widths with the published shape kept — query heads on fewer K/V
heads, sliding and full layers mixed, T past the window so that the
window cuts, a dense block then expert blocks holding a share of a
wider router, an untied head.
"""

from collections import Counter
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

from kungfu_tpu.models import afmoe
from kungfu_tpu.models import afmoe_reference as ref
from kungfu_tpu.models.afmoe import (FULL, SLIDING, AfmoeConfig, AfmoeLM,
                                     GlobalAttention, LocalAttention,
                                     afmoe_fused_loss, afmoe_logits,
                                     layer_plan, visible_pairs)
from kungfu_tpu.models.glm_moe import (ROUTER_BIAS, ExpertFFN,
                                       glm_moe_optimizer)
from kungfu_tpu.ops import flash
from kungfu_tpu.ops.flash import FLASH_LSE, FLASH_OUT
from kungfu_tpu.parallel import (afmoe_rules, build_gspmd_train_step,
                                 shard_params)
from kungfu_tpu.parallel import grouped_moe as gm
from kungfu_tpu.parallel.grouped_moe import MOE_ROUTED
from kungfu_tpu.parallel import rules as R
from kungfu_tpu.trace.scopes import (ATTN_GLOBAL, ATTN_LOCAL, FUSED_CE,
                                     MOE_EXPERTS, MOE_ROUTE)

from test_device_scopes import (primitive, scope_paths,
                                switches_stand_outside)
from test_glm_moe import (kernel_calls, leaves_with_names, one_rung,
                          rel_err, routed_conds, sub_jaxprs)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small(**kw):
    base = dict(
        vocab_size=256, hidden_size=64, num_heads=4, num_kv_heads=2,
        head_dim=16, sliding_window=24,
        layer_types=(SLIDING, FULL, SLIDING, FULL), num_dense_layers=1,
        intermediate_size=160, moe_intermediate_size=48,
        n_routed_experts=16, num_experts_per_tok=4, held=(2, 4),
        dtype=jnp.float32)
    base.update(kw)
    return AfmoeConfig(**base)


def ref_cfg(c):
    return dict(
        num_attention_heads=c.num_heads, num_key_value_heads=c.num_kv_heads,
        sliding_window=c.sliding_window, layer_types=c.layer_types,
        num_dense_layers=c.num_dense_layers,
        num_experts_per_tok=c.num_experts_per_tok,
        route_scale=c.routed_scaling_factor, rope_theta=c.rope_theta,
        rms_norm_eps=c.rms_norm_eps, mup_enabled=c.mup_enabled,
        held=c.held)


def tokens_for(c, shape=(2, 64), seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                              c.vocab_size)


def init(c, tokens, seed=0):
    """Seeded weights with every vector leaf (norm scales, selection
    biases) moved off its initial ones and zeros, so that it matters."""
    params = AfmoeLM(c).init(jax.random.PRNGKey(seed), tokens)["params"]
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
        for x, k in zip(leaves, keys)])


# -- (a) the model against the reference --------------------------------------


@pytest.fixture(scope="module")
def f32_case():
    c = small()
    tokens = tokens_for(c)
    return c, tokens, init(c, tokens)


def test_logits_and_counts_match_the_reference_in_f32(f32_case):
    c, tokens, params = f32_case
    assert tokens.shape[1] > c.sliding_window  # the window cuts
    logits, aux = afmoe_logits(AfmoeLM(c), params, tokens)
    with jax.default_matmul_precision("highest"):
        _, want = ref.reference_loss(params, tokens, ref_cfg(c))
    assert logits.shape == (*tokens.shape, c.vocab_size)
    assert (aux["counts"] == want["counts"]).all()
    assert aux["counts"].shape == (3, c.n_routed_experts)
    assert int(aux["dropped"].sum()) == 0


@pytest.mark.parametrize("attention", ["local", "flash"])
@pytest.mark.parametrize("remat", [False, True], ids=["kept", "remat"])
def test_loss_and_every_gradient_match_the_reference_in_f32(
        f32_case, attention, remat):
    """Both kinds of layer, a dense and three expert layers, 4 query
    heads on 2 K/V heads, T 64 past a window of 24: f32 against f32,
    summation order only."""
    c, tokens, params = f32_case
    c = dataclasses.replace(c, attention=attention, remat=remat)
    (loss, m), g = jax.jit(jax.value_and_grad(
        lambda p: afmoe_fused_loss(AfmoeLM(c), p, tokens),
        has_aux=True))(params)
    (want, parts), g_want = jax.jit(jax.value_and_grad(
        lambda p: ref.reference_loss(p, tokens, ref_cfg(c), q_block=16,
                                     row_block=16, remat=remat),
        has_aux=True))(params)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    assert float(m["ce"]) == pytest.approx(float(parts["ce"]), rel=2e-6)
    assert (m["counts"] == parts["counts"]).all()
    for (name, got), (_, exp) in zip(leaves_with_names(g),
                                     leaves_with_names(g_want)):
        if ROUTER_BIAS in name:
            continue  # no gradient's business: test (f)
        assert rel_err(got, exp) < 3e-5, name


def test_bf16_compute_stays_near_the_f32_reference():
    """bf16 matmuls and residual stream, f32 statistics, gate, router
    and loss, through the flash kernels and the fused head. The band's
    reason: one bf16 rounding is 2^-9 relative; the loss averages 254
    rows of ~6.2 whose logits each carry some dozens of roundings and
    reads a few 1e-3; a gradient leaf sums products of rounded
    activations through four sandwich-normed blocks and reads 1-4% of
    its norm, more where a token's top-k flips."""
    c = small(dtype=jnp.bfloat16, vocab_size=512, hidden_size=128,
              head_dim=32, intermediate_size=320,
              moe_intermediate_size=96, attention="flash", remat=True)
    tokens = tokens_for(c, (2, 128))
    params = init(c, tokens)
    (loss, m), g = jax.jit(jax.value_and_grad(
        lambda p: afmoe_fused_loss(AfmoeLM(c), p, tokens),
        has_aux=True))(params)
    (want, _), g_want = jax.jit(jax.value_and_grad(
        lambda p: ref.reference_loss(p, tokens, ref_cfg(c), q_block=32),
        has_aux=True))(params)
    assert abs(float(loss) - float(want)) < 1e-2
    for path in (("embed", "embedding"), ("lm_head",),
                 ("Block_0", "mlp", "down", "kernel"),
                 ("Block_0", "LocalAttention_0", "gate", "kernel"),
                 ("Block_1", "GlobalAttention_0", "k", "kernel"),
                 ("Block_1", "GlobalAttention_0", "q_norm", "scale")):
        got, exp = g, g_want
        for key in path:
            got, exp = got[key], exp[key]
        assert rel_err(got, exp) < 0.08, path


def test_recomputation_changes_neither_loss_nor_gradients(f32_case):
    c, tokens, params = f32_case

    def run(remat):
        cfg = dataclasses.replace(c, attention="flash", remat=remat)
        return jax.jit(jax.value_and_grad(
            lambda p: afmoe_fused_loss(AfmoeLM(cfg), p, tokens)[0]))(params)

    loss, grads = run(False)
    loss_r, grads_r = run(True)
    assert float(loss) == pytest.approx(float(loss_r), rel=1e-6)
    for (name, got), (_, exp) in zip(leaves_with_names(grads_r),
                                     leaves_with_names(grads)):
        assert rel_err(got, exp) < 1e-5, name


@pytest.mark.parametrize("remat", [False, True], ids=["kept", "remat"])
def test_the_ladder_changes_neither_loss_nor_gradients(monkeypatch,
                                                       f32_case, remat):
    """The whole model on the ladder of row buffers (4 of 16 experts
    held: rungs 256 and 512 for 128 tokens, and every layer of this
    step fits the first) against the worst-case buffer alone, with and
    without the per-block recomputation."""
    c, tokens, params = f32_case
    assert gm.row_ladder(tokens.size, 4, c.held, 16) == (256, 512)

    def run():
        cfg = dataclasses.replace(c, remat=remat)
        return jax.jit(jax.value_and_grad(    # a new function a run
            lambda p: afmoe_fused_loss(AfmoeLM(cfg), p, tokens),
            has_aux=True))(params)

    (loss, metrics), grads = run()
    one_rung(monkeypatch)
    (loss_w, metrics_w), grads_w = run()
    assert metrics["rung_rows"].tolist() == [256] * 3
    assert metrics_w["rung_rows"].tolist() == [512] * 3
    assert (metrics["held_assignments"] <= 256).all()
    assert (metrics["held_assignments"] == metrics_w["held_assignments"]).all()
    assert int(metrics["dropped"].sum()) == 0
    assert float(loss) == float(loss_w)
    for (name, got), (_, exp) in zip(leaves_with_names(grads),
                                     leaves_with_names(grads_w)):
        if "['w_" in name:    # an expert stack's: a sum over the rows
            assert rel_err(got, exp) <= 1e-6, name
        else:
            np.testing.assert_array_equal(got, exp, err_msg=name)


# -- (b) the shares add up to the uncut layer ---------------------------------


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """Guide section 4, at this model's routing (top-4 of 16, scale
    2.826): the routed parts the eight shares give, with the shared
    expert (which every chip computes alike) counted once, are the
    uncut reference's layer output."""
    c = small(held=(0, 16))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, c.hidden_size))
    whole = ExpertFFN(c).init(jax.random.PRNGKey(3), x)["params"]
    whole[ROUTER_BIAS] = 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                                 (16,))
    flat = x.reshape(-1, c.hidden_size)
    with jax.default_matmul_precision("highest"):
        uncut, counts = ref.expert_ffn(whole, flat, ref_cfg(c), False)
        shared = ref.swiglu(*(whole["shared"][k]["kernel"]
                              for k in ("gate", "up", "down")), flat)
        routed = jnp.zeros_like(uncut)
        for share in range(8):
            held = (2 * share, 2)
            mine = {**whole, **{k: whole[k][held[0]:held[0] + 2]
                                for k in ("w_gate", "w_up", "w_down")}}
            y, aux = ExpertFFN(dataclasses.replace(c, held=held)).apply(
                {"params": mine}, x)
            assert int(aux["dropped"]) == 0
            assert (aux["counts"] == counts).all()  # the router is whole
            routed += y.reshape(-1, c.hidden_size) - shared
    np.testing.assert_allclose(routed + shared, uncut, rtol=2e-5,
                               atol=2e-5)
    assert int(counts.sum()) == flat.shape[0] * c.num_experts_per_tok


# -- (c) flash with grouped K/V heads against plain attention -----------------


def _qkv(t, h, h_kv, d, dtype=jnp.float32, b=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, t, h, d), dtype)
    k = jax.random.normal(ks[1], (b, t, h_kv, d), dtype)
    v = jax.random.normal(ks[2], (b, t, h_kv, d), dtype)
    w = jax.random.normal(ks[3], (b, t, h, d), jnp.float32)
    return q, k, v, w


def _plain_on_repeated(q, k, v, window):
    from kungfu_tpu.parallel.sequence import _local_attention

    g = q.shape[2] // k.shape[2]
    return _local_attention(q, jnp.repeat(k, g, axis=2),
                            jnp.repeat(v, g, axis=2), causal=True,
                            window=window)


@pytest.mark.parametrize(
    "t, h, h_kv, window, blocks, force, fwd, bwd", [
        (512, 4, 2, None, None, None, "head", "head"),
        (512, 8, 2, None, (128, 128), "resident", "resident",
         "stream_fused"),
        (512, 4, 1, None, (256, 128), "stream", "stream", "stream_fused"),
        (512, 4, 2, 100, (128, 128), "resident", "resident",
         "resident_fused"),
        (512, 4, 2, 100, (128, 128), "stream", "stream", "resident_fused"),
        (512, 4, 2, 100, (256, 128), "stream", "stream", "stream"),
        (300, 4, 2, None, None, None, "resident", "stream_fused"),
        (100, 4, 2, 37, None, None, "resident", "resident_fused"),
    ], ids=["head", "resident+fused", "stream+fused", "window-resident",
            "window-stream-narrowed", "window-stream-m2", "one-block",
            "one-block-window"])
def test_grouped_flash_matches_plain_attention_on_repeated_kv(
        monkeypatch, t, h, h_kv, window, blocks, force, fwd, bwd):
    """Output, dq, dk and dv of every scheme a grouped call can take
    (interpret mode), causal with and without a window: query head h
    reads K/V head h // group through the block specs' index maps, and
    dk, dv are the sums over a group's query heads."""
    monkeypatch.setattr(flash, "_FORCE_SCHEME", force)
    bq, bk = blocks or (None, None)
    plan = flash.flash_plan(t, 32, causal=True, window=window, block_q=bq,
                            block_k=bk, q_per_kv=h // h_kv)
    assert plan["fwd"]["scheme"] == fwd and plan["bwd"]["scheme"] == bwd
    assert plan["kv_group"]["q_per_kv"] == h // h_kv
    q, k, v, w = _qkv(t, h, h_kv, 32)

    def grads(fn):
        return jax.value_and_grad(
            lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum(),
            argnums=(0, 1, 2))(q, k, v)

    got = flash.flash_attention(q, k, v, causal=True, window=window,
                                block_q=bq, block_k=bk)
    want = _plain_on_repeated(q, k, v, window)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    (_, g), (_, g_want) = (
        grads(lambda q, k, v: flash.flash_attention(
            q, k, v, causal=True, window=window, block_q=bq, block_k=bk)),
        grads(lambda q, k, v: _plain_on_repeated(q, k, v, window)))
    for name, a, b in zip("qkv", g, g_want):
        assert a.shape == b.shape == (q if name == "q" else k).shape
        assert rel_err(a, b) < 2e-5, f"d{name}"


@pytest.mark.parametrize("window", [None, 50], ids=["causal", "window"])
def test_grouped_call_of_a_non_tiling_length_takes_the_fallback(window):
    t = 1100  # over 1024 and no multiple of 128: no tile
    assert flash.flash_plan(t, 16, causal=True, window=window,
                            q_per_kv=2) == {"scheme": "plain"}
    q, k, v, w = _qkv(t, 4, 2, 16, b=1)
    fn = lambda q, k, v: flash.flash_attention(  # noqa: E731
        q, k, v, causal=True, window=window)
    np.testing.assert_allclose(fn(q, k, v), _plain_on_repeated(
        q, k, v, window), rtol=2e-5, atol=2e-5)
    g = jax.grad(lambda q, k, v: (fn(q, k, v) * w).sum(),
                 argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(lambda q, k, v: (_plain_on_repeated(
        q, k, v, window) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_want):
        assert a.shape == b.shape and rel_err(a, b) < 2e-5


def test_grouped_bf16_partials_are_summed_in_f32():
    """dk and dv leave the kernels one a query head in the input dtype
    and `_unbh_kv` sums a group's in f32: against the f32 call the bf16
    one stands where one bf16 rounding a partial puts it."""
    q, k, v, w = _qkv(512, 8, 1, 32, jnp.bfloat16)

    def dkv(q, k, v):
        return jax.grad(lambda q, k, v: (flash.flash_attention(
            q, k, v, causal=True).astype(jnp.float32) * w).sum(),
            argnums=(1, 2))(q, k, v)

    got = dkv(q, k, v)
    want = dkv(*(x.astype(jnp.float32) for x in (q, k, v)))
    for a, b in zip(got, want):
        assert a.dtype == jnp.bfloat16 and a.shape == k.shape
        assert rel_err(a.astype(jnp.float32), b) < 1e-2


def test_kv_heads_must_divide_the_query_heads():
    q, k, v, _ = _qkv(128, 4, 3, 16)
    with pytest.raises(ValueError, match="H_kv dividing H"):
        flash.flash_attention(q, k, v, causal=True)
    with pytest.raises(ValueError, match="H_kv dividing H"):
        jax.grad(lambda q: flash.flash_attention(
            q, k[:, :, :2], v, causal=True).sum())(q)


# -- (d) the window's edge, and positions -------------------------------------


def _attention_case(cls, window, t=48, **kw):
    c = small(sliding_window=window, **kw)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, t, c.hidden_size))
    params = cls(c).init(jax.random.PRNGKey(8), x)["params"]
    return c, x, params


@pytest.mark.parametrize("attention", ["local", "flash"])
def test_window_minus_one_is_the_references_window(attention):
    """`flash_attention`'s `window` counts the keys BEFORE self and the
    source's `sliding_window` counts self too: the model passes
    `sliding_window - 1`, and that is exactly the reference's `0 <= i -
    j < sliding_window`; one key more or less is another output."""
    c, x, params = _attention_case(LocalAttention, 9, attention=attention)
    got = LocalAttention(c).apply({"params": params}, x)[0]
    with jax.default_matmul_precision("highest"):
        want, more, less = (ref.attention(
            params, x[0], {**ref_cfg(c), "sliding_window": w}, True, 16,
            False) for w in (9, 10, 8))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # rows past the window's reach: another window is another output
    assert float(jnp.abs(got[12:] - more[12:]).max()) > 1e-3
    assert float(jnp.abs(got[12:] - less[12:]).max()) > 1e-3
    # the first rows see fewer keys than any of the three windows
    np.testing.assert_allclose(got[:8], more[:8], rtol=2e-5, atol=2e-5)
    assert layer_plan(c, 1, 48)["window"] == 8
    assert visible_pairs(48, 8) == sum(min(i + 1, 9) for i in range(48))


def test_full_layers_carry_no_positions_and_sliding_ones_relative_ones():
    """A full layer's last row is the same whatever order the earlier
    tokens stand in (no positions at all), a sliding layer's is not
    (rotary); a sliding layer's rows read only relative positions: the
    suffix of a sequence, run alone from position 0, gives the rows
    whose window lies inside it again."""
    order = jnp.concatenate([jnp.arange(46)[::-1], jnp.arange(46, 48)])
    c, x, params = _attention_case(GlobalAttention, 64)
    full = GlobalAttention(c).apply({"params": params}, x)
    np.testing.assert_allclose(
        GlobalAttention(c).apply({"params": params}, x[:, order])[0, -1],
        full[0, -1], rtol=2e-5, atol=2e-5)
    c, x, params = _attention_case(LocalAttention, 64)   # sees all 48
    local = LocalAttention(c).apply({"params": params}, x)
    assert float(jnp.abs(LocalAttention(c).apply(
        {"params": params}, x[:, order])[0, -1] - local[0, -1]).max()) > 1e-3
    c, x, params = _attention_case(LocalAttention, 9)
    whole = LocalAttention(c).apply({"params": params}, x)
    alone = LocalAttention(c).apply({"params": params}, x[:, 16:])
    np.testing.assert_allclose(alone[0, 8:], whole[0, 24:], rtol=2e-5,
                               atol=2e-5)
    assert float(jnp.abs(alone[0, :8] - whole[0, 16:24]).max()) > 1e-3


# -- (e) the plans ------------------------------------------------------------

def _per_kernel(scheme, visited, grid):
    return {"scheme": scheme, "visited_blocks": visited,
            "masked_blocks": visited, "grid_blocks": grid}


def test_the_cells_two_calls_plan_as_perf_md_says():
    """T 8192, d 128, bf16, 32 query heads on 4: the full layers as any
    window-less call (one fused backward kernel; their plan, key for
    key, is among test_flash_skip.py's cells that must not move), the
    sliding layers (window 2047) on SQUARE 512 x 512 tiles with the
    forward on the resident loops, 70 of 256 blocks, and ONE fused
    backward over the same 70 (PR 37) — where PR 34's parent's 1024 x
    512 put dq and dkv on the streaming grid and the dkv, which narrows
    only at square tiles, walked all 128 steps a head (PERF.md section
    6, PR 34)."""
    kw = dict(dtype=jnp.bfloat16, causal=True, q_per_kv=8)
    full = flash.flash_plan(8192, 128, **kw)
    local = flash.flash_plan(8192, 128, window=2047, **kw)
    group = {"q_per_kv": 8, "kv_read": "index_map", "dkv_sum": "xla_f32",
             "dkv_partial_bytes": 2 * 8 * 8192 * 128 * 2}
    assert full["kv_group"] == local["kv_group"] == group
    assert full["bwd"]["scheme"] == "stream_fused"
    assert (local["block_q"], local["block_k"]) == (512, 512)
    for which in ("fwd", "dq", "dkv"):
        assert local[which] == _per_kernel("resident", 70, 256)
    assert local["bwd"] == {
        "scheme": "resident_fused", "block_q": 512, "block_k": 512,
        "visited_blocks": 70, "masked_blocks": 70, "grid_blocks": 256,
        "block_matmuls": 5, "vmem_bytes": 22675456}
    # a windowed call stays square at the other head sizes too
    for d in (64, 256):
        p = flash.flash_plan(8192, d, dtype=jnp.bfloat16, causal=True,
                             window=2047)
        assert p["block_q"] == p["block_k"], d


def test_layer_plan_counts_what_the_issue_says():
    c = AfmoeConfig(layer_types=AfmoeConfig.layer_types[:8], held=(0, 8),
                    vocab_size=25024, attention="flash", remat=True)
    plan = layer_plan(c, 1, 8192)
    assert plan["layers"] == (
        ("sliding", "dense"), ("sliding", "dense"), ("sliding", "expert"),
        ("full", "expert")) + (("sliding", "expert"),) * 3 + (
        ("full", "expert"),)
    assert plan["window"] == 2047
    assert plan["visible_pairs"] == {"full": 33558528, "sliding": 14681088}
    assert plan["kept"] == ("input", FLASH_OUT, FLASH_LSE, MOE_ROUTED)
    assert plan["kept_bytes_per_block"] == (
        8192 * 2048 * 2 + 8192 * 32 * 128 * 2 + 8192 * 32 * 4)
    # the six expert blocks keep their routed output too
    assert plan["kept_bytes_per_expert_block"] == (
        plan["kept_bytes_per_block"] + 8192 * 2048 * 2)
    assert plan["kept_bytes"] == (2 * plan["kept_bytes_per_block"]
                                  + 6 * plan["kept_bytes_per_expert_block"])


# -- (f) recomputation keeps flash's two names through both kinds of call -----


def flash_case(dtype=jnp.float32, **kw):
    c = small(dtype=dtype, attention="flash", remat=True, head_dim=32,
              **kw)
    tokens = tokens_for(c, (1, 512))
    model = AfmoeLM(c)
    return c, tokens, lambda p: afmoe_fused_loss(model, p, tokens)[0]


@pytest.fixture(scope="module")
def flash_params():
    c, tokens, _ = flash_case()
    return init(c, tokens)


def test_recomputed_blocks_run_no_flash_forward_twice(flash_params):
    """Two sliding layers (window 23: the resident loops, forward, dq
    and dkv) and two full ones (the head kernels): with the policy that
    keeps `kf.flash_out` and `kf.flash_lse` every forward kernel runs
    once, outside the checkpoints, and the backward kernels inside."""
    c, _, loss = flash_case()
    calls = kernel_calls(
        jax.make_jaxpr(jax.grad(loss))(flash_params).jaxpr)
    first = [k for k, inside in calls if remat_p.name not in inside]
    again = [k for k, inside in calls if remat_p.name in inside]
    assert sorted(first) == ["_fwd_head_kernel"] * 2 + [
        "_fwd_res_kernel"] * 2
    assert sorted(again) == ["_bwd_head_kernel"] * 2 + [
        "_dkv_res_kernel"] * 2 + ["_dq_res_kernel"] * 2


@pytest.mark.parametrize("kept, switches", [(True, 6), (False, 9)])
def test_recomputed_blocks_run_no_routed_forward_twice(
        monkeypatch, flash_params, kept, switches):
    """Three expert blocks, two rungs each: with `kf.moe_routed` kept a
    step runs a forward and a backward switch a block (the backward
    rebuilds the rung it needs) and the recomputed forward none; without
    the name the fourth norm's backward makes it run the routed path a
    third time."""
    if not kept:
        monkeypatch.setattr(afmoe, "_KEPT", (FLASH_OUT, FLASH_LSE))
    c, tokens, _ = flash_case()
    model = AfmoeLM(c)
    jaxpr = jax.make_jaxpr(jax.grad(    # a new function a case
        lambda p: afmoe_fused_loss(model, p, tokens)[0]))(flash_params)
    conds = [e for e in routed_conds(jaxpr.jaxpr) if any(
        q.primitive.name == "ragged_dot_general"
        for b in e.params["branches"] for q in sub_jaxprs(b.jaxpr))]
    assert len(conds) == switches


@pytest.mark.parametrize("case, names", [
    ("flash", ("input", FLASH_OUT, FLASH_LSE, MOE_ROUTED)),
    ("local", ("input", MOE_ROUTED)),
    ("kept", ()),
])
def test_layer_plan_is_what_jax_keeps(monkeypatch, flash_params, case,
                                      names):
    """`layer_plan` against `saved_residuals`: each of the four blocks
    keeps its input and, through the kernels of either kind of call,
    flash's output and lse, each of the three expert blocks its routed
    output, and NOTHING else beyond what the same program keeps with no
    name asked for."""
    kw = {"local": dict(attention="local"), "kept": dict(remat=False)}
    c, tokens, _ = flash_case()
    c = dataclasses.replace(c, **kw.get(case, {}))
    model = AfmoeLM(c)
    loss = lambda p: afmoe_fused_loss(model, p, tokens)[0]  # noqa: E731
    plan = layer_plan(c, *tokens.shape)
    assert plan["kept"] == names
    assert plan["kept_bytes"] == (plan["kept_bytes_per_block"]
                                  + 3 * plan["kept_bytes_per_expert_block"])
    if not names:
        assert plan["kept_bytes"] == 0
        return
    res = saved_residuals(loss, flash_params)
    assert sum(f"named '{FLASH_LSE}'" in why for _, why in res) == (
        4 if FLASH_LSE in names else 0)

    def held(res):
        return Counter((a.str_short(), a.size * a.dtype.itemsize)
                       for a, why in res if "from the argument" not in why)

    monkeypatch.setattr(afmoe, "_KEPT", ())
    bare = held(saved_residuals(loss, flash_params))
    extra = held(res) - bare
    # the bare program keeps each forward switch's index for a routed
    # forward it runs again; with the routed output kept none runs
    assert set(bare - held(res)) <= {("int32[1]", 4)}
    state = tokens.size * c.hidden_size * 4
    assert plan["kept_bytes_per_expert_block"] == (
        plan["kept_bytes_per_block"] + state)
    assert extra[(f"float32[{tokens.size},{c.hidden_size}]", state)] == 3
    assert sum(size * n for (_, size), n in extra.items()) == (
        plan["kept_bytes"] - 4 * state)
    assert bare[("float32[1,512,64]", state)] >= 4


# -- (g) the selection bias rides in tx ---------------------------------------


def test_bias_moves_by_gamma_against_the_load_and_takes_no_adamw():
    c = small()
    tokens = tokens_for(c)
    params = AfmoeLM(c).init(jax.random.PRNGKey(0), tokens)["params"]
    model = AfmoeLM(c)
    gamma = 0.001
    adamw = optax.adamw(1e-3)

    def loss_fn(p, t):
        return afmoe_fused_loss(model, p, t)

    tx = glm_moe_optimizer(adamw, gamma)
    step = build_gspmd_train_step(loss_fn, tx, donate=False, has_aux=True)
    new, _, _, m = step(params, tx.init(params), tokens)

    @jax.jit
    def adamw_alone(p):
        grads = jax.grad(lambda q: loss_fn(q, tokens)[0])(p)
        updates, _ = adamw.update(grads, adamw.init(p), p)
        return optax.apply_updates(p, updates)

    plain = adamw_alone(params)
    for i, name in enumerate(["Block_1", "Block_2", "Block_3"]):
        counts = m["counts"][i].astype(jnp.float32)
        want = params[name]["moe"][ROUTER_BIAS] + gamma * jnp.sign(
            counts.mean() - counts)
        np.testing.assert_allclose(new[name]["moe"][ROUTER_BIAS], want,
                                   rtol=0, atol=1e-9)
        assert float(jnp.abs(new[name]["moe"][ROUTER_BIAS]).max()) == \
            pytest.approx(gamma)
    for (name, got), (_, exp) in zip(leaves_with_names(new),
                                     leaves_with_names(plain)):
        if ROUTER_BIAS not in name:
            np.testing.assert_allclose(got, exp, rtol=1e-5, atol=5e-6,
                                       err_msg=name)


# -- (h) the rules table ------------------------------------------------------


def test_rules_table_covers_every_leaf_and_splits_what_it_says():
    from kungfu_tpu.analysis.shard_rules import check_coverage, check_mesh

    registered = {"afmoe": R.REGISTRY["afmoe"]}
    assert check_coverage(registered) == []
    assert check_mesh(registered) == []
    c = small()
    params = init(c, tokens_for(c))
    specs = R.plan(afmoe_rules(), params, {"data": 1, "model": 2})
    flat = {R.path_str(p): s for p, s in
            jax.tree_util.tree_flatten_with_path(specs)[0]}
    assert len(flat) == len(jax.tree_util.tree_leaves(params))
    split = {p for p, s in flat.items() if "model" in str(s)}
    for leaf in ("Block_0/LocalAttention_0/q/kernel",
                 "Block_0/LocalAttention_0/k/kernel",
                 "Block_1/GlobalAttention_0/gate/kernel",
                 "Block_1/GlobalAttention_0/o/kernel",
                 "Block_0/mlp/up/kernel", "Block_1/moe/w_down",
                 "Block_3/moe/shared/gate/kernel"):
        assert leaf in split, leaf
    for leaf in ("Block_1/moe/router", "Block_1/moe/router_bias",
                 "Block_0/LocalAttention_0/q_norm/scale", "lm_head",
                 "embed/embedding", "Block_2/ffn_out_norm/scale"):
        assert leaf in flat and leaf not in split, leaf
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    placed = shard_params(params, mesh, afmoe_rules())
    assert jax.tree_util.tree_structure(placed) == \
        jax.tree_util.tree_structure(params)


# -- (i) the names a trace reader selects by ----------------------------------


@pytest.fixture(scope="module")
def afmoe_paths():
    """The step at a tiny size: flash attention of both kinds, the
    fused head + CE (hidden 128), recomputation, the optimizer with the
    bias' sgd, the GSPMD builder."""
    c = small(vocab_size=512, hidden_size=128, num_heads=2, num_kv_heads=1,
              head_dim=64, intermediate_size=256, dtype=jnp.bfloat16,
              attention="flash", remat=True)
    model = AfmoeLM(c)
    tokens = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 16), jnp.int32))["params"])
    tx = glm_moe_optimizer(optax.adamw(1e-4), 0.001)
    step = build_gspmd_train_step(
        lambda p, t: afmoe_fused_loss(model, p, t), tx, has_aux=True)
    return scope_paths(step, params, jax.eval_shape(tx.init, params),
                       tokens, calls=("cond",))


@pytest.mark.parametrize("scope, module, other", [
    (ATTN_LOCAL, "LocalAttention", "GlobalAttention"),
    (ATTN_GLOBAL, "GlobalAttention", "LocalAttention"),
])
def test_attention_scopes_hold_their_kind_of_layer(afmoe_paths, scope,
                                                   module, other):
    under = [p for p in afmoe_paths if scope in re.split(r"[/()]", p)]
    fwd = {primitive(p) for p in under if "transpose(" not in p}
    bwd = {primitive(p) for p in under if "transpose(" in p}
    want = {"pallas_call", "dot_general", "rsqrt", "logistic"}
    if scope == ATTN_LOCAL:
        want |= {"cos"}        # rotary on the sliding layers alone
    else:
        assert "cos" not in fwd
    assert want <= fwd, sorted(fwd)
    assert {"pallas_call", "dot_general"} <= bwd, sorted(bwd)
    assert not [p for p in under if other in p]
    # the adjacency benchmark/metrics/{window,global}_flash_roofline.json
    # select by: this kind's kernels directly under this kind's module
    kernels = [p for p in under if "pallas_call" in p.split("/")]
    assert len(kernels) >= 2
    for p in kernels:
        assert re.search(module + r"_\d+/pallas_call", p), p


def test_every_flash_kernel_is_under_one_of_the_two_scopes(afmoe_paths):
    kernels = [p for p in afmoe_paths if "pallas_call" in p.split("/")]
    flash_calls = [p for p in kernels if FUSED_CE not in p]
    for p in flash_calls:
        parts = re.split(r"[/()]", p)
        assert (ATTN_LOCAL in parts) != (ATTN_GLOBAL in parts), p
    assert [p for p in kernels if FUSED_CE in p]
    # the expert layer keeps its two names
    for scope in (MOE_ROUTE, MOE_EXPERTS):
        assert [p for p in afmoe_paths if scope in re.split(r"[/()]", p)]


@pytest.mark.parametrize("scope, forward, backward", [
    (MOE_ROUTE, {"dot_general", "top_k", "sort", "gather"},
     {"gather", "dot_general"}),
    (MOE_EXPERTS, {"ragged_dot_general", "dot_general", "logistic"},
     {"ragged_dot_general", "dot_general"}),
])
def test_expert_scopes_hold_their_layers_round_the_switch(
        afmoe_paths, scope, forward, backward):
    """4 of 16 experts held: two rungs, so the routed path runs under a
    switch, and the two names still hold what their metrics read."""
    under = [p for p in afmoe_paths if scope in re.split(r"[/()]", p)]
    fwd = {primitive(p) for p in under if "transpose(" not in p}
    bwd = {primitive(p) for p in under if "transpose(" in p}
    assert forward <= fwd, sorted(fwd)
    assert backward <= bwd, sorted(bwd)
    assert "cond" not in fwd | bwd


def test_switches_carry_neither_expert_scope(afmoe_paths):
    switches_stand_outside(afmoe_paths)


# -- (j) the cell's rehearsal twin through the benchmark's command ------------


def test_rehearsal_twin_runs_through_the_benchmark_command(tmp_path):
    """Control flow and finite numbers, not a limit read at however
    many steps a busy CPU fits into the window: the reference checks
    are THERE and their readings are finite."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "trinity-mini.train-b1-t8192", "--seed", "3000000007",
         "--seconds", "2", "--trace", "0", "--rehearse", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(line) for line in out.stdout.splitlines()
             if line.startswith("{")]
    result = lines[-1]
    assert result["correct"] is False  # a rehearsal never is
    assert result["failed"] == 0 and result["attempted"] > 0
    window = next(x for x in lines if x.get("phase") == "window")
    assert {"dropped_is_zero", "reference_objective",
            "reference_gradients", "reference_route_counts"} <= set(
        window["checks"])
    assert window["checks"]["losses_finite"]
    assert window["checks"]["no_compile_in_window"]
    assert window["checks"]["dropped_is_zero"]
    reference = next(x for x in lines if x.get("phase") == "reference")
    readings = [*reference["loss_abs_err"].values(),
                *reference["grad_rel_err"].values()]
    assert len(reference["grad_rel_err"]) >= 3
    assert all(np.isfinite(r) for r in readings), reference
    plan = next(x for x in lines if x.get("phase") == "plan")
    assert plan["layer_plan"]["layers"][:2] == [["sliding", "dense"],
                                                ["full", "expert"]]
    assert plan["buffer_rows"] == 64 * 2
    counters = next(x for x in lines if x.get("phase") == "counters")
    assert counters["dropped"] == 0
