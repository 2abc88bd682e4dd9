"""`models/glm_moe.py` and `parallel/grouped_moe.py` against the plain
reference (`models/glm_moe_reference.py`), on the CPU at small widths
with every ratio of the published model kept: a rope/nope split whose
sum is the value head size, q and kv ranks below the hidden size,
top-k of E with a held subset, one dense block + expert blocks + the
MTP module.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax._src.ad_checkpoint import remat_p, saved_residuals

from kungfu_tpu.models import glm_moe
from kungfu_tpu.models.glm_moe import (MLA_O, MLA_QKV, ROUTER_BIAS, Block,
                                       ExpertFFN, GlmMoeConfig, GlmMoeLM,
                                       MLAttention, glm_moe_fused_loss,
                                       glm_moe_logits, glm_moe_optimizer,
                                       remat_plan)
from kungfu_tpu.models import glm_moe_reference as ref
from kungfu_tpu.ops import flash
from kungfu_tpu.ops.flash import FLASH_LSE, FLASH_OUT
from kungfu_tpu.parallel import (build_gspmd_train_step, glm_moe_rules,
                                 shard_params)
from kungfu_tpu.parallel import grouped_moe as gm
from kungfu_tpu.parallel import rules as R

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small(**kw):
    base = dict(
        vocab_size=256, hidden_size=64, num_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8,
        v_head_dim=32, intermediate_size=160, moe_intermediate_size=48,
        n_routed_experts=16, num_experts_per_tok=4, num_layers=3,
        held=(2, 2), dtype=jnp.float32)
    base.update(kw)
    return GlmMoeConfig(**base)


def ref_cfg(c):
    return dict(
        num_attention_heads=c.num_heads, q_lora_rank=c.q_lora_rank,
        kv_lora_rank=c.kv_lora_rank, qk_nope_head_dim=c.qk_nope_head_dim,
        qk_rope_head_dim=c.qk_rope_head_dim, v_head_dim=c.v_head_dim,
        num_experts_per_tok=c.num_experts_per_tok,
        routed_scaling_factor=c.routed_scaling_factor,
        first_k_dense_replace=c.first_k_dense_replace,
        num_hidden_layers=c.num_layers,
        num_nextn_predict_layers=c.num_nextn_predict_layers,
        rope_theta=c.rope_theta, rms_norm_eps=c.rms_norm_eps,
        held=c.held, mtp_lambda=c.mtp_lambda)


def tokens_for(c, shape=(2, 32), seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                              c.vocab_size)


def init(c, tokens, seed=0):
    return GlmMoeLM(c).init(jax.random.PRNGKey(seed), tokens)["params"]


def rel_err(a, b):
    return float(jnp.linalg.norm((a - b).ravel())
                 / (jnp.linalg.norm(b.ravel()) + 1e-30))


def leaves_with_names(tree):
    return [(jax.tree_util.keystr(p), x) for p, x in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


# -- (a) the system against the plain reference -------------------------------


@pytest.fixture(scope="module")
def f32_case():
    c = small()
    tokens = tokens_for(c)
    return c, tokens, init(c, tokens)


def test_logits_match_the_reference_in_f32(f32_case):
    c, tokens, params = f32_case
    with jax.default_matmul_precision("highest"):
        logits, mtp_logits, aux = jax.jit(
            lambda p: glm_moe_logits(GlmMoeLM(c), p, tokens))(params)
    # the reference's objective from the system's logits: both heads
    def ce(lg, tg):
        return optax.softmax_cross_entropy_with_integer_labels(
            lg, tg).mean()

    want, parts = jax.jit(lambda p: ref.reference_loss(
        p, tokens, ref_cfg(c), q_block=16, row_block=16))(params)
    assert float(ce(logits[:, :-1], tokens[:, 1:])) == pytest.approx(
        float(parts["ce"]), rel=2e-6)
    assert float(ce(mtp_logits[:, :-2], tokens[:, 2:])) == pytest.approx(
        float(parts["ce_mtp"]), rel=2e-6)
    assert (aux["counts"] == parts["counts"]).all()
    assert int(aux["dropped"].sum()) == 0


@pytest.mark.parametrize("remat", [False, True], ids=["kept", "remat"])
def test_objective_and_gradients_match_the_reference_in_f32(
        f32_case, remat):
    c, tokens, params = f32_case
    model = GlmMoeLM(dataclasses.replace(c, remat=remat))
    with jax.default_matmul_precision("highest"):
        (loss, m), g = jax.jit(jax.value_and_grad(
            lambda p: glm_moe_fused_loss(model, p, tokens),
            has_aux=True))(params)
    (want, parts), g_want = jax.jit(jax.value_and_grad(
        lambda p: ref.reference_loss(p, tokens, ref_cfg(c), q_block=16,
                                     row_block=16, remat=remat),
        has_aux=True))(params)
    # f32 against f32, summation order only: at hidden 64 the fused
    # head takes its plain path (its kernels run the head in bf16; the
    # bf16 test below is at a width they take)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    assert float(m["ce"]) == pytest.approx(float(parts["ce"]), rel=2e-6)
    assert float(m["ce_mtp"]) == pytest.approx(float(parts["ce_mtp"]),
                                               rel=2e-6)
    for (name, got), (_, exp) in zip(leaves_with_names(g),
                                     leaves_with_names(g_want)):
        if ROUTER_BIAS in name:
            continue  # no gradient's business: test (e)
        assert rel_err(got, exp) < 2e-5, name


def test_bf16_compute_stays_near_the_f32_reference():
    """bf16 matmuls and residual stream, f32 statistics, router and
    losses. The limits' reason: bf16 keeps 8 bits, so one rounding is
    2^-9 relative; the loss averages 62 rows of ~5.5 whose logits each
    carry some dozens of roundings, and reads 1e-3 or less here; a
    gradient leaf sums products of rounded activations through 4
    blocks and reads 1-3% of its norm, more where a token's top-k
    flips. All-bf16 statistics (norms, router, softmax, loss) read
    several times these (the benchmark's cell holds that line on the
    chip)."""
    c = small(dtype=jnp.bfloat16, vocab_size=512, hidden_size=128,
              q_lora_rank=48, kv_lora_rank=32, intermediate_size=320,
              moe_intermediate_size=96)
    tokens = tokens_for(c, (2, 64))
    params = init(c, tokens)
    (loss, m), g = jax.jit(jax.value_and_grad(
        lambda p: glm_moe_fused_loss(GlmMoeLM(c), p, tokens),
        has_aux=True))(params)
    (want, parts), g_want = jax.jit(jax.value_and_grad(
        lambda p: ref.reference_loss(p, tokens, ref_cfg(c), q_block=16),
        has_aux=True))(params)
    assert abs(float(loss) - float(want)) < 5e-3
    assert abs(float(m["ce_mtp"]) - float(parts["ce_mtp"])) < 5e-3
    for path in (("embed", "embedding"), ("lm_head",),
                 ("mtp", "eh_proj", "kernel"),
                 ("Block_0", "mlp", "down", "kernel")):
        got, exp = g, g_want
        for key in path:
            got, exp = got[key], exp[key]
        assert rel_err(got, exp) < 0.05, path


# -- (b) the shares add up to the uncut layer ---------------------------------


def test_eight_shares_add_up_to_the_uncut_layer():
    """Guide section 4: the routed parts the eight shares give, with
    the shared expert (which every chip computes alike) counted once,
    are the uncut reference's layer output."""
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


# -- (c) dropless under imbalance ---------------------------------------------


@pytest.mark.parametrize("favoured, held_rows", [
    ((2, 8, 9, 10), 1.0),   # every token to ONE held expert (2 of 2, 3)
    ((8, 9, 10, 11), 0.0),  # no token to a held expert
    ((2, 3, 9, 10), 2.0),   # every token to both: the buffer is full
], ids=["all-to-one", "none", "all-to-both"])
def test_rigged_router_drops_nothing(favoured, held_rows):
    c = small()
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 40, c.hidden_size))
    params = ExpertFFN(c).init(jax.random.PRNGKey(6), x)["params"]
    # equal scores everywhere, so the bias alone selects
    params["router"] = jnp.zeros_like(params["router"])
    params[ROUTER_BIAS] = jnp.zeros((16,)).at[jnp.array(favoured)].set(1.0)
    y, aux = ExpertFFN(c).apply({"params": params}, x)
    with jax.default_matmul_precision("highest"):
        want, counts = ref.expert_ffn(params, x[0], ref_cfg(c), False)
    np.testing.assert_allclose(y[0], want, rtol=2e-5, atol=2e-5)
    assert int(aux["dropped"]) == 0
    assert int(aux["held_assignments"]) == int(held_rows * 40)
    assert (aux["counts"] == counts).all()
    assert int(counts[jnp.array(favoured)].sum()) == 4 * 40


# -- (c') the ladder of buffer sizes: same bits, fewer rows --------------------


@pytest.mark.parametrize("shapes, want", [
    # tokens, k, held, router width
    ((8192, 8, (0, 8), 128), (8192, 16384, 65536)),     # trinity-mini
    ((8192, 4, (0, 8), 64), (8192, 16384, 32768)),      # glm-4.7-flash
    ((8192, 4, (0, 64), 64), (32768,)),     # the whole layer: one rung
    ((8192, 4, (0, 32), 64), (32768,)),     # half of it: still one
    ((8192, 8, (120, 8), 128), (8192, 16384, 65536)),   # any share
    ((8192, 8, (0, 32), 128), (32768, 65536)),
    ((128, 4, (2, 2), 32), (64, 128, 256)),     # `laddered_runs` below
    ((100, 4, (0, 3), 32), (76, 152, 300)),     # the expected rows round up
], ids=["trinity-mini", "glm", "whole-layer", "half-layer", "last-share",
        "quarter-layer", "small", "odd"])
def test_row_ladder_follows_the_shapes(shapes, want):
    ladder = gm.row_ladder(*shapes)
    assert ladder == want
    assert ladder[-1] == gm.buffer_rows(*shapes[:3])    # the worst case


def sub_jaxprs(jaxpr):
    """Every equation of a jaxpr, nested programs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from sub_jaxprs(sub)


def one_rung(monkeypatch):
    """`ExpertFFN` as it was before the ladder: the worst-case buffer
    alone, autodiff through the three calls."""
    monkeypatch.setattr(gm, "row_ladder",
                        lambda n, k, held, e: (gm.buffer_rows(n, k, held),))


LADDERED_TOKENS = 128


@pytest.fixture(scope="module")
def laddered_runs():
    """An expert layer whose ladder has three rungs (64, 128, 256) and
    whose router is rigged so that a token's first two features send
    it to held expert 2 and to held expert 3: the test says how many
    rows the layer holds. {wrap: (on the ladder, on the worst-case
    buffer alone)}, each `f(held rows)` -> loss, y, counters and every
    gradient, through `jax.jit` alone or through the model's per-block
    `jax.checkpoint` as well. 128 tokens: under 64 rows the CPU's
    matmuls take another kernel, whose sums run in another order, and
    a rung's bits are then its own."""
    c = small(n_routed_experts=32)
    n = LADDERED_TOKENS
    assert gm.row_ladder(n, 4, c.held, 32) == (64, 128, 256)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, n, c.hidden_size))
    params = ExpertFFN(c).init(jax.random.PRNGKey(6), x)["params"]
    # two absent experts always chosen; the other two slots go to the
    # absent 12 and 13 (score 0.5 + 0.2) unless a held expert's score
    # nears 1
    router = jnp.zeros_like(params["router"])
    params["router"] = router.at[0, 2].set(12.0).at[1, 3].set(12.0)
    params[ROUTER_BIAS] = jnp.zeros((32,)).at[jnp.array([8, 9])].set(
        2.0).at[jnp.array([12, 13])].set(0.2)

    def tokens(held):
        """x whose first feature is +1 on `min(held, n)` tokens (held
        expert 2 takes them) and -1 elsewhere, the second likewise for
        the rest of `held` (expert 3)."""
        sign = lambda m: jnp.where(jnp.arange(n) < m, 1.0, -1.0)  # noqa: E731
        return x.at[0, :, 0].set(sign(min(held, n))).at[0, :, 1].set(
            sign(held - min(held, n)))

    def run(wrap):
        def loss(params, x):    # a new one a run: jax caches traces by it
            y, aux = ExpertFFN(c).apply({"params": params}, x)
            return jnp.sum(y * y) + aux.pop("bias_loss"), (y, aux)

        f = loss
        if wrap == "checkpoint":
            f = jax.checkpoint(
                loss, policy=jax.checkpoint_policies.save_only_these_names(
                    *glm_moe._KEPT))
        f = jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))
        f(params, x)    # traced here, whatever `row_ladder` is now
        return lambda held: f(params, tokens(held))

    wraps = ("jit", "checkpoint")
    ladder = {wrap: run(wrap) for wrap in wraps}
    with pytest.MonkeyPatch.context() as patch:
        one_rung(patch)
        return {wrap: (ladder[wrap], run(wrap)) for wrap in wraps}


@pytest.mark.parametrize("wrap", ["jit", "checkpoint"])
@pytest.mark.parametrize("held, rung", [
    (0, 64), (63, 64), (64, 64), (65, 128), (127, 128), (128, 128),
    (129, 256), (255, 256), (256, 256)])
def test_every_rung_gives_the_one_buffer_paths_bits(laddered_runs, wrap,
                                                    held, rung):
    """Just under, at and just over each rung's edge: the step runs on
    the smallest rung that holds its rows, drops nothing, and what it
    returns is the worst-case buffer's to the bit (the expert stacks'
    gradients to the order of an f32 sum over fewer rows)."""
    ladder, whole = laddered_runs[wrap]
    (loss, (y, aux)), (dparams, dx) = ladder(held)
    (loss0, (y0, aux0)), (dparams0, dx0) = whole(held)
    assert int(aux["held_assignments"]) == held
    assert int(aux["rung_rows"]) == rung and int(aux0["rung_rows"]) == 256
    assert int(aux["dropped"]) == 0 and int(aux0["dropped"]) == 0
    assert float(loss) == float(loss0)
    np.testing.assert_array_equal(y, y0)
    np.testing.assert_array_equal(dx, dx0)
    for name, got in leaves_with_names(dparams):
        want = dict(leaves_with_names(dparams0))[name]
        if "['w_" in name:    # an expert stack's: a sum over the rows
            assert rel_err(got, want) <= 1e-6, name
        else:
            np.testing.assert_array_equal(got, want, err_msg=name)
    if held:
        assert float(jnp.abs(dparams["w_down"]).max()) > 0


def routed_conds(jaxpr):
    return [eqn for eqn in sub_jaxprs(jaxpr) if eqn.primitive.name == "cond"]


def test_no_rung_sized_value_leaves_a_switch():
    """The trap ISSUE 35 found: differentiated THROUGH, the forward
    `cond` returns every rung's residuals, each branch zero-filling the
    others'. With one `custom_vjp` round the routed path a switch hands
    out the layer's output or the cotangents of its inputs: arrays as
    long as the tokens or the weight stacks, never as long as a rung."""
    c = small(n_routed_experts=32)
    n, h, f, k = 48, c.hidden_size, c.moe_intermediate_size, 4
    ladder = gm.row_ladder(n, k, c.held, 32)
    assert ladder == (24, 48, 96)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, n, h))
    params = ExpertFFN(c).init(jax.random.PRNGKey(6), x)["params"]

    def loss(params, x):
        y, aux = ExpertFFN(c).apply({"params": params}, x)
        return jnp.sum(y * y) + aux["bias_loss"]

    step = jax.grad(jax.checkpoint(loss), argnums=(0, 1))
    conds = routed_conds(jax.make_jaxpr(step)(params, x).jaxpr)
    # forward, the recomputed forward, backward
    assert len(conds) == 3
    assert all(len(eqn.params["branches"]) == 3 for eqn in conds)
    allowed = {(n, h), (n, k), (2, h, f), (2, f, h)}
    shapes = {v.aval.shape for eqn in conds for v in eqn.outvars}
    assert shapes <= allowed, shapes - allowed
    assert {(n, h), (2, h, f)} <= shapes
    # and inside the branches the rows ARE as long as their rung
    inside = {v.aval.shape[0] for eqn in conds
              for branch in eqn.params["branches"]
              for e in sub_jaxprs(branch.jaxpr)
              for v in e.outvars if v.aval.shape[1:] == (h,)}
    assert set(ladder) <= inside


def test_a_caller_that_holds_every_expert_has_no_switch():
    c = small(held=(0, 16))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 40, c.hidden_size))
    params = ExpertFFN(c).init(jax.random.PRNGKey(6), x)["params"]
    assert gm.row_ladder(40, 4, c.held, 16) == (160,)

    def loss(params, x):
        y, aux = ExpertFFN(c).apply({"params": params}, x)
        assert aux["rung_rows"].shape == ()
        return jnp.sum(y * y) + aux["bias_loss"]

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x).jaxpr
    assert not routed_conds(jaxpr)
    assert any(e.primitive.name == "ragged_dot_general"
               for e in sub_jaxprs(jaxpr))


# -- (c'') what routing reads: no gather, no scatter, no [N, k, H] ------------


def pick(rows, pos, valid):
    """[N, k, H]: each assignment's row, zero where it has none: how
    combine and dispatch's backward once read the rows, and the plain
    reference their sums are held to here."""
    picked = rows[jnp.where(valid, pos, 0)]
    return jnp.where(valid[..., None], picked, jnp.zeros((), rows.dtype))


def scatter_pos(idx, held):
    """The sort's inverse permutation by scatter, as `plan_dispatch`
    once computed it."""
    first, count = held
    n, k = idx.shape
    local = idx - first
    key = jnp.where((local >= 0) & (local < count), local, count).reshape(-1)
    order = jnp.argsort(key, stable=True)
    return jnp.zeros((n * k,), jnp.int32).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32)).reshape(n, k)


def gathered_route(x, router, bias, k, scaling):
    """`route_sigmoid_topk`'s weights through `take_along_axis`, as it
    once computed them."""
    scores = jax.nn.sigmoid(jnp.dot(x, router,
                                    precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
    chosen = jnp.take_along_axis(scores, idx, axis=1)
    return scaling * chosen / (chosen.sum(axis=1, keepdims=True) + 1e-20)


ROUTINGS = {
    # tokens, k, router width, held, the bias' favoured experts (None:
    # a random router)
    "random": (128, 4, 32, (2, 2), None),
    "all-held": (128, 4, 16, (0, 16), None),
    "none-held": (128, 4, 32, (2, 2), (8, 9, 10, 11)),
    "top8": (128, 8, 128, (0, 8), None),
    "two-tiles": (200, 4, 32, (2, 2), None),    # tokens no power of two
}


def routing(case):
    n, k, e, held, favoured = ROUTINGS[case]
    x = jax.random.normal(jax.random.PRNGKey(11), (n, 64))
    router = jax.random.normal(jax.random.PRNGKey(12), (64, e)) * 0.3
    bias = jnp.zeros((e,))
    if favoured is not None:    # equal scores: the bias alone selects
        router = jnp.zeros_like(router)
        bias = bias.at[jnp.array(favoured)].set(1.0)
    return x, router, bias, k, held


@pytest.mark.parametrize("case", list(ROUTINGS))
def test_pos_by_counting_is_the_scatters_to_the_bit(case):
    x, router, bias, k, held = routing(case)
    r = gm.route_sigmoid_topk(x, router, bias, k, 2.5)
    d = gm.plan_dispatch(r.idx, held)
    np.testing.assert_array_equal(d.pos, scatter_pos(r.idx, held))
    held_slots = int(((r.idx >= held[0])
                      & (r.idx < held[0] + held[1])).sum())
    assert int(d.valid.sum()) == int(d.group_sizes.sum()) == held_slots
    assert held_slots == {"all-held": x.shape[0] * k,
                          "none-held": 0}.get(case, held_slots)
    # every held row's assignment is the one whose `pos` names it
    flat = np.asarray(d.row_assign)[:held_slots]
    np.testing.assert_array_equal(
        np.asarray(d.pos).reshape(-1)[flat], np.arange(held_slots))


@pytest.mark.parametrize("case", list(ROUTINGS))
def test_chosen_and_its_gradient_are_the_gathers_to_the_bit(case):
    x, router, bias, k, _ = routing(case)
    cot = jax.random.normal(jax.random.PRNGKey(13), (x.shape[0], k))

    def run(route):
        w, pull = jax.vjp(lambda x, r: route(x, r), x, router)
        return (w, *pull(cot))

    got = run(lambda x, r: gm.route_sigmoid_topk(x, r, bias, k, 2.5).weights)
    want = run(lambda x, r: gathered_route(x, r, bias, k, 2.5))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("case", list(ROUTINGS))
def test_held_rows_are_the_pick_form_on_every_rung(case):
    """Combine forward, dispatch backward (each token's sum of its held
    rows) and combine backward's weight cotangent (over the rows), each
    on every rung of the case's ladder, against the [N, k, H] pick in
    f32."""
    x, router, bias, k, held = routing(case)
    r = gm.route_sigmoid_topk(x, router, bias, k, 2.5)
    d = gm.plan_dispatch(r.idx, held)
    n, e = x.shape[0], router.shape[1]
    for rung in gm.row_ladder(n, k, held, e):
        rows = jax.random.normal(jax.random.PRNGKey(rung), (rung, 64))
        row_assign, valid = d.row_assign[:rung], d.valid & (d.pos < rung)
        dy = jax.random.normal(jax.random.PRNGKey(14), (n, 64))
        y, pull = jax.vjp(lambda rows, w: gm.combine_rows(
            rows, w, row_assign, d.pos, valid), rows, r.weights)
        _, d_w = pull(dy)
        picked = pick(rows, d.pos, valid)
        w = jnp.where(valid, r.weights, 0.0)
        with jax.default_matmul_precision("highest"):
            want_y = jnp.einsum("nk,nkh->nh", w, picked)
            want_dw = jnp.einsum("nh,nkh->nk", dy, picked)
        _, pull = jax.vjp(lambda x: gm.dispatch_rows(
            x, row_assign, d.pos, valid), x)
        (dx,) = pull(rows)
        assert rel_err(y, want_y) <= 1e-6, rung
        assert rel_err(d_w, want_dw) <= 1e-6, rung
        assert rel_err(dx, picked.sum(axis=1)) <= 1e-6, rung
        assert bool((y == 0).all()) == (case == "none-held")


@pytest.mark.parametrize("weighed", [False, True], ids=["plain", "weighed"])
@pytest.mark.parametrize("case", ["random", "top8", "all-held"])
def test_held_sum_of_bf16_rows_is_the_pick_forms_on_every_rung(case,
                                                                weighed):
    """The rows as the step holds them, in bf16: each token's sum, in
    f32, equals the [N, k, H] pick's cast to f32 and summed over k, with
    and without combine's weights, on every rung."""
    x, router, bias, k, held = routing(case)
    r = gm.route_sigmoid_topk(x, router, bias, k, 2.5)
    d = gm.plan_dispatch(r.idx, held)
    w = r.weights if weighed else None
    for rung in gm.row_ladder(x.shape[0], k, held, router.shape[1]):
        rows = jax.random.normal(jax.random.PRNGKey(rung), (rung, 64),
                                 jnp.bfloat16)
        valid = d.valid & (d.pos < rung)
        got = gm._held_sum(rows, d.pos, valid, w)
        picked = pick(rows, d.pos, valid).astype(jnp.float32)
        if weighed:
            picked = r.weights[:, :, None] * picked
        assert got.dtype == jnp.float32
        assert rel_err(got, picked.sum(axis=1)) <= 1e-6, rung


def test_the_step_holds_no_tokens_by_slots_by_hidden_value():
    """Forward, recomputed forward and backward of `ExpertFFN` on its
    ladder: no value of shape (N, k, H) anywhere, and no scatter."""
    c = small(n_routed_experts=32)
    n, h, k = 48, c.hidden_size, c.num_experts_per_tok
    x = jax.random.normal(jax.random.PRNGKey(5), (1, n, h))
    params = ExpertFFN(c).init(jax.random.PRNGKey(6), x)["params"]

    def loss(params, x):
        y, aux = ExpertFFN(c).apply({"params": params}, x)
        return jnp.sum(y * y) + aux["bias_loss"]

    jaxpr = jax.make_jaxpr(jax.grad(jax.checkpoint(loss), argnums=(0, 1)))(
        params, x).jaxpr
    eqns = list(sub_jaxprs(jaxpr))
    shapes = {v.aval.shape for e in eqns for v in e.outvars
              if hasattr(v.aval, "shape")}
    assert (n, k, h) not in shapes
    assert not [e for e in eqns if "scatter" in e.primitive.name]
    sums = [e for e in eqns if e.params.get("name") == "_held_sum"]
    # a rung each: combine's forward in the forward and the recomputed
    # forward; in the backward the rebuilt forward's and dispatch's
    # backward
    assert len(sums) == 3 * (1 + 1 + 2)


# -- (d) latent attention through the flash kernels at d = 256 ----------------


@pytest.mark.parametrize("scheme", [None, "stream"],
                         ids=["head", "stream"])
def test_mla_through_flash_at_head_size_256_matches_the_plain_path(
        monkeypatch, scheme):
    """The published head sizes (192 + 64 and 256), rotary included,
    through `flash_attention` in interpret mode against the plain
    path on the same parameters — by the head kernels (T 512) and,
    forced, by the streaming forward and its one fused backward
    kernel, which is what the cell's T 8192 runs."""
    monkeypatch.setattr(flash, "_FORCE_SCHEME", scheme)
    assert flash.flash_plan(512, 256, causal=True)["bwd"]["scheme"] == (
        "stream_fused" if scheme else "head")
    c = small(hidden_size=128, num_heads=2, q_lora_rank=48,
              kv_lora_rank=32, qk_nope_head_dim=192, qk_rope_head_dim=64,
              v_head_dim=256)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 512, c.hidden_size))
    params = MLAttention(c).init(jax.random.PRNGKey(8), x)["params"]

    def run(attention):
        mod = MLAttention(dataclasses.replace(c, attention=attention))
        return jax.jit(jax.value_and_grad(
            lambda p: (mod.apply({"params": p}, x) ** 2).sum()))(params)

    (plain, g_plain), (fused, g_flash) = run("local"), run("flash")
    assert float(fused) == pytest.approx(float(plain), rel=1e-5)
    for (name, a), (_, b) in zip(leaves_with_names(g_flash),
                                 leaves_with_names(g_plain)):
        assert rel_err(a, b) < 1e-4, name
    # and against the reference's own attention and rotary
    with jax.default_matmul_precision("highest"):
        want = ref.mla(params, x[0], ref_cfg(c), 128, False)
        got = MLAttention(dataclasses.replace(c, attention="flash")).apply(
            {"params": params}, x)[0]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_flash_needs_one_head_size():
    with pytest.raises(ValueError, match="one head size"):
        small(attention="flash", v_head_dim=64)


# -- (d') what a recomputed block keeps, by name ------------------------------


def flash_case(dtype=jnp.float32, **kw):
    """Two blocks (one dense, one expert) through the flash kernels
    (T 512: the head scheme), recomputed; no MTP module and a
    plain-path head, so every `pallas_call` in the program is flash's.
    Returns (config, tokens, loss of the parameters)."""
    c = small(**{**dict(attention="flash", remat=True, num_layers=2,
                        num_nextn_predict_layers=0, dtype=dtype), **kw})
    tokens = tokens_for(c, (1, 512))
    model = GlmMoeLM(c)
    return c, tokens, lambda p: glm_moe_fused_loss(model, p, tokens)[0]


@pytest.fixture(scope="module")
def flash_params():
    c, tokens, _ = flash_case()
    return init(c, tokens)


@pytest.fixture
def bare_remat(monkeypatch):
    """The blocks under `nn.remat` with no policy, as before PR 28."""
    import flax.linen as nn

    monkeypatch.setattr(
        glm_moe, "_block", lambda c, expert, name: nn.remat(Block)(
            c, expert, name=name))


def kernel_calls(jaxpr, inside=()):
    """(kernel function, the primitives it sits under) of every
    `pallas_call` in a jaxpr, sub-jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["jaxpr"].debug_info.func_name, inside))
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += kernel_calls(sub, inside + (eqn.primitive.name,))
    return found


@pytest.mark.parametrize("policy", ["names", "bare"])
@pytest.mark.parametrize("scheme, fwd, bwd", [
    (None, "_fwd_head_kernel", ["_bwd_head_kernel"]),
    ("stream", "_kernel", ["_bwd_stream_kernel"]),
], ids=["head", "stream"])
def test_recomputed_blocks_run_flash_forward_once(
        monkeypatch, request, flash_params, scheme, fwd, bwd, policy):
    monkeypatch.setattr(flash, "_FORCE_SCHEME", scheme)
    if policy == "bare":
        request.getfixturevalue("bare_remat")
    c, _, loss = flash_case()
    calls = kernel_calls(
        jax.make_jaxpr(jax.grad(loss))(flash_params).jaxpr)
    recomputed = [k for k, inside in calls if remat_p.name in inside]
    first = [k for k, inside in calls if remat_p.name not in inside]
    assert first == [fwd] * c.num_layers
    # the backward of a recomputed block is inside its checkpoint; so
    # is the second forward, unless the policy kept what it would make
    again = [fwd] if policy == "bare" else []
    assert sorted(recomputed) == sorted((again + bwd) * c.num_layers)


@pytest.mark.parametrize("case, names, arrays", [
    ("flash", (FLASH_OUT, FLASH_LSE, MLA_QKV, MLA_O), 6),
    ("local", (MLA_QKV, MLA_O), 4),
    ("kept", (), 0),
])
def test_remat_plan_is_what_jax_keeps(request, flash_params, case, names,
                                      arrays):
    """`remat_plan` against `saved_residuals`: with the policy each
    block keeps the lse, the kernel's output, q, k, v and the `o`
    projection (a block: `arrays`) and NOTHING else beyond what a bare
    `nn.remat` keeps. JAX lists a kept value that the forward also
    reads under the `reduce_precision` it puts on it, so only the lse
    shows by name and the rest is held to its bytes."""
    kw = {"local": dict(attention="local"), "kept": dict(remat=False)}
    c, tokens, loss = flash_case(**kw.get(case, {}))
    plan = remat_plan(c, *tokens.shape)
    assert plan["names"] == names and plan["blocks"] == 2
    assert plan["total_bytes"] == 2 * plan["bytes_per_block"]
    if not names:
        assert plan["total_bytes"] == 0
        return

    def held(res):
        return sorted((a.str_short(), a.size * a.dtype.itemsize)
                      for a, why in res if "from the argument" not in why)

    res = saved_residuals(loss, flash_params)
    assert sum(f"named '{FLASH_LSE}'" in why for _, why in res) == (
        2 if FLASH_LSE in names else 0)
    request.getfixturevalue("bare_remat")
    bare = saved_residuals(loss, flash_params)
    heads = (*tokens.shape, c.num_heads, c.v_head_dim)
    assert not any(a.shape == heads or "named" in why for a, why in bare)
    extra = held(res)
    for item in held(bare):
        extra.remove(item)
    assert len(extra) == 2 * arrays
    assert sum(size for _, size in extra) == plan["total_bytes"]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_keeping_the_names_changes_no_bit(request, flash_params, dtype):
    """The backward reads the output and lse the first forward wrote
    where it read a second, identical pair. Identical where a kernel
    is a kernel: interpret mode inlines it, and XLA:CPU then carries
    bf16 values in f32 through whichever fusions it forms, differently
    in the recomputed forward than in the first (`jax` PR 22244), so
    the comparison is compiled without that licence."""
    _, _, loss = flash_case(dtype)

    def run():
        return jax.jit(jax.value_and_grad(loss)).lower(
            flash_params).compile(compiler_options={
                "xla_allow_excess_precision": False})(flash_params)

    value, grads = run()
    request.getfixturevalue("bare_remat")
    value_bare, grads_bare = run()
    assert float(value) == float(value_bare)
    for (name, got), (_, exp) in zip(leaves_with_names(grads),
                                     leaves_with_names(grads_bare)):
        assert bool((got == exp).all()), name


# -- (e) the selection bias rides in tx ---------------------------------------


def test_bias_moves_by_gamma_against_the_load_and_weights_take_adamw():
    c = small()
    tokens = tokens_for(c)
    params = init(c, tokens)
    model = GlmMoeLM(c)
    gamma = 0.001
    adamw = optax.adamw(1e-3)

    def loss_fn(p, t):
        return glm_moe_fused_loss(model, p, t)

    tx = glm_moe_optimizer(adamw, gamma)
    step = build_gspmd_train_step(loss_fn, tx, donate=False, has_aux=True)
    new, _, _, m = step(params, tx.init(params), tokens)
    @jax.jit
    def adamw_alone(p):
        grads = jax.grad(lambda q: loss_fn(q, tokens)[0])(p)
        updates, _ = adamw.update(grads, adamw.init(p), p)
        return optax.apply_updates(p, updates)

    plain = adamw_alone(params)
    layers = ["Block_1", "Block_2", "mtp"]
    for i, name in enumerate(layers):
        tree = lambda t: (t[name]["block"] if name == "mtp"  # noqa: E731
                          else t[name])["moe"]
        counts = m["counts"][i].astype(jnp.float32)
        want = tree(params)[ROUTER_BIAS] + gamma * jnp.sign(
            counts.mean() - counts)
        np.testing.assert_allclose(tree(new)[ROUTER_BIAS], want,
                                   rtol=0, atol=1e-9)
        assert float(jnp.abs(tree(new)[ROUTER_BIAS]).max()) == \
            pytest.approx(gamma)
        # the bias' surrogate term is worth nothing and touches no
        # other leaf: the router's update is adamw's on the CE alone
        np.testing.assert_allclose(tree(new)["router"],
                                   tree(plain)["router"], rtol=1e-5,
                                   atol=5e-6)
    for (name, got), (_, exp) in zip(leaves_with_names(new),
                                     leaves_with_names(plain)):
        if ROUTER_BIAS not in name:
            # adam's first step is lr * g / (|g| + eps): where g is
            # nearly nothing two programs' rounding shows, well under
            # one update (1e-3)
            np.testing.assert_allclose(got, exp, rtol=1e-5, atol=5e-6,
                                       err_msg=name)


# -- (f) the rules table ------------------------------------------------------


def test_rules_table_covers_every_leaf_and_splits_what_it_says():
    from kungfu_tpu.analysis.shard_rules import check_coverage, check_mesh

    registered = {"glm_moe": R.REGISTRY["glm_moe"]}
    assert check_coverage(registered) == []
    assert check_mesh(registered) == []
    c = small()
    params = init(c, tokens_for(c))
    specs = R.plan(glm_moe_rules(), params, {"data": 1, "model": 2})
    flat = {R.path_str(p): s for p, s in
            jax.tree_util.tree_flatten_with_path(specs)[0]}
    assert len(flat) == len(jax.tree_util.tree_leaves(params))
    split = {p for p, s in flat.items() if "model" in str(s)}
    assert "Block_1/MLAttention_0/q_b/kernel" in split
    assert "Block_1/moe/w_down" in split and "mtp/block/moe/w_up" in split
    assert "Block_1/moe/router" not in split and "lm_head" not in split
    # on the one-chip mesh the adapter builds, placement is a no-op
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    placed = shard_params(params, mesh, glm_moe_rules())
    assert jax.tree_util.tree_structure(placed) == \
        jax.tree_util.tree_structure(params)


# -- (h) the cell's rehearsal twin through the benchmark's command ------------


def test_rehearsal_twin_runs_through_the_benchmark_command(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "glm-4.7-flash.train-b1-t8192", "--seed", "3000000007",
         "--seconds", "2", "--trace", "0", "--rehearse", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(line) for line in out.stdout.splitlines()
             if line.startswith("{")]
    result = lines[-1]
    assert result["correct"] is False  # a rehearsal never is
    assert result["failed"] == 0 and result["attempted"] > 0
    window = next(x for x in lines if x.get("phase") == "window")
    assert all(window["checks"].values()), window["checks"]
    assert {"dropped_is_zero", "reference_objective",
            "reference_gradients", "reference_route_counts"} <= set(
        window["checks"])
    counters = next(x for x in lines if x.get("phase") == "counters")
    assert counters["dropped"] == 0
    assert next(x for x in lines if x.get("phase") == "plan")[
        "buffer_rows"] == 64 * 2
