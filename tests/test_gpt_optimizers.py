"""The reference's distributed-optimizer families on the GPT model.

The optimizer transformations (sync_sgd / sma / pair_averaging) are
model-agnostic by design — these tests pin that down for the
transformer-LM family: each family takes real training steps on GPT
over the worker-stacked DP layout and reduces the loss, and sync_sgd's
workers stay bit-identical (the invariant the reference's S-SGD
guarantees via all-reduce).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from kungfu_tpu.models import GPTConfig, GPTLM, gpt_loss
from kungfu_tpu.optimizers import pair_averaging, sma, sync_sgd
from kungfu_tpu.parallel import (
    build_train_step,
    data_mesh,
    init_worker_state,
    replicate_to_workers,
    shard_batch,
)

N = 4
CFG = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                num_heads=4, intermediate_size=64, max_position=16,
                dtype=jnp.float32)


@pytest.fixture(scope="module")
def setup():
    model = GPTLM(CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4 * N, 16), 0,
                                CFG.vocab_size)
    params = model.init(jax.random.PRNGKey(1), tokens[:1])["params"]
    mesh = data_mesh(N, devices=jax.devices()[:N])
    return model, params, tokens, mesh


def run_family(tx, setup, steps=25):
    model, params, tokens, mesh = setup

    def loss_fn(p, batch):
        return gpt_loss(model.apply({"params": p}, batch["tokens"]),
                        batch["tokens"])

    params_s = replicate_to_workers(params, mesh)
    opt_s = init_worker_state(tx, params_s, mesh)
    step = build_train_step(loss_fn, tx, mesh)
    batch = shard_batch({"tokens": tokens}, mesh)
    first = None
    for _ in range(steps):
        params_s, opt_s, loss = step(params_s, opt_s, batch)
        first = float(loss) if first is None else first
    return first, float(loss), params_s


def test_sync_sgd_trains_gpt_and_rows_identical(setup):
    first, last, params_s = run_family(
        sync_sgd(optax.adam(1e-2)), setup)
    assert last < first / 2, (first, last)
    for leaf in jax.tree_util.tree_leaves(params_s):
        rows = np.asarray(jax.device_get(leaf))
        for r in range(1, N):
            np.testing.assert_array_equal(rows[0], rows[r])


def test_sma_trains_gpt(setup):
    first, last, _ = run_family(
        sma(optax.sgd(0.1), alpha=0.5), setup)
    assert last < first, (first, last)


def test_pair_averaging_trains_gpt(setup):
    first, last, _ = run_family(
        pair_averaging(optax.sgd(0.1)), setup)
    assert last < first, (first, last)


class TestGroupSmallLeaves:
    """group_small_leaves: only the small-leaf tail is concatenated,
    large leaves stay per-leaf. Must be bitwise-identical to per-leaf
    `inner`."""

    @staticmethod
    def _mixed_tree():
        """A GPT-shaped mix: big 2-D projections above the threshold,
        a long tail of layernorm/bias leaves below it, mixed dtypes."""
        params = {
            "wte": jnp.linspace(-1, 1, 64 * 32).reshape(64, 32
                                                        ).astype(jnp.float32),
            "blocks": {
                "proj": jnp.full((48, 48), 0.2, jnp.float32),
                "ln_scale": jnp.ones((48,), jnp.float32),
                "ln_bias": jnp.zeros((48,), jnp.float32),
                "bias_bf16": jnp.full((48,), 0.1, jnp.bfloat16),
                "gain_bf16": jnp.full((16,), 0.5, jnp.bfloat16),
            },
        }
        grads = jax.tree_util.tree_map(
            lambda p: (jnp.arange(p.size).reshape(p.shape)
                       / p.size).astype(p.dtype), params)
        return params, grads

    THRESHOLD = 1024  # big leaves: wte (2048) + proj (2304); rest tail

    @pytest.mark.parametrize("make", [
        lambda: optax.adamw(1e-3),
        lambda: optax.sgd(0.1, momentum=0.9),
        lambda: optax.adam(1e-2),
    ], ids=["adamw", "sgd-momentum", "adam"])
    def test_bitwise_parity_elementwise(self, make):
        from kungfu_tpu.optimizers import group_small_leaves

        params, grads0 = self._mixed_tree()
        ref_tx = make()
        grp_tx = group_small_leaves(make(), threshold=self.THRESHOLD)
        rp = gp = params
        rs, gs = ref_tx.init(rp), grp_tx.init(gp)
        for step in range(4):
            g = jax.tree_util.tree_map(lambda g: g * (step + 1), grads0)
            ru, rs = ref_tx.update(g, rs, rp)
            gu, gs = grp_tx.update(g, gs, gp)
            rp = optax.apply_updates(rp, ru)
            gp = optax.apply_updates(gp, gu)
        for a, b in zip(jax.tree_util.tree_leaves(rp),
                        jax.tree_util.tree_leaves(gp)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    @pytest.mark.parametrize("threshold", [1, 10**9],
                             ids=["all-big", "all-small"])
    def test_degenerate_partitions_still_exact(self, threshold):
        """threshold below every leaf (pure per-leaf) and above every
        leaf (the whole-tree flat buffer) are both valid partitions and
        must both stay bitwise-exact."""
        from kungfu_tpu.optimizers import group_small_leaves

        params, grads = self._mixed_tree()
        ref_tx = optax.adamw(1e-3)
        grp_tx = group_small_leaves(optax.adamw(1e-3),
                                    threshold=threshold)
        ru, _ = ref_tx.update(grads, ref_tx.init(params), params)
        gu, _ = grp_tx.update(grads, grp_tx.init(params), params)
        for a, b in zip(jax.tree_util.tree_leaves(ru),
                        jax.tree_util.tree_leaves(gu)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    def test_requires_params(self):
        from kungfu_tpu.optimizers import group_small_leaves

        params, grads = self._mixed_tree()
        tx = group_small_leaves(optax.adamw(1e-3))
        state = tx.init(params)
        with pytest.raises(ValueError, match="requires params"):
            tx.update(grads, state)

    def test_works_under_jit_train_step(self):
        """Grouped updates must trace inside a jitted train step on the
        real GPT tree (the layernorm/bias tail concatenates, the 2-D
        projections stay per-leaf) and train."""
        from kungfu_tpu.models import GPTConfig, GPTLM, gpt_fused_loss
        from kungfu_tpu.optimizers import group_small_leaves
        from kungfu_tpu.parallel import build_gspmd_train_step

        cfg = GPTConfig(vocab_size=128, hidden_size=128, num_layers=2,
                        num_heads=4, intermediate_size=256,
                        max_position=32)
        model = GPTLM(cfg)
        toks = jax.random.randint(jax.random.PRNGKey(0), (4, 32), 0,
                                  128)
        params = model.init(jax.random.PRNGKey(1), toks[:1])["params"]
        # hidden^2 = 16384 elems: a threshold of 1024 keeps every
        # projection per-leaf while the ln scales/biases (128) group
        tx = group_small_leaves(optax.adamw(1e-3), threshold=1024)
        opt = tx.init(params)
        step = build_gspmd_train_step(
            lambda p, t: gpt_fused_loss(model, p, t), tx)
        losses = []
        for _ in range(5):
            params, opt, loss = step(params, opt, toks)
            losses.append(float(loss))
        assert losses[-1] < losses[0]
