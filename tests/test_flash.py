"""Flash-attention Pallas kernel vs plain attention (interpret mode).

The kernel streams K/V blocks through VMEM with online softmax; on the
CPU test backend it runs under the Pallas interpreter, which executes
the same program the Mosaic compiler lowers on TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kungfu_tpu.ops import flash_attention
from kungfu_tpu.ops.flash import _plain_attention


def qkv(b=2, t=256, h=4, d=32, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, t, h, d), dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_matches_plain(causal):
    q, k, v = qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = _plain_attention(q, k, v, causal, 1.0 / (32 ** 0.5))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_uneven_blocks_within_t():
    """block_q != block_k exercises the causal diagonal handling."""
    q, k, v = qkv(t=256)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=64)
    ref = _plain_attention(q, k, v, True, 1.0 / (32 ** 0.5))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t,window,blocks", [
    (256, 32, (64, 64)),    # window smaller than a block: in-block mask
    (256, 100, (64, 64)),   # window spans blocks, odd size
    (256, 64, (128, 64)),   # uneven blocks + whole-block skipping
    (128, 8, (None, None)),  # auto single-block path
])
def test_sliding_window_matches_masked_plain(t, window, blocks):
    """Mistral-style local attention: position q sees keys [q-window, q].
    Blocks entirely outside the window are skipped (O(T*window)
    compute), so both the mask math and the skip logic are under test."""
    q, k, v = qkv(t=t)
    bq, bk = blocks
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                          window=window)
    ref = _plain_attention(q, k, v, True, 1.0 / (32 ** 0.5),
                           window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_sliding_window_grads_match_masked_plain():
    q, k, v = qkv(t=256)
    g = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def loss_flash(q, k, v):
        return jnp.vdot(flash_attention(q, k, v, causal=True,
                                        block_q=64, block_k=64,
                                        window=50), g)

    def loss_ref(q, k, v):
        return jnp.vdot(_plain_attention(q, k, v, True,
                                         1.0 / (32 ** 0.5), window=50),
                        g)

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gp = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("dq dk dv".split(), gf, gp):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=2e-5 * scale,
                                   err_msg=name)


def test_window_wider_than_t_equals_causal():
    q, k, v = qkv(t=128)
    out = flash_attention(q, k, v, causal=True, window=1000)
    ref = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_window_requires_causal():
    q, k, v = qkv(t=128)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=16)


def test_bf16_io_f32_accumulate():
    q, k, v = qkv(dtype=jnp.bfloat16, t=128)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    assert out.dtype == jnp.bfloat16
    ref = _plain_attention(q, k, v, True, 1.0 / (32 ** 0.5))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_untileable_shapes_fall_back():
    q, k, v = qkv(t=1000)  # > 512 and no 128/256/512 divisor
    out = flash_attention(q, k, v, causal=False)
    ref = _plain_attention(q, k, v, False, 1.0 / (32 ** 0.5))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_with_flash_local_step():
    """use_flash swaps the Ulysses local mixer without changing results."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from kungfu_tpu.parallel import ulysses_attention

    b, t, h, d = 1, 256, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(kk, (b, t, h, d)) for kk in ks)
    mesh = Mesh(np.array(jax.devices()[:8]), ("seq",))

    def run(use_flash):
        fn = shard_map(
            lambda q, k, v: ulysses_attention(
                q, k, v, "seq", causal=True, use_flash=use_flash),
            mesh=mesh, in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"), check_vma=False)
        return jax.jit(fn)(q, k, v)

    np.testing.assert_allclose(np.asarray(run(True)),
                               np.asarray(run(False)),
                               rtol=2e-5, atol=2e-5)


def test_jit_and_grad():
    q, k, v = qkv(t=128)

    @jax.jit
    def loss(q):
        return (flash_attention(q, k, v, causal=True,
                                block_q=64, block_k=64) ** 2).sum()

    g = jax.grad(loss)(q)

    def loss_plain(q):
        return (_plain_attention(q, k, v, True, 1.0 / (32 ** 0.5))
                ** 2).sum()

    g_ref = jax.grad(loss_plain)(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


class TestFlashBackwardKernels:
    """The fused backward kernels (dq / dk+dv) vs the plain-attention VJP.

    Comparisons run under `highest` matmul precision: this platform's
    default f32 matmul is bf16-grade (~1e-1 abs error on unit normals),
    which would swamp the kernel-vs-plain delta being measured.
    """

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("t,block_q,block_k",
                             [(256, 128, 128), (512, 128, 64),
                              (128, 64, 64)])
    def test_grads_match_plain(self, causal, t, block_q, block_k):
        with jax.default_matmul_precision("highest"):
            q, k, v = qkv(t=t, d=64)
            g = jax.random.normal(jax.random.PRNGKey(9), q.shape,
                                  q.dtype)

            def loss_flash(q, k, v):
                return jnp.vdot(
                    flash_attention(q, k, v, causal, None, block_q,
                                    block_k), g)

            def loss_plain(q, k, v):
                return jnp.vdot(
                    _plain_attention(q, k, v, causal,
                                     1.0 / (64 ** 0.5)), g)

            gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
            gp = jax.grad(loss_plain, argnums=(0, 1, 2))(q, k, v)
            for name, a, b in zip("dq dk dv".split(), gf, gp):
                scale = float(jnp.max(jnp.abs(b)))
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b),
                    rtol=0, atol=2e-4 * scale, err_msg=name)

    def test_bf16_grads(self):
        q, k, v = qkv(t=128, dtype=jnp.bfloat16)
        g = jax.random.normal(jax.random.PRNGKey(9), q.shape, q.dtype)

        def loss(q, k, v):
            return jnp.vdot(
                flash_attention(q, k, v, True, None, 64, 64)
                .astype(jnp.float32), g.astype(jnp.float32))

        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        for name, a in zip("dq dk dv".split(), grads):
            assert a.dtype == jnp.bfloat16, name
            assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32)))), name
            assert float(jnp.max(jnp.abs(a.astype(jnp.float32)))) > 0, name

    def test_untileable_shape_grads_fall_back(self):
        """t=1000 doesn't tile (> 512, no MXU-sized divisor): forward
        AND backward take the plain path (the residual carries
        lse=None), still correct. Short non-tiling lengths (<= 512)
        now run the kernel as a single block instead."""
        with jax.default_matmul_precision("highest"):
            q, k, v = qkv(t=1000)
            g = jax.random.normal(jax.random.PRNGKey(9), q.shape)

            def loss_flash(q, k, v):
                return jnp.vdot(flash_attention(q, k, v, True), g)

            def loss_plain(q, k, v):
                return jnp.vdot(
                    _plain_attention(q, k, v, True,
                                     1.0 / (32 ** 0.5)), g)

            gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
            gp = jax.grad(loss_plain, argnums=(0, 1, 2))(q, k, v)
            for a, b in zip(gf, gp):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-5)

    def test_above_lane_width_blocks(self):
        """Regression: block sizes > 128 that are not multiples of 128
        crashed the backward's lane-broadcast tiling (_rowvals)."""
        with jax.default_matmul_precision("highest"):
            q, k, v = qkv(t=384, d=64)
            g = jax.random.normal(jax.random.PRNGKey(9), q.shape)

            def loss(q, k, v):
                return jnp.vdot(
                    flash_attention(q, k, v, False, None, 192, 192), g)

            def loss_plain(q, k, v):
                return jnp.vdot(
                    _plain_attention(q, k, v, False,
                                     1.0 / (64 ** 0.5)), g)

            gf = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            gp = jax.grad(loss_plain, argnums=(0, 1, 2))(q, k, v)
            for a, b in zip(gf, gp):
                scale = float(jnp.max(jnp.abs(b)))
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=0, atol=2e-4 * scale)


def test_explicit_nondividing_blocks_fall_back():
    """Explicit block sizes that don't divide T must take the plain
    fallback (auto-mode tests no longer exercise this branch)."""
    from kungfu_tpu.ops.flash import _tiles

    assert _tiles(100, False, 64, 64) is None
    q, k, v = qkv(t=100)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = _plain_attention(q, k, v, True, 1.0 / (32 ** 0.5))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_flash_grads_match_plain():
    """The long-context TRAINING composition: gradients flow through
    the flash kernel inside the Ulysses shard_map and match the plain
    local-mixer run.

    Was strict-xfailed in round 2: the reshape-wrapped
    `all_to_all(tiled=False)` formulation miscompiles the BACKWARD under
    shard_map(check_vma=False) (upstream JAX 0.9.0 — minimal repro in
    docs/long_context.md). seq_to_heads/heads_to_seq now use tiled=True,
    which needs no reshapes around the collective, so grads flow."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from kungfu_tpu.parallel import ulysses_attention

    b, t, h, d = 1, 256, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v = (jax.random.normal(kk, (b, t, h, d)) for kk in ks[:3])
    g = jax.random.normal(ks[3], (b, t, h, d))
    mesh = Mesh(np.array(jax.devices()[:8]), ("seq",))

    def grads(use_flash):
        fn = shard_map(
            lambda q, k, v: ulysses_attention(
                q, k, v, "seq", causal=True, use_flash=use_flash),
            mesh=mesh, in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"), check_vma=False)

        def loss(q, k, v):
            return jnp.vdot(fn(q, k, v), g)

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    with jax.default_matmul_precision("highest"):
        gf = grads(True)
        gp = grads(False)
    for name, a, b_ in zip("dq dk dv".split(), gf, gp):
        scale = float(jnp.max(jnp.abs(b_)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=0, atol=2e-4 * scale,
                                   err_msg=name)


def test_windowed_narrowing_generalizes_to_rect_blocks():
    """block_q = m*block_k with a sliding window: the round-5 affine
    narrowing (span = m + ceil(w/bk), K/V front-padded by span-m
    blocks) must match the plain masked reference in fwd AND grads for
    m in {1, 2, 4}, including a window that doesn't divide block_k."""
    from kungfu_tpu.ops.flash import flash_attention
    from kungfu_tpu.parallel.sequence import _local_attention

    b, t, h, d = 1, 2048, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, h, d), jnp.float32)
    ct = jax.random.normal(ks[3], (b, t, h, d), jnp.float32)
    for window in (256, 300):
        ref, ref_vjp = jax.vjp(
            lambda q, k, v: _local_attention(
                q, k, v, causal=True, scale=d ** -0.5, window=window),
            q, k, v)
        ref_g = ref_vjp(ct)
        for bq, bk in ((256, 256), (512, 256), (1024, 256)):
            got, got_vjp = jax.vjp(
                lambda q, k, v: flash_attention(
                    q, k, v, causal=True, window=window,
                    block_q=bq, block_k=bk), q, k, v)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(ref), atol=2e-2)
            for a, r in zip(got_vjp(ct), ref_g):
                np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                           atol=3e-2)


# ---------------------------------------------------------------------------
# bf16 inputs: the block matmuls take bf16 operands (f32 accumulation);
# the reference is the f32 plain attention on the same (upcast) inputs
# ---------------------------------------------------------------------------


def _f32_reference(q, k, v, g, causal):
    """(out, dq, dk, dv) of the plain attention computed in f32, at
    full matmul precision, on bf16 inputs upcast to f32."""
    d = q.shape[-1]
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(
            lambda q, k, v: _plain_attention(q, k, v, causal, d ** -0.5),
            *(x.astype(jnp.float32) for x in (q, k, v)))
        return (out,) + vjp(g.astype(jnp.float32))


def _max_errs(got, refs):
    return [float(jnp.max(jnp.abs(a.astype(jnp.float32) - r)))
            for a, r in zip(got, refs)]


@pytest.mark.parametrize("scheme", ["resident", "stream", None])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blocks", [
    (64, 64),    # nq = nk = 4: diagonal and interior blocks both run
    (128, 64),   # rect: two k-blocks of every q-block cross the diagonal
    (256, 256),  # one block, the diagonal's mask only
])
def test_bf16_operands_match_f32_plain(monkeypatch, scheme, causal,
                                       blocks):
    """bf16 in: forward and jax.grad against the f32 reference, within
    what bf16 operands (8 mantissa bits on p, ds and the inputs) and a
    bf16 output can hold. All schemes share the block step; `None`
    leaves the choice to the budget (causal square tiles: the head
    kernels)."""
    import kungfu_tpu.ops.flash as F

    monkeypatch.setattr(F, "_FORCE_SCHEME", scheme)
    q, k, v = qkv(b=1, t=256, h=2, d=64, dtype=jnp.bfloat16)
    g = jax.random.normal(jax.random.PRNGKey(9), q.shape, q.dtype)
    bq, bk = blocks
    out, vjp = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                        block_q=bq, block_k=bk), q, k, v)
    got = (out,) + vjp(g)
    refs = _f32_reference(q, k, v, g, causal)
    for name, a, r in zip("out dq dk dv".split(), got, refs):
        assert a.dtype == jnp.bfloat16, name
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(r), rtol=0,
            atol=1e-2 * scale, err_msg=f"{scheme} {name}")


def test_bf16_kernel_no_worse_than_the_models_plain_bf16_attention():
    """The yardstick for 'the configuration's stated precision': against
    the f32 reference, the kernel's error on bf16 inputs is no larger
    than 1.5 x that of the model's own plain path
    (`attention="local"`: flax's dot_product_attention in bfloat16),
    for the output and each of dq, dk, dv."""
    import flax.linen as nn

    t, d = 256, 64
    q, k, v = qkv(b=2, t=t, h=4, d=d, dtype=jnp.bfloat16)
    g = jax.random.normal(jax.random.PRNGKey(9), q.shape, q.dtype)
    refs = _f32_reference(q, k, v, g, True)

    def run(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return _max_errs((out,) + vjp(g), refs)

    mask = nn.make_causal_mask(jnp.ones((q.shape[0], t)))
    plain = run(lambda q, k, v: nn.dot_product_attention(
        q, k, v, mask=mask, dtype=jnp.bfloat16))
    # auto tiles, and a tiling whose diagonal and interior blocks both run
    for blocks in ((None, None), (64, 64)):
        flash = run(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=blocks[0], block_k=blocks[1]))
        for name, f, p in zip("out dq dk dv".split(), flash, plain):
            assert f <= 1.5 * p, (blocks, name, f, p)


@pytest.mark.parametrize("scheme", [None, "resident", "stream"],
                         ids=["head", "resident", "stream"])
def test_checkpoint_names_leave_nothing_outside_a_checkpoint(
        monkeypatch, scheme):
    """`FLASH_OUT` / `FLASH_LSE` are for a recomputing caller's policy
    (`models/glm_moe.py::_block`). With no `jax.checkpoint` above the
    call, which is how the GPT cells and Ulysses run it, the lowered
    gradient is the same text with and without them (but for the
    counter in a private function's symbol, `@_where_58`)."""
    import re

    from kungfu_tpu.ops import flash

    monkeypatch.setattr(flash, "_FORCE_SCHEME", scheme)
    q, k, v = qkv(t=512, dtype=jnp.bfloat16)
    assert flash.flash_plan(512, 32, dtype=q.dtype, causal=True)["fwd"][
        "scheme"] == (scheme or "head")

    def lowered():
        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True).astype(
                jnp.float32).sum()

        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, k, v).as_text()
        return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)

    named = lowered()
    monkeypatch.setattr(flash, "checkpoint_name", lambda x, name: x)
    assert lowered() == named
