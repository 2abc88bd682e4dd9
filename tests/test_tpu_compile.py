"""The main path's Pallas kernels, compiled for a described TPU v5e.

The CPU suite runs every kernel in interpret mode, which accepts
programs the chip's compiler refuses (a dot with its batch dimension in
the middle, a scratch buffer past scoped VMEM). libtpu compiles for a
topology that is described and not attached, so these cases hand each
kernel of the main path, at its real widths, to Mosaic itself: nothing
runs, but what the compiler would refuse on the chip it refuses here.

One file on purpose: only one process may load libtpu, and under xdist
the worker that is handed this file is that process. The topology is
therefore described inside a fixture, never while a module is imported.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

# GPT-2-small widths: the LM benchmark's `--size small --batch 8 --seq 1024`
B, T, H, D = 8, 1024, 12, 64
HIDDEN, VOCAB = 768, 50257
# serving shape: KF_SERVE_MAX_BATCH=8, KF_KV_BLOCK_TOKENS=16, max_len 1024
SERVE_BATCH, BLOCK_TOKENS, MAX_LEN, LAYERS = 8, 16, 1024, 12


@pytest.fixture(scope="module")
def topo():
    """A described (not attached) v5e 2x2, with the persistent compile
    cache off around the module: an entry written for a described chip
    cannot be read back without one, and the next compile would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    """(2, 2) ("data", "model") over the four described chips — the
    sequence tests use the "model" axis as their ring."""
    return Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _kernels(compiled):
    return compiled.as_text().count("tpu_custom_call")


def _qkv(sharding, b=B, t=T, h=H, d=D):
    s = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=sharding)
    return s, s, s


@pytest.mark.parametrize("scheme", [None, "resident", "stream"],
                         ids=["head", "resident", "stream"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_flash_gpt2_small(one_chip, monkeypatch, scheme, grad):
    from kungfu_tpu.ops import flash

    # unforced, the auto pick at this shape is the head kernels: the
    # ones the GPT cells run
    monkeypatch.setattr(flash, "_FORCE_SCHEME", scheme)
    plan = flash.flash_plan(T, D, dtype=jnp.bfloat16, causal=True)
    assert plan["dkv"]["scheme"] == (scheme or "head")

    def fwd(q, k, v):
        return flash.flash_attention(q, k, v, causal=True,
                                     interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    # forward is one kernel; the backward adds dq, dk and dv, which
    # the head scheme computes in one kernel of its own and the fused
    # backward in one behind the loops and the streaming grid alike
    assert plan["bwd"]["scheme"] == ("stream_fused" if scheme else "head")
    assert plan["bwd"]["block_matmuls"] == 5
    assert _kernels(_compile(fn, *_qkv(one_chip))) == (2 if grad else 1)


@pytest.mark.parametrize("t,d,dtype", [
    (2048, 64, jnp.bfloat16),   # the widest step the budget lets in
    (1024, 128, jnp.float32),
    (1024, 256, jnp.bfloat16),
], ids=["t2048", "t1024-d128-f32", "t1024-d256"])
def test_flash_head_kernels_at_the_budgets_edge(one_chip, t, d, dtype):
    """The head kernels hold a whole head and a [256, t - 256] score
    step in VMEM: where `_head_vmem` says that fits, Mosaic agrees."""
    from kungfu_tpu.ops import flash

    plan = flash.flash_plan(t, d, dtype=dtype, causal=True)
    assert {plan[w]["scheme"] for w in ("fwd", "dq", "dkv")} == {"head"}

    def loss(q, k, v):
        return flash.flash_attention(
            q, k, v, causal=True, interpret=False).astype(
                jnp.float32).sum()

    s = jax.ShapeDtypeStruct((2, t, 4, d), dtype, sharding=one_chip)
    assert _kernels(_compile(jax.grad(loss, argnums=(0, 1, 2)),
                             s, s, s)) == 2


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_flash_latent_attention_d256_t8192(one_chip, grad):
    """The `glm-4.7-flash.train-b1-t8192` cell's call: one sequence of
    8192, 20 heads of 256, bf16, causal. Past the head kernels
    (`_HEAD_MAX_CHUNKS`), so the budget picks 1024 x 512 tiles on the
    streaming grid for the forward, and the backward is the ONE fused
    kernel at its own 1024 x 1024 with a whole head's f32 dq in VMEM;
    Mosaic takes both, the second under the `vmem_limit_bytes` it
    states."""
    from kungfu_tpu.ops import flash

    plan = flash.flash_plan(8192, 256, dtype=jnp.bfloat16, causal=True)
    assert (plan["block_q"], plan["block_k"]) == (1024, 512)
    assert {plan[w]["scheme"] for w in ("fwd", "dq", "dkv")} == {"stream"}
    assert (plan["fwd"]["block_q"], plan["fwd"]["block_k"]) == (1024, 1024)
    assert plan["bwd"]["scheme"] == "stream_fused"
    assert (plan["bwd"]["block_q"], plan["bwd"]["block_k"]) == (1024, 1024)
    assert plan["bwd"]["vmem_bytes"] <= flash._BWD_STREAM_VMEM_LIMIT

    def fwd(q, k, v):
        return flash.flash_attention(q, k, v, causal=True,
                                     interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    shapes = _qkv(one_chip, b=1, t=8192, h=20, d=256)
    compiled = _compile(fn, *shapes)
    assert _kernels(compiled) == (2 if grad else 1)
    # the scoped VMEM the fused kernel states, and nothing else does
    stated = f'"size":"{flash._BWD_STREAM_VMEM_LIMIT}"'
    assert compiled.as_text().count(stated) == (1 if grad else 0)
    _assert_forward_states_its_limit(compiled)


def _assert_forward_states_its_limit(compiled):
    """The window-less forward on the streaming grid hands Mosaic the
    scoped-VMEM limit it states, once a call."""
    from kungfu_tpu.ops import flash

    stated = f'"size":"{flash._FWD_STREAM_VMEM_LIMIT}"'
    assert compiled.as_text().count(stated) == 1


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_flash_looped_model_d128_t4096(one_chip, grad):
    """The `ouro-2.6b.train-b1-t4096` cell's call, 32 times a step: one
    sequence of 4096, 16 heads of 128, bf16, causal. Past the head
    kernels, inside the budget of the resident loops: the forward on
    them at 1024 x 512 tiles, and the ONE fused backward kernel at its
    own 1024 x 1024 under the `vmem_limit_bytes` it states (PR 33; the
    resident dq + dkv pair until then). The forward streams at its own
    1024 x 1024 under the limit it states."""
    from kungfu_tpu.ops import flash

    plan = flash.flash_plan(4096, 128, dtype=jnp.bfloat16, causal=True)
    assert (plan["block_q"], plan["block_k"]) == (1024, 512)
    assert plan["fwd"]["scheme"] == "stream"
    assert (plan["fwd"]["block_q"], plan["fwd"]["block_k"]) == (1024, 1024)
    assert plan["bwd"]["scheme"] == "stream_fused"
    assert (plan["bwd"]["block_q"], plan["bwd"]["block_k"]) == (1024, 1024)
    assert plan["bwd"]["vmem_bytes"] <= flash._BWD_STREAM_VMEM_LIMIT

    def fwd(q, k, v):
        return flash.flash_attention(q, k, v, causal=True,
                                     interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    compiled = _compile(fn, *_qkv(one_chip, b=1, t=4096, h=16, d=128))
    assert _kernels(compiled) == (2 if grad else 1)
    stated = f'"size":"{flash._BWD_STREAM_VMEM_LIMIT}"'
    assert compiled.as_text().count(stated) == (1 if grad else 0)
    _assert_forward_states_its_limit(compiled)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_flash_d64_t4096(one_chip, grad):
    """The `gpt2-small.train-b2-t4096` cell's call, 12 times a step: two
    sequences of 4096, 12 heads of 64, bf16, causal. Past the head
    kernels: the forward on the streaming grid at its own 1024 x 1024
    (the diagonal's blocks alone masked, nothing fetched past it) and
    the ONE fused backward, each under the limit it states."""
    from kungfu_tpu.ops import flash

    plan = flash.flash_plan(4096, 64, dtype=jnp.bfloat16, causal=True)
    assert plan["fwd"] == {
        "scheme": "stream", "block_q": 1024, "block_k": 1024,
        "visited_blocks": 10, "masked_blocks": 4, "fetched_blocks": 9,
        "grid_blocks": 16}
    assert plan["bwd"]["scheme"] == "stream_fused"

    def fwd(q, k, v):
        return flash.flash_attention(q, k, v, causal=True,
                                     interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    compiled = _compile(fn, *_qkv(one_chip, b=2, t=4096, h=12, d=64))
    assert _kernels(compiled) == (2 if grad else 1)
    stated = f'"size":"{flash._BWD_STREAM_VMEM_LIMIT}"'
    assert compiled.as_text().count(stated) == (1 if grad else 0)
    _assert_forward_states_its_limit(compiled)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("window", [None, 2047], ids=["full", "sliding"])
def test_flash_grouped_heads_d128_t8192(one_chip, window, grad):
    """The `trinity-mini.train-b1-t8192` cell's two calls: one sequence
    of 8192, 32 query heads of 128 on 4 K/V heads, bf16, causal. A full
    layer's: the forward on the streaming grid and ONE fused backward
    kernel, each at its own 1024 x 1024. A sliding layer's (window
    2047 = `sliding_window - 1`): 512 x 512 tiles, the forward on the
    resident loops and ONE fused backward, `_bwd_res_kernel` (PR 37).
    Both backwards state their VMEM limit. K/V reach every kernel
    through `i // 8` in their block specs' index maps, which Mosaic
    takes in the resident, streaming and fused forms alike."""
    from kungfu_tpu.ops import flash

    plan = flash.flash_plan(8192, 128, dtype=jnp.bfloat16, causal=True,
                            window=window, q_per_kv=8)
    assert plan["fwd"]["scheme"] == ("resident" if window else "stream")
    assert plan["bwd"]["scheme"] == ("resident_fused" if window
                                     else "stream_fused")
    assert (plan["block_q"], plan["block_k"]) == (
        (512, 512) if window else (1024, 512))

    def fwd(q, k, v):
        return flash.flash_attention(q, k, v, causal=True, window=window,
                                     interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    q = _qkv(one_chip, b=1, t=8192, h=32, d=128)[0]
    k = _qkv(one_chip, b=1, t=8192, h=4, d=128)[0]
    compiled = _compile(fn, q, k, k)
    assert _kernels(compiled) == (2 if grad else 1)
    stated = f'"size":"{flash._BWD_STREAM_VMEM_LIMIT}"'
    assert compiled.as_text().count(stated) == (1 if grad else 0)
    if window is None:
        _assert_forward_states_its_limit(compiled)
    if grad:   # dk and dv come back at the K/V heads' count
        assert [x.shape for x in compiled.out_info] == [
            (1, 8192, 32, 128), (1, 8192, 4, 128), (1, 8192, 4, 128)]


def test_grouped_expert_matmuls_top8_of_128(one_chip):
    """The held experts' grouped SwiGLU at the `trinity-mini` cell's
    sizes: 8 experts of 2048 x 1024 over the worst-case row buffer
    (8192 tokens x min(8, 8) = 65536 rows), forward and backward."""
    from kungfu_tpu.parallel.grouped_moe import buffer_rows, grouped_swiglu

    rows, h, f, held = buffer_rows(8192, 8, (0, 8)), 2048, 1024, 8
    assert rows == 65536

    def loss(x, w_gate, w_up, w_down, sizes):
        return grouped_swiglu(x, w_gate, w_up, w_down, sizes).astype(
            jnp.float32).sum()

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3)),
        arg((rows, h), jnp.bfloat16), arg((held, h, f), jnp.float32),
        arg((held, h, f), jnp.float32), arg((held, f, h), jnp.float32),
        arg((held,), jnp.int32))
    assert compiled.as_text().count("ragged-dot") >= 9


def test_row_cross_entropy_looped_model_head(one_chip):
    """One of the cell's four head + CE calls: 4095 rows of 2048
    against the whole 49152-row vocabulary, every row's loss under a
    cotangent of its own (`ops/fused_ce_rows.py`): the forward kernel
    and the d rebuild, as `fused_cross_entropy(residual=True)`."""
    from kungfu_tpu.ops.fused_ce_rows import fused_cross_entropy_rows

    def loss(x, w, t, r):
        return (fused_cross_entropy_rows(x, w, t, interpret=False)
                * r).sum()

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 3)),
        sds((4095, 2048), jnp.bfloat16), sds((2048, 49152), jnp.float32),
        sds((4095,), jnp.int32), sds((4095,), jnp.float32))
    assert _kernels(compiled) == 2


@pytest.mark.parametrize("t,d,dtype,causal,scheme,kernels", [
    # the largest estimate under the limit (63.0 MiB): Mosaic agrees
    (32768, 128, jnp.float32, True, "stream_fused", 2),
    (8192, 256, jnp.float32, False, "stream_fused", 2),
    (16384, 256, jnp.bfloat16, True, "stream_fused", 2),
    # a head's f32 dq no longer fits: the streaming dq + dkv pair
    (32768, 256, jnp.bfloat16, True, "stream", 3),
    (65536, 64, jnp.bfloat16, True, "stream", 3),
], ids=["t32k-d128-f32", "t8k-d256-f32-full", "t16k-d256", "t32k-d256-pair",
        "t64k-d64-pair"])
def test_flash_streaming_backward_at_the_limits_edge(
        one_chip, t, d, dtype, causal, scheme, kernels):
    """Where `_bwd_stream_vmem` says a head's dq fits the limit the
    fused kernel states, Mosaic takes it; past it the pair runs, and
    Mosaic takes that."""
    from kungfu_tpu.ops import flash

    plan = flash.flash_plan(t, d, dtype=dtype, causal=causal)
    assert plan["bwd"]["scheme"] == scheme

    def loss(q, k, v):
        return flash.flash_attention(
            q, k, v, causal=causal, interpret=False).astype(
                jnp.float32).sum()

    s = jax.ShapeDtypeStruct((1, t, 2, d), dtype, sharding=one_chip)
    assert _kernels(_compile(jax.grad(loss, argnums=(0, 1, 2)),
                             s, s, s)) == kernels


def test_grouped_expert_matmuls_published_widths(one_chip):
    """The held experts' grouped SwiGLU at the cell's sizes: 8 experts
    of 2048 x 1536 over the worst-case row buffer (8192 tokens x
    min(4, 8) rows), forward and backward. XLA:TPU lowers each
    `ragged_dot` to a Mosaic kernel that walks row tiles by group; if
    a later JAX expands it into dense masked matmuls this count
    falls."""
    from kungfu_tpu.parallel.grouped_moe import buffer_rows, grouped_swiglu

    rows, h, f, held = buffer_rows(8192, 4, (0, 8)), 2048, 1536, 8
    assert rows == 32768

    def loss(x, w_gate, w_up, w_down, sizes):
        return grouped_swiglu(x, w_gate, w_up, w_down, sizes).astype(
            jnp.float32).sum()

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3)),
        arg((rows, h), jnp.bfloat16), arg((held, h, f), jnp.float32),
        arg((held, h, f), jnp.float32), arg((held, f, h), jnp.float32),
        arg((held,), jnp.int32))
    # three forward, three input-gradient and three weight-gradient
    # grouped matmuls (beside the kernels that lay out their tiles)
    assert compiled.as_text().count("ragged-dot") >= 9


def test_flash_window_16k(one_chip):
    from kungfu_tpu.ops.flash import flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=512,
                              interpret=False)
        return out.astype(jnp.float32).sum()

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                        *_qkv(one_chip, b=1, t=16384, h=8))
    assert _kernels(compiled) == 2   # forward + ONE backward (PR 37)


@pytest.mark.parametrize("residual", [True, False],
                         ids=["residual", "recompute"])
def test_fused_ce_gpt2_small(one_chip, residual):
    from kungfu_tpu.ops.fused_ce import fused_cross_entropy

    def loss(x, w, b, t):
        return fused_cross_entropy(x, w, b, t, interpret=False,
                                   residual=residual)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2)),
        sds((B * T, HIDDEN), jnp.bfloat16),
        sds((HIDDEN, VOCAB), jnp.float32), sds((VOCAB,), jnp.float32),
        sds((B * T,), jnp.int32))
    # residual: forward + the d rebuild; recompute: forward + dW + dx
    assert _kernels(compiled) == (2 if residual else 3)


@pytest.mark.parametrize("dtype,max_len,scheme,plan", [
    (jnp.bfloat16, MAX_LEN, "resident", "resident"),
    (jnp.bfloat16, MAX_LEN, "stream", "resident"),
    # chip_smoke.py's float32 token check: resident where float32 fits
    (jnp.float32, MAX_LEN // 2, "resident", "resident"),
    (jnp.float32, MAX_LEN, "stream", "stream"),
], ids=["bf16-resident", "bf16-stream", "f32-512-resident", "f32-stream"])
def test_paged_attention_gpt2_small_serving(one_chip, dtype, max_len,
                                            scheme, plan):
    from kungfu_tpu.ops.paged_attn import paged_attention, paged_plan

    max_blocks = max_len // BLOCK_TOKENS
    pool_blocks = SERVE_BATCH * max_blocks + 1
    # what `paged_plan` offers at this shape must be what compiles
    assert paged_plan(max_blocks, BLOCK_TOKENS, H, D,
                      dtype=dtype)["scheme"] == plan

    def sds(shape, dtype=dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((LAYERS * pool_blocks, BLOCK_TOKENS, H, D))
    compiled = _compile(
        lambda q, k, v, tables, lengths: paged_attention(
            q, k, v, tables, lengths, block_base=pool_blocks,
            scheme=scheme, interpret=False),
        sds((SERVE_BATCH, H, D)), pool, pool,
        sds((SERVE_BATCH, max_blocks), jnp.int32),
        sds((SERVE_BATCH,), jnp.int32))
    assert _kernels(compiled) == 1


@pytest.mark.parametrize("mixer", ["ring", "ulysses"])
def test_sequence_parallel_flash_4chips(mesh4, monkeypatch, mixer):
    """4096 positions over a 4-chip ring, 1024 a chip. The mixers leave
    `interpret` to `jax.default_backend()`, which is the CPU here, so
    the test answers for the chip the program is compiled for."""
    from kungfu_tpu.parallel import sequence

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ring = Mesh(mesh4.devices.reshape(4), ("seq",))
    attend = {"ring": sequence.ring_attention,
              "ulysses": sequence.ulysses_attention}[mixer]

    def local(q, k, v):
        return attend(q, k, v, "seq", causal=True, use_flash=True)

    mapped = jax.shard_map(local, mesh=ring,
                           in_specs=(P(None, "seq"),) * 3,
                           out_specs=P(None, "seq"), check_vma=False)

    def loss(q, k, v):
        return mapped(q, k, v).astype(jnp.float32).sum()

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2)),
        *_qkv(NamedSharding(ring, P(None, "seq")), b=1, t=4 * T))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("collective-permute" if mixer == "ring"
            else "all-to-all") in text


def test_vocab_sharded_ce_4chips(mesh4):
    from kungfu_tpu.parallel.vocab_ce import vocab_sharded_fused_ce

    def loss(x, w, b, t):
        return vocab_sharded_fused_ce(x, w, b, t, mesh=mesh4)

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh4, P(*spec)))

    compiled = _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2)),
        sds((B * T, HIDDEN), jnp.bfloat16, "data"),
        # the head arrives replicated, as the rules tables leave it
        # (50257 divides no model axis): the op pads and shards it
        sds((HIDDEN, VOCAB), jnp.float32), sds((VOCAB,), jnp.float32),
        sds((B * T,), jnp.int32, "data"))
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
