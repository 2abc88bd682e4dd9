"""Round-6 flash kernel overhaul guards: block-skip trip counts,
scheme selection, delta folding, and numerics of both execution
schemes against the masked plain-attention reference.

The resident kernels' fori_loop bounds come from `_k_span`/`_q_span`
and `flash_plan` derives its visited-block counts from the SAME
functions, so the structural tests here pin the actual work-skip of
all five loop nests (fwd/dq over k-blocks, dkv over q-blocks, causal
and windowed); the jaxpr tests pin that those kernels (2-D grids,
in-kernel loops) are really the ones a grad call runs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kungfu_tpu.ops.flash as F
from kungfu_tpu.ops.flash import _plain_attention, flash_attention


def qkv(b=1, t=512, h=2, d=64, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, t, h, d), dtype) for k in ks)


def _visible_block_mask(t, bq, bk, window):
    """[nq, nk] bool: does block (iq, jk) contain >= 1 causally (and
    window-) visible (q, k) pair — brute-forced from the position
    mask, the ground truth the span helpers must reproduce exactly."""
    q_pos = np.arange(t)[:, None]
    k_pos = np.arange(t)[None, :]
    keep = q_pos >= k_pos
    if window is not None:
        keep &= q_pos - k_pos <= window
    nq, nk = t // bq, t // bk
    return keep.reshape(nq, bq, nk, bk).any(axis=(1, 3))


@pytest.mark.parametrize("t,bq,bk,window", [
    (512, 64, 64, None),     # square blocks, pure causal
    (512, 128, 64, None),    # rect blocks (m=2), pure causal
    (512, 64, 64, 100),      # window spans blocks, odd size
    (1024, 256, 64, 300),    # m=4, window not a block multiple
    (512, 128, 128, 8),      # window smaller than a block
])
def test_span_helpers_cover_exactly_the_visible_blocks(t, bq, bk,
                                                       window):
    vis = _visible_block_mask(t, bq, bk, window)
    nq, nk = t // bq, t // bk
    for iq in range(nq):
        lo, hi = F._k_span(iq, nk, causal=True, window=window,
                           block_q=bq, block_k=bk)
        lo, hi = int(lo), int(hi)
        for jk in range(nk):
            assert (lo <= jk < hi) == vis[iq, jk], (iq, jk)
    for jk in range(nk):
        lo, hi = F._q_span(jk, nq, causal=True, window=window,
                           block_q=bq, block_k=bk)
        lo, hi = int(lo), int(hi)
        for iq in range(nq):
            assert (lo <= iq < hi) == vis[iq, jk], (iq, jk)


@pytest.mark.parametrize("t,block", [(512, 128), (1024, 256),
                                     (1024, 512), (384, 128)])
def test_head_kernels_unroll_the_causal_schedule(t, block):
    """The head scheme's trace IS its schedule: a grad call runs two
    kernels (forward; dq, dk and dv together) on a 1-D (B*H,) grid
    whose bodies hold 2*nb - 1 block steps (one masked square a chunk
    on the diagonal, one wide unmasked step for what lies under it) —
    counted by their matmuls (2 a forward step; 5 a backward step, not
    the 3 + 4 of a dq / dkv pair) and by the mask's two iotas, which
    only the nb diagonal steps build. `flash_plan` reports the same
    schedule in block units."""
    nb = t // block
    plan = F.flash_plan(t, 64, causal=True, block_q=block, block_k=block)
    for which in ("fwd", "dq", "dkv"):
        assert plan[which] == {
            "scheme": "head", "visited_blocks": nb * (nb + 1) // 2,
            "masked_blocks": nb, "grid_blocks": nb * nb,
            # the forward names its tiles and fetches the head once
            **({"block_q": block, "block_k": block, "fetched_blocks": nb}
               if which == "fwd" else {})}
    q, k, v = qkv(t=t)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None, block, block).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    eqns = _pallas_eqns(jaxpr.jaxpr)
    assert len(eqns) == 2
    for eqn, matmuls in zip(eqns, (2, 5)):
        assert len(eqn.params["grid_mapping"].grid) == 1
        body = eqn.params["jaxpr"]
        assert _count(body, "dot_general") == matmuls * (2 * nb - 1)
        assert _count(body, "iota") == 2 * nb
        assert _count(body, "while") == 0   # nothing left to loop over


def test_head_kernels_trace_once_for_every_layer():
    """A model calls the kernels once a layer at one shape: the second
    call replays the first's trace (the unrolled bodies are the
    expensive part of tracing a step), and lands under the caller's
    scope with no name between it and `pallas_call`."""
    q, k, v = qkv(t=640, h=3, d=16)   # a shape no other test traces
    traced = []
    real = F._fwd_head_kernel

    def counting(*a, **kw):
        traced.append(1)
        return real(*a, **kw)

    def model(q, k, v):
        x = q
        for i in range(3):
            with jax.named_scope(f"CausalSelfAttention_{i}"):
                x = flash_attention(x, k, v, causal=True, block_q=128,
                                    block_k=128)
        return x.sum()

    import unittest.mock as mock
    with mock.patch.object(F, "_fwd_head_kernel", counting):
        jaxpr = jax.make_jaxpr(jax.grad(model))(q, k, v)
    assert len(traced) == 1
    eqns = _pallas_eqns(jaxpr.jaxpr)
    assert len(eqns) == 6
    stacks = {str(e.source_info.name_stack) for e in eqns}
    # three layers, forward and backward, the scope innermost
    assert stacks == {f(f"jvp(CausalSelfAttention_{i})") for i in range(3)
                      for f in (str, "transpose({})".format)}


@pytest.mark.parametrize("kw,why", [
    (dict(causal=False), "no diagonal to schedule round"),
    (dict(causal=True, window=64), "a window cuts blocks at both ends"),
    (dict(causal=True, block_k=64), "rect tiles"),
    (dict(causal=True, block_q=512, block_k=512), "one chunk"),
    (dict(causal=True, block_q=32, block_k=32), "too many chunks"),
])
def test_head_scheme_leaves_other_shapes_to_the_loops(kw, why):
    kw = dict(dict(block_q=128, block_k=128), **kw)
    plan = F.flash_plan(512, 64, **kw)
    assert plan["fwd"]["scheme"] == "resident", why


@pytest.mark.parametrize("t,block,d", [(512, 128, 64), (512, 256, 64),
                                       (384, 128, 32)])
def test_head_scheme_matches_plain_fwd_and_grads(t, block, d):
    """f32 numerics of the statically scheduled kernels, at the loops'
    tolerances: same block steps, another order of visiting."""
    assert F.flash_plan(t, d, causal=True, block_q=block,
                        block_k=block)["dkv"]["scheme"] == "head"
    with jax.default_matmul_precision("highest"):
        q, k, v = qkv(t=t, d=d)
        g = jax.random.normal(jax.random.PRNGKey(9), q.shape)
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=block, block_k=block),
            q, k, v)
        ref, ref_vjp = jax.vjp(
            lambda q, k, v: _plain_attention(q, k, v, True, d ** -0.5),
            q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        for name, a, r in zip("dq dk dv".split(), vjp(g), ref_vjp(g)):
            scale = float(jnp.max(jnp.abs(r))) or 1.0
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=0, atol=2e-4 * scale,
                                       err_msg=name)


def test_gpt_cells_plan_bf16_operands_and_an_engaged_skip():
    """The counter that says PR 25's mechanism engages at the shape of
    both GPT benchmark cells (T 1024, d 64, bf16, causal, auto tiles;
    the plan is per head): bf16 operands, fewer visited than grid
    blocks in all three kernels, and fewer masked than visited ones."""
    plan = F.flash_plan(1024, 64, dtype=jnp.bfloat16, causal=True)
    assert plan["operand_dtype"] == "bfloat16"
    assert plan["block_q"] < 1024 and plan["block_q"] % plan["block_k"] == 0
    for which in ("fwd", "dq", "dkv"):
        k = plan[which]
        assert k["scheme"] == "head"
        assert k["masked_blocks"] < k["visited_blocks"] < k["grid_blocks"]


@pytest.mark.parametrize("dtype,want", [
    (jnp.float32, "float32"), (jnp.bfloat16, "bfloat16"),
    (jnp.float16, "float32"),   # anything but bf16 keeps the f32 upcast
])
def test_plan_reports_operand_dtype(dtype, want):
    plan = F.flash_plan(1024, 64, dtype=dtype, causal=True)
    assert plan["operand_dtype"] == want


@pytest.mark.parametrize("scheme,window,kernels", [
    ("resident", None, 2), ("stream", None, 2),   # 2: the fused backward
    ("resident", 64, 3), ("stream", 64, 3)],      # 3: a window keeps the pair
    ids=["resident", "stream", "resident-window", "stream-window"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernels_feed_the_mxu_the_planned_operand_dtype(
        monkeypatch, scheme, window, kernels, dtype):
    """Every dot_general inside the kernels of a grad call takes both
    operands in the plan's operand dtype — bf16 in, bf16 on the MXU;
    f32 in, the f32 contraction f32 callers always ran."""
    monkeypatch.setattr(F, "_FORCE_SCHEME", scheme)
    q, k, v = qkv(t=256, dtype=dtype)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None, 128, 64, None,
                               window).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    eqns = _pallas_eqns(jaxpr.jaxpr)
    assert len(eqns) == kernels
    want = F.flash_plan(256, 64, dtype=dtype, causal=True, block_q=128,
                        block_k=64)["operand_dtype"]
    for eqn in eqns:
        assert {str(v.aval.dtype) for e in _eqns(eqn.params["jaxpr"])
                if e.primitive.name == "dot_general"
                for v in e.invars} == {want}


def test_causal_trip_counts_shrink(monkeypatch):
    """The block-skip regression guard: under the resident scheme the
    summed fori trip counts of ALL THREE kernels equal the causal
    lower triangle — roughly half the unskipped grid — and a window
    shrinks them further. flash_plan derives these counts from the
    same span helpers the kernels pass to lax.fori_loop."""
    monkeypatch.setattr(F, "_FORCE_SCHEME", "resident")
    t, d, bq = 2048, 64, 256
    nq = t // bq
    tri = nq * (nq + 1) // 2
    plan = F.flash_plan(t, d, causal=True, block_q=bq, block_k=bq)
    for which in ("fwd", "dq", "dkv"):
        assert plan[which]["scheme"] == "resident"
        assert plan[which]["visited_blocks"] == tri
        assert plan[which]["grid_blocks"] == nq * nq
        assert tri < nq * nq  # the actual shrink

    win = 300
    wplan = F.flash_plan(t, d, causal=True, window=win, block_q=bq,
                         block_k=bq)
    wvis = int(_visible_block_mask(t, bq, bq, win).sum())
    for which in ("fwd", "dq", "dkv"):
        assert wplan[which]["visited_blocks"] == wvis < tri


def test_stream_fallback_plan_keeps_windowed_narrowing(monkeypatch):
    """The over-budget streaming path retains the round-5 narrowing:
    windowed fwd/dq visit span*nq blocks (< the full grid); causal
    without a window still sweeps the full grid there (compute-skip
    only) — which is exactly why the resident scheme is preferred."""
    monkeypatch.setattr(F, "_FORCE_SCHEME", "stream")
    t, d, b = 2048, 64, 256
    nq = t // b
    plan = F.flash_plan(t, d, causal=True, window=256, block_q=b,
                        block_k=b)
    span = F._window_span(256, b, b, nq)
    for which in ("fwd", "dq", "dkv"):
        assert plan[which]["scheme"] == "stream"
        assert plan[which]["visited_blocks"] == span * nq < nq * nq


def test_auto_blocks_shrink_under_vmem_budget():
    """The fused_ce-style selector: auto blocks at a huge head dim
    stay within `_VMEM_BUDGET` by shrinking (the old fixed auto choice
    would blow the Mosaic scoped-vmem limit), while the flagship
    d=64 shape keeps the round-5 measured-fastest 1024 tiles."""
    small = F._tiles(4096, True, None, None, d=64, itemsize=2)
    assert small == (1024, 1024)  # measured-best config preserved
    big = F._tiles(4096, True, None, None, d=512, itemsize=4)
    assert big is not None
    bq, bk = big
    assert bq < 1024 or bk < 1024
    assert max(F._fwd_stream_vmem(bq, bk, 512, 4),
               F._dq_stream_vmem(bq, bk, 512, 4),
               F._dkv_stream_vmem(bq, bk, 512, 4, 4096)) \
        <= F._VMEM_BUDGET
    # explicit blocks are respected as given, never budget-shrunk
    assert F._tiles(4096, True, 1024, 1024, d=512,
                    itemsize=4) == (1024, 1024)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for x in (v if isinstance(v, (list, tuple)) else (v,)):
                if hasattr(x, "jaxpr"):          # ClosedJaxpr
                    yield from _eqns(x.jaxpr)
                elif hasattr(x, "eqns"):         # raw Jaxpr
                    yield from _eqns(x)


def _pallas_eqns(jaxpr):
    return [e for e in _eqns(jaxpr) if e.primitive.name == "pallas_call"]


def _count(jaxpr, name):
    return sum(e.primitive.name == name for e in _eqns(jaxpr))


def test_resident_grad_runs_three_2d_kernels(monkeypatch):
    """Structural: a fwd+bwd trace of a WINDOWED call under the
    resident scheme with the dq + dkv pair (what rectangular tiles
    under a window run) contains exactly three pallas_calls (fwd, dq,
    dkv) — no standalone delta pass — each on a 2-D (B*H, blocks) grid,
    i.e. the block loop with its dynamic trip count lives INSIDE the
    kernel. The dq call emits two outputs (dq + the folded delta row
    set for dkv)."""
    monkeypatch.setattr(F, "_FORCE_SCHEME", "resident")
    _pair_only(monkeypatch)
    q, k, v = qkv(t=512)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None, 128, 128, None,
                               128).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    eqns = _pallas_eqns(jaxpr.jaxpr)
    assert len(eqns) == 3
    for eqn in eqns:
        assert len(eqn.params["grid_mapping"].grid) == 2
        assert len(eqn.outvars) == 2  # (o,lse) / (dq,delta) / (dk,dv)


def test_resident_windowless_grad_is_a_loop_and_one_backward(monkeypatch):
    """Without a window the resident forward (2-D grid, the k-loop
    inside) is followed by ONE backward kernel on the 3-D grid
    (B*H, nk, nq) with dq, dk and dv as outputs (PR 33)."""
    monkeypatch.setattr(F, "_FORCE_SCHEME", "resident")
    q, k, v = qkv(t=512)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None, 128, 128).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    fwd, bwd = _pallas_eqns(jaxpr.jaxpr)
    assert fwd.params["grid_mapping"].grid == (2, 4)
    assert _count(fwd.params["jaxpr"], "while") == 1
    assert bwd.params["grid_mapping"].grid == (2, 4, 4)
    assert len(bwd.outvars) == 3
    assert _count(bwd.params["jaxpr"], "while") == 0


def test_stream_grad_also_folds_delta(monkeypatch):
    """The streaming dq + dkv pair (what a windowed call past the
    budget runs at rectangular tiles) folds delta into the dq kernel's
    kk==0 prologue too: still exactly three pallas_calls, 3-D grids."""
    monkeypatch.setattr(F, "_FORCE_SCHEME", "stream")
    _pair_only(monkeypatch)
    q, k, v = qkv(t=512)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None, 128, 128, None,
                               128).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    eqns = _pallas_eqns(jaxpr.jaxpr)
    assert len(eqns) == 3
    for eqn in eqns:
        assert len(eqn.params["grid_mapping"].grid) == 3


# -- the fused streaming backward (PR 31) -------------------------------------


def _grads(q, k, v, g, **kw):
    _, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, **kw),
                     q, k, v)
    return vjp(g)


def _assert_grads_close(got, want, tol):
    """dq, dk, dv to `tol` of each reference gradient's largest entry."""
    for name, a, r in zip("dq dk dv".split(), got, want):
        scale = float(jnp.max(jnp.abs(r))) or 1.0
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(r), rtol=0,
                                   atol=tol * scale, err_msg=name)


def _pair_only(monkeypatch):
    """The dq + dkv pair wherever the fused backward would engage."""
    monkeypatch.setattr(F, "_bwd_stream_tiles", lambda *a: None)


@pytest.mark.parametrize("scheme", ["stream", "resident"])
@pytest.mark.parametrize("causal", [True, False])
def test_stream_grad_is_one_backward_kernel(monkeypatch, causal, scheme):
    """Forward + ONE backward kernel on grid (B*H, nk, nq) with three
    outputs (dq, dk, dv) and five dot_generals — `flash_plan`'s
    `block_matmuls` — where the pair's two hold seven: whichever of the
    loops or the streaming grid the pair would have run on."""
    monkeypatch.setattr(F, "_FORCE_SCHEME", scheme)
    q, k, v = qkv(t=512)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal, None, 256, 128).sum()

    def dots(eqn):
        return _count(eqn.params["jaxpr"], "dot_general")

    def bwd_kernels():
        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        return _pallas_eqns(jaxpr.jaxpr)[1:]

    plan = F.flash_plan(512, 64, causal=causal, block_q=256, block_k=128)
    assert plan["bwd"]["scheme"] == "stream_fused"
    (bwd,) = bwd_kernels()
    assert bwd.params["grid_mapping"].grid == (2, 4, 2)
    assert len(bwd.outvars) == 3
    assert dots(bwd) == plan["bwd"]["block_matmuls"] == 5
    _pair_only(monkeypatch)
    plan = F.flash_plan(512, 64, causal=causal, block_q=256, block_k=128)
    assert plan["bwd"]["scheme"] == scheme
    assert (sum(dots(e) for e in bwd_kernels())
            == plan["bwd"]["block_matmuls"] == 7)


@pytest.mark.parametrize("scheme,d,blocks,dtype,tol", [
    ("stream", 64, (128, 128), jnp.float32, 2e-4),
    ("stream", 64, (128, 128), jnp.bfloat16, 3e-2),
    ("stream", 64, (256, 128), jnp.float32, 2e-4),
    ("stream", 64, (256, 128), jnp.bfloat16, 3e-2),
    ("stream", 128, (128, 128), jnp.float32, 2e-4),   # lane-filling heads:
    ("stream", 256, (128, 128), jnp.float32, 2e-4),   # 128, the glm cell's 256
    # the `ouro-2.6b` cell's form: the forward on the resident loops at
    # bq = 2 bk over four q-blocks, d 128, the backward fused (PR 33)
    ("resident", 128, (128, 64), jnp.float32, 2e-4),
    ("resident", 128, (128, 64), jnp.bfloat16, 3e-2),
], ids=["square-f32", "square-bf16", "rect-f32", "rect-bf16", "d128",
        "d256", "resident-fwd-f32", "resident-fwd-bf16"])
@pytest.mark.parametrize("causal", [True, False])
def test_fused_backward_matches_plain(monkeypatch, causal, scheme, d, blocks,
                                      dtype, tol):
    """dq, dk, dv of the fused kernel against plain attention's: the
    clamped q/dO index map, the whole-head dq accumulator and the XLA
    delta all have to be right for these to agree."""
    monkeypatch.setattr(F, "_FORCE_SCHEME", scheme)
    bq, bk = blocks
    assert F.flash_plan(512, d, dtype=dtype, causal=causal, block_q=bq,
                        block_k=bk)["fwd"]["scheme"] == scheme
    assert F.flash_plan(512, d, dtype=dtype, causal=causal, block_q=bq,
                        block_k=bk)["bwd"]["scheme"] == "stream_fused"
    with jax.default_matmul_precision("highest"):
        q, k, v = qkv(t=512, d=d, dtype=dtype)
        g = jax.random.normal(jax.random.PRNGKey(9), q.shape, dtype)
        got = _grads(q, k, v, g, causal=causal, block_q=bq, block_k=bk)
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        _, ref_vjp = jax.vjp(
            lambda q, k, v: _plain_attention(q, k, v, causal, d ** -0.5),
            *f32)
        assert {a.dtype for a in got} == {jnp.dtype(dtype)}
        _assert_grads_close(got, ref_vjp(g.astype(jnp.float32)), tol)


@pytest.mark.parametrize("scheme,d,blocks", [
    ("stream", 64, (128, 128)), ("stream", 64, (256, 128)),
    ("resident", 128, (128, 64)),   # against `_dq_res_kernel` + `_dkv_res_kernel`
], ids=["square", "rect", "resident-pair"])
@pytest.mark.parametrize("causal", [True, False])
def test_fused_backward_matches_the_pair(monkeypatch, causal, scheme, d,
                                         blocks):
    """Same work, same numbers: on the same f32 inputs the fused
    kernel's gradients are the dq + dkv pair's to 1e-5 (each q-block
    still sums its k-blocks in ascending order in f32)."""
    monkeypatch.setattr(F, "_FORCE_SCHEME", scheme)
    q, k, v = qkv(t=512, d=d)
    g = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    kw = dict(causal=causal, block_q=blocks[0], block_k=blocks[1])
    assert F.flash_plan(512, d, **kw)["bwd"]["scheme"] == "stream_fused"
    fused = _grads(q, k, v, g, **kw)
    _pair_only(monkeypatch)
    assert F.flash_plan(512, d, **kw)["bwd"]["scheme"] == scheme
    for name, a, b in zip("dq dk dv".split(), fused,
                          _grads(q, k, v, g, **kw)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=1e-5, err_msg=name)


def test_fused_backward_takes_its_own_tiles(monkeypatch):
    """Auto tiles: the forward keeps `_tiles`' pick, the fused backward
    runs `_BWD_STREAM_BLOCK` squares where T divides — and still gives
    plain attention's gradients (T 2048: a 2 x 2 grid of 1024s)."""
    monkeypatch.setattr(F, "_FORCE_SCHEME", "stream")
    plan = F.flash_plan(2048, 64, causal=True)
    assert plan["bwd"]["scheme"] == "stream_fused"
    assert (plan["bwd"]["block_q"], plan["bwd"]["block_k"]) == (1024, 1024)
    assert plan["bwd"]["visited_blocks"] == 3
    with jax.default_matmul_precision("highest"):
        q, k, v = qkv(t=2048, h=1, d=64)
        g = jax.random.normal(jax.random.PRNGKey(9), q.shape)
        _, ref_vjp = jax.vjp(
            lambda q, k, v: _plain_attention(q, k, v, True, 64 ** -0.5),
            q, k, v)
        _assert_grads_close(_grads(q, k, v, g, causal=True), ref_vjp(g),
                            2e-4)


# -- the window-less forward on the streaming grid ----------------------------


def _kv_index_map(t, d, causal, blocks, h=2, h_kv=2):
    """The forward kernel's K block spec index map, out of the traced
    `pallas_call`, as a function of Python ints (i, j, kk) -> block."""
    q = jnp.zeros((1, t, h, d))
    k = jnp.zeros((1, t, h_kv, d))
    jaxpr = jax.make_jaxpr(lambda q, k, v: F._flash_fwd_impl(
        q, k, v, causal, None, *blocks, True, save_lse=True))(q, k, k)
    (eqn,) = _pallas_eqns(jaxpr.jaxpr)
    grid = eqn.params["grid_mapping"].grid
    fn = eqn.params["grid_mapping"].block_mappings[1].index_map_jaxpr

    def block(*ijk):
        return tuple(int(x) for x in jax.core.eval_jaxpr(
            fn.jaxpr, fn.consts, *ijk))
    return grid, block


def _forward_counts_by_brute_force(t, bq, bk, causal):
    """(visited, masked, fetched) of the streaming forward a head, from
    the position mask alone: a step computes where its block holds a
    visible pair, builds the mask unless every pair is visible, and the
    pipeline moves a K/V block wherever the block a step needs (its own,
    or for a step that computes nothing the last one its q-block sees)
    differs from the step before's."""
    nq, nk = t // bq, t // bk
    keep = (np.arange(t)[:, None] >= np.arange(t)[None, :] if causal
            else np.ones((t, t), bool)).reshape(nq, bq, nk, bk)
    any_, all_ = keep.any(axis=(1, 3)), keep.all(axis=(1, 3))
    seq = []
    for iq in range(nq):
        last = max(jk for jk in range(nk) if any_[iq, jk])
        seq += [jk if any_[iq, jk] else last for jk in range(nk)]
    fetched = 1 + sum(a != b for a, b in zip(seq, seq[1:]))
    return int(any_.sum()), int((any_ & ~all_).sum()), fetched, any_


def test_streaming_forward_traces_once_for_every_layer(monkeypatch):
    """As the head kernels: a model's layers call the streaming forward
    at one shape, the second call replays the first's trace of its
    two-branch body, and the kernel lands under the caller's scope with
    no name between it and `pallas_call`."""
    monkeypatch.setattr(F, "_FORCE_SCHEME", "stream")
    q, k, v = qkv(t=768, h=3, d=16)   # a shape no other test traces
    traced = []
    real = F._kernel

    def counting(*a, **kw):
        traced.append(1)
        return real(*a, **kw)

    def model(q, k, v):
        x = q
        for i in range(3):
            with jax.named_scope(f"CausalSelfAttention_{i}"):
                x = flash_attention(x, k, v, causal=True, block_q=256,
                                    block_k=256)
        return x.sum()

    import unittest.mock as mock
    with mock.patch.object(F, "_kernel", counting):
        jaxpr = jax.make_jaxpr(jax.grad(model))(q, k, v)
    assert len(traced) == 1
    eqns = _pallas_eqns(jaxpr.jaxpr)
    assert len(eqns) == 6
    assert {str(e.source_info.name_stack) for e in eqns} == {
        f(f"jvp(CausalSelfAttention_{i})") for i in range(3)
        for f in (str, "transpose({})".format)}


@pytest.mark.parametrize("t,bq,bk,causal", [
    (4096, 1024, 1024, True),    # the d = 64 cell's call
    (4096, 1024, 512, True),     # the loops' old tiles at d = 128
    (8192, 1024, 1024, True),
    (8192, 1024, 512, True),     # the glm cell's old tiles
    (4096, 1024, 1024, False),
    (2048, 512, 128, True),      # bq = 4 bk
])
def test_forward_fetch_and_mask_counts_match_a_brute_force(
        monkeypatch, t, bq, bk, causal):
    """`flash_plan`'s "fwd" counts on the streaming grid against the
    position mask: `visited_blocks` the steps `_diag_ok` passes,
    `masked_blocks` those the diagonal crosses (every other one takes
    the unmasked step), `fetched_blocks` the K/V blocks the pipeline
    moves a head: no step past the diagonal fetches, and a q-block's
    first block repeats the last one of the q-block before it only at
    the top."""
    monkeypatch.setattr(F, "_FORCE_SCHEME", "stream")
    fwd = F.flash_plan(t, 64, dtype=jnp.bfloat16, causal=causal,
                       block_q=bq, block_k=bk)["fwd"]
    visited, masked, fetched, any_ = _forward_counts_by_brute_force(
        t, bq, bk, causal)
    nq, nk = t // bq, t // bk
    assert all(bool(F._diag_ok(iq, jk, causal, bq, bk)) == any_[iq, jk]
               for iq in range(nq) for jk in range(nk))
    assert fwd == {"scheme": "stream", "block_q": bq, "block_k": bk,
                   "visited_blocks": visited, "masked_blocks": masked,
                   "fetched_blocks": fetched, "grid_blocks": nq * nk}
    if causal:   # the diagonal crosses bq / bk blocks a q-block
        assert masked == nq * (bq // bk) and fetched <= visited < nq * nk
    else:
        assert masked == 0 and fetched == visited == nq * nk


@pytest.mark.parametrize("causal,blocks,h,h_kv", [
    (True, (128, 128), 2, 2), (True, (256, 128), 2, 2),
    (True, (128, 128), 8, 1), (False, (128, 128), 2, 2)],
    ids=["square", "rect", "grouped-8", "non-causal"])
def test_forward_kv_index_map_names_the_last_visible_block(
        monkeypatch, causal, blocks, h, h_kv):
    """The K block spec of the traced forward, evaluated at every grid
    step: a step that computes names its own k-block, of its query
    head's K/V head; a causal step past the diagonal names the last
    k-block its q-block sees, so the block index does not move."""
    monkeypatch.setattr(F, "_FORCE_SCHEME", "stream")
    t = 512
    grid, block = _kv_index_map(t, 64, causal, blocks, h, h_kv)
    bq, bk = blocks
    assert grid == (h, t // bq, t // bk)
    for i in range(grid[0]):
        for j in range(grid[1]):
            last = max(jk for jk in range(grid[2])
                       if F._diag_ok(j, jk, causal, bq, bk))
            for kk in range(grid[2]):
                want = kk if F._diag_ok(j, kk, causal, bq, bk) else last
                assert block(i, j, kk) == (i // (h // h_kv), want, 0)
            assert last == (grid[2] - 1 if not causal
                            else ((j + 1) * bq - 1) // bk)


def _plain_lse(q, k, causal, scale):
    """[B*H, T] logsumexp of the scaled (and causally masked) scores."""
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        t = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    return lse.reshape(-1, q.shape[1])


@pytest.mark.parametrize("t,d,h,h_kv,causal,force,want", [
    (3072, 64, 1, 1, True, None, (1024, 1024)),    # unforced, past the head
    (2048, 64, 1, 1, True, "stream", (1024, 1024)),
    (2048, 128, 2, 2, False, None, (1024, 1024)),
    (2048, 64, 8, 1, True, "stream", (1024, 1024)),   # grouped K/V
    (1536, 128, 2, 2, False, None, (512, 512)),    # 1024 does not divide
    (1536, 64, 2, 2, True, "stream", (256, 256)),  # ... the call's tiles
], ids=["t3072", "causal-d64", "non-causal-d128", "grouped-8",
        "t1536-non-causal", "t1536-causal"])
@pytest.mark.parametrize("save_lse", [True, False])
def test_streaming_forward_matches_plain(monkeypatch, t, d, h, h_kv,
                                         causal, force, want, save_lse):
    """The window-less forward on the streaming grid at the tiles
    `flash_plan` names, against plain attention in f32: output and the
    lse the backward consumes, with and without it."""
    monkeypatch.setattr(F, "_FORCE_SCHEME", force)
    fwd = F.flash_plan(t, d, causal=causal, q_per_kv=h // h_kv)["fwd"]
    assert fwd["scheme"] == "stream"
    assert (fwd["block_q"], fwd["block_k"]) == want
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, t, h, d))
    k, v = (jax.random.normal(x, (1, t, h_kv, d)) for x in ks[1:])
    with jax.default_matmul_precision("highest"):
        out, lse = F._flash_fwd_impl(q, k, v, causal, None, None, None,
                                     None, save_lse=save_lse)
        ref = _plain_attention(q, k, v, causal, d ** -0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        if save_lse:
            np.testing.assert_allclose(
                np.asarray(lse), np.asarray(_plain_lse(q, k, causal,
                                                       d ** -0.5)),
                rtol=2e-5, atol=2e-5)
        else:
            assert lse is None


@pytest.mark.parametrize("t,d,h,h_kv,causal,force", [
    (3072, 64, 1, 1, True, None),
    (2048, 128, 2, 2, False, None),
    (2048, 64, 8, 1, True, "stream"),
], ids=["t3072", "non-causal-d128", "grouped-8"])
def test_grads_through_the_streaming_forward_match_plain(
        monkeypatch, t, d, h, h_kv, causal, force):
    """Gradients of `flash_attention` where the forward streams at its
    1024 x 1024 tiles and the fused backward reads its lse, against the
    plain VJP."""
    monkeypatch.setattr(F, "_FORCE_SCHEME", force)
    plan = F.flash_plan(t, d, causal=causal, q_per_kv=h // h_kv)
    assert plan["fwd"]["scheme"] == "stream"
    assert plan["bwd"]["scheme"] == "stream_fused"
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    q, g = (jax.random.normal(x, (1, t, h, d)) for x in ks[:2])
    k, v = (jax.random.normal(x, (1, t, h_kv, d)) for x in ks[2:])
    with jax.default_matmul_precision("highest"):
        _, ref_vjp = jax.vjp(lambda q, k, v: _plain_attention(
            q, k, v, causal, d ** -0.5), q, k, v)
        _assert_grads_close(_grads(q, k, v, g, causal=causal),
                            ref_vjp(g), 2e-4)


# -- the fused backward under a window (PR 37) --------------------------------


@pytest.mark.parametrize("t,h,h_kv,window,block", [
    (512, 2, 2, 100, 64),     # a window that is no multiple of the block
    (512, 2, 2, 511, 128),    # window + 1 == T: every key visible
    (512, 2, 2, 700, 128),    # window + 1 > T
    (512, 2, 2, 20, 128),     # a window under one block
    (512, 8, 1, 150, 64),     # grouped K/V: 8 query heads a K/V head
    (512, 2, 2, 300, 128),    # the last k-blocks' q-spans run past nq
], ids=["window-off-block", "window-is-T", "window-past-T", "under-a-block",
        "grouped-8", "span-past-nq"])
def test_windowed_fused_backward_matches_plain_and_the_pair(
        monkeypatch, t, h, h_kv, window, block):
    """dq, dk, dv of `_bwd_res_kernel` against masked plain attention's
    (keys [q - window, q]; the group summed in f32) and against the dq +
    dkv pair it replaces, on the same f32 inputs: each q-block still
    sums its k-blocks in ascending order in f32, so to 1e-5."""
    kw = dict(causal=True, window=window, block_q=block, block_k=block)
    plan = F.flash_plan(t, 32, q_per_kv=h // h_kv, **kw)
    assert plan["bwd"]["scheme"] == "resident_fused"
    assert plan["bwd"]["block_matmuls"] == 5
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q, g = (jax.random.normal(k, (1, t, h, 32)) for k in ks[:2])
    k, v = (jax.random.normal(x, (1, t, h_kv, 32)) for x in ks[2:])
    with jax.default_matmul_precision("highest"):
        fused = _grads(q, k, v, g, **kw)
        _, ref_vjp = jax.vjp(lambda q, k, v: _plain_attention(
            q, k, v, True, 32 ** -0.5, window=window), q, k, v)
        _assert_grads_close(fused, ref_vjp(g), 2e-4)
        _pair_only(monkeypatch)
        assert F.flash_plan(t, 32, **kw)["bwd"]["scheme"] == "resident"
        for name, a, b in zip("dq dk dv".split(), fused,
                              _grads(q, k, v, g, **kw)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("t,d,window,visited", [
    (8192, 128, 2047, 70),    # the trinity-mini cell's sliding layers
    (16384, 128, 512, 63),    # past the loops' budget: the pair streamed
])
def test_windowed_fused_plan_counts_the_steps_the_kernel_computes(
        t, d, window, visited):
    """At the cell's sliding call: "bwd" is ONE kernel at 512 x 512 whose
    loops run `_q_span`'s q-blocks of every k-block, which are exactly
    the blocks `_diag_ok(..., window)` passes and the brute-forced
    visible ones (70 of 256); `grid_blocks` is the unskipped nq x nk as
    for the resident pair, and its estimate is under the kernel's
    stated limit."""
    bwd = F.flash_plan(t, d, dtype=jnp.bfloat16, causal=True,
                       window=window, q_per_kv=8)["bwd"]
    assert bwd["scheme"] == "resident_fused" and bwd["block_matmuls"] == 5
    bq, bk = bwd["block_q"], bwd["block_k"]
    assert bq == bk == 512
    nq, nk = t // bq, t // bk
    ok = np.array([[bool(F._diag_ok(iq, jk, True, bq, bk, window))
                    for jk in range(nk)] for iq in range(nq)])
    assert (ok == _visible_block_mask(t, bq, bk, window)).all()
    trips = sum(int(hi) - int(lo) for lo, hi in (
        F._q_span(jk, nq, causal=True, window=window, block_q=bq,
                  block_k=bk) for jk in range(nk)))
    assert bwd["visited_blocks"] == bwd["masked_blocks"] == ok.sum() \
        == trips == visited
    assert bwd["grid_blocks"] == nq * nk
    assert bwd["vmem_bytes"] == F._bwd_res_vmem(bq, bk, d, 2, t) \
        <= F._BWD_STREAM_VMEM_LIMIT


@pytest.mark.parametrize("scheme", ["resident", "stream"])
def test_windowed_grad_is_a_loop_and_one_backward(monkeypatch, scheme):
    """Forward + ONE backward kernel on grid (B*H, nk): three outputs
    (dq, dk, dv), one loop over q-blocks inside, five dot_generals —
    whichever of the loops or the streaming grid the forward and the
    pair would run on."""
    monkeypatch.setattr(F, "_FORCE_SCHEME", scheme)
    q, k, v = qkv(t=512)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None, 128, 128, None,
                               200).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    _, bwd = _pallas_eqns(jaxpr.jaxpr)
    assert bwd.params["grid_mapping"].grid == (2, 4)
    assert len(bwd.outvars) == 3
    assert _count(bwd.params["jaxpr"], "while") == 1
    assert _count(bwd.params["jaxpr"], "dot_general") == 5


# -- the streaming kernels under the TPU-faithful interpreter (PR 31) ---------
#
# `interpret=True` runs a kernel's body as plain JAX on blocks sliced
# from the whole arrays, one grid step after another: no buffer, no
# copy, no clock. `pltpu.InterpretParams` models the chip's memories:
# every block moves by a DMA between HBM and a VMEM buffer under a
# semaphore (waited for where the kernel waits, or at once with
# `dma_execution_mode="eager"`), buffers start as NaN, and with
# `detect_races` every read and write is checked against a vector
# clock. A block a skipped step left stale, a copy that lands late, or
# two steps racing on one buffer show here and not there.

_TPU_INTERPRETERS = {
    "nan-races": dict(uninitialized_memory="nan", detect_races=True),
    "eager-dma": dict(uninitialized_memory="nan",
                      dma_execution_mode="eager"),
}


def _under_tpu_interpreter(mode, fn):
    """`fn(interpret)`'s result under the TPU interpreter in `mode`, and
    whether it reported a race."""
    from jax._src.pallas.mosaic.interpret import (
        interpret_pallas_call as tpu_interpret)
    from jax.experimental.pallas import tpu as pltpu

    tpu_interpret.reset_tpu_interpret_mode_state()
    got = jax.block_until_ready(
        fn(pltpu.InterpretParams(**_TPU_INTERPRETERS[mode])))
    races = tpu_interpret.races
    return got, bool(races is not None and races.races_found)


@pytest.mark.parametrize("mode", list(_TPU_INTERPRETERS))
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("backward", ["fused", "pair", "resident-fused"])
def test_stream_kernels_under_the_tpu_interpreter(monkeypatch, backward,
                                                  causal, mode):
    """The forward `stream` kernel with the fused backward, and with
    `_bwd_dq_kernel` + `_bwd_dkv_kernel`, and the `resident` forward
    with the fused backward (what a window-less call inside the budget
    runs since PR 33), where uninitialised VMEM reads as NaN and DMAs
    are modelled: every value finite, bit-equal to the plain
    interpreter's, no race (B*H 2, T 512, d 128, 128 x 128: a 4 x 4
    grid a head, so causal calls skip six steps and the clamped q/dO
    index map repeats a block)."""
    scheme = "resident" if backward == "resident-fused" else "stream"
    monkeypatch.setattr(F, "_FORCE_SCHEME", scheme)
    if backward == "pair":
        _pair_only(monkeypatch)
    plan = F.flash_plan(512, 128, causal=causal, block_q=128, block_k=128)
    assert plan["fwd"]["scheme"] == scheme
    assert plan["bwd"]["scheme"] == ("stream" if backward == "pair"
                                     else "stream_fused")
    q, k, v = qkv(t=512, d=128)
    g = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def run(interpret):
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal, None, 128,
                                            128, interpret), q, k, v)
        return (out, *vjp(g))

    got, raced = _under_tpu_interpreter(mode, run)
    assert not raced
    for name, a, b in zip("out dq dk dv".split(), got, run(True)):
        assert bool(jnp.isfinite(a).all()), name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


@pytest.mark.parametrize("mode", list(_TPU_INTERPRETERS))
@pytest.mark.parametrize("scheme", ["resident", "stream"])
def test_windowed_fused_backward_under_the_tpu_interpreter(monkeypatch,
                                                           scheme, mode):
    """`_bwd_res_kernel` (PR 37) where uninitialised VMEM reads as NaN
    and DMAs are modelled: every value finite, bit-equal to the plain
    interpreter's, no race (B*H 2, T 512, d 128, 128 x 128, window 200:
    two or three q-blocks a k-block, the last ones cut at nq)."""
    monkeypatch.setattr(F, "_FORCE_SCHEME", scheme)
    plan = F.flash_plan(512, 128, causal=True, window=200, block_q=128,
                        block_k=128)
    assert plan["bwd"]["scheme"] == "resident_fused"
    q, k, v = qkv(t=512, d=128)
    g = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def run(interpret):
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, True, None, 128, 128,
                                            interpret, 200), q, k, v)
        return (out, *vjp(g))

    got, raced = _under_tpu_interpreter(mode, run)
    assert not raced
    for name, a, b in zip("out dq dk dv".split(), got, run(True)):
        assert bool(jnp.isfinite(a).all()), name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_tpu_interpreter_sees_an_unzeroed_accumulator():
    """The instrument's control: the fused kernel's shape of fault — a
    VMEM accumulator summed into over grid steps and never zeroed —
    reads NaN (as it does under this JAX's plain interpreter, whose
    scratch starts as NaN too), so `isfinite` above means something."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(x_ref, o_ref, acc):
        acc[...] += x_ref[...]           # no `pl.when(i == 0)` zeroing
        o_ref[...] = acc[...]

    def run(interpret):
        return pl.pallas_call(
            kernel, grid=(2,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
            interpret=interpret)(jnp.ones((16, 128), jnp.float32))

    got, _ = _under_tpu_interpreter("nan-races", run)
    assert bool(jnp.isnan(got).all()) and bool(jnp.isnan(run(True)).all())


@pytest.mark.parametrize("kw,scheme,why", [
    (dict(t=8192, d=256, dtype=jnp.bfloat16, causal=True), "stream_fused",
     "the glm-4.7-flash cell's call"),
    (dict(t=8192, d=256, dtype=jnp.bfloat16), "stream_fused",
     "non-causal: every step computes"),
    (dict(t=16384, d=128, dtype=jnp.bfloat16, causal=True, window=512),
     "resident_fused", "a window past the loops' budget: ONE kernel (PR 37)"),
    (dict(t=32768, d=256, dtype=jnp.bfloat16, causal=True), "stream",
     "a head's f32 dq (32 MB) and its output block pass the limit"),
    (dict(t=65536, d=64, dtype=jnp.bfloat16, causal=True), "stream",
     "d = 64 fills whole 128-lane tiles in VMEM: 32 MB again"),
    (dict(t=1024, d=64, dtype=jnp.bfloat16, causal=True), "head",
     "the GPT cells keep the head kernels"),
    (dict(t=4096, d=128, dtype=jnp.bfloat16, causal=True), "stream_fused",
     "the ouro-2.6b cell's call: the forward on the loops, ONE backward"),
    (dict(t=4096, d=128, dtype=jnp.bfloat16, causal=True, window=512),
     "resident_fused", "a window inside the loops' budget: ONE kernel"),
    (dict(t=8192, d=128, dtype=jnp.bfloat16, causal=True, window=2047),
     "resident_fused", "the trinity-mini cell's sliding layers"),
    (dict(t=4096, d=128, dtype=jnp.bfloat16, causal=True, window=512,
          block_q=512, block_k=256), "resident",
     "rectangular tiles under a window keep the pair"),
    (dict(t=4096, d=64, dtype=jnp.bfloat16, causal=True, window=255),
     "resident", "a window under 512: 256-row tiles keep the loops"),
    (dict(t=2048, d=128, dtype=jnp.bfloat16), "stream_fused",
     "non-causal inside the budget: what Ulysses heads send"),
    (dict(t=4096, d=64, dtype=jnp.bfloat16, causal=True), "stream_fused",
     "d = 64 at T 4096: its pair was past the budget, fused before PR 33"),
    (dict(t=2048, d=256, dtype=jnp.bfloat16, causal=True), "stream_fused",
     "dq past the budget and dkv inside it: no mixed pair any more"),
    (dict(t=1024, d=64, dtype=jnp.bfloat16), "stream_fused",
     "one block: non-causal T <= 1024"),
    (dict(t=1000, d=64, dtype=jnp.bfloat16, causal=True), "stream_fused",
     "one block of no power of two"),
    (dict(t=1152, d=64, dtype=jnp.bfloat16, causal=True), "resident",
     "81 blocks of 128 x 128 a head: the loops' trips cost less (measured)"),
    (dict(t=1280, d=64, dtype=jnp.bfloat16), "resident",
     "25 blocks of 256 x 256: not measured, keeps the pair"),
])
def test_plan_says_which_backward_a_shape_takes(kw, scheme, why):
    """The choice is a function of (t, d, dtype, causal, window)
    alone, and `flash_plan` shows it."""
    bwd = F.flash_plan(kw.pop("t"), kw.pop("d"), **kw)["bwd"]
    assert bwd["scheme"] == scheme, why
    assert bwd["block_matmuls"] == (5 if scheme in F._ONE_KERNEL_BWD
                                    else 7)
    if scheme in ("stream_fused", "resident_fused"):
        assert bwd["vmem_bytes"] <= F._BWD_STREAM_VMEM_LIMIT
    else:
        assert bwd["vmem_bytes"] <= F._VMEM_BUDGET


_HEAD_1024 = {"scheme": "head", "visited_blocks": 10, "masked_blocks": 4,
              "grid_blocks": 16}
_STREAM_8192 = {"scheme": "stream", "visited_blocks": 128,
                "masked_blocks": 128, "grid_blocks": 128}


_RESIDENT_4096 = {"scheme": "resident", "visited_blocks": 20,
                  "masked_blocks": 20, "grid_blocks": 32}
_STREAM_4096 = {"scheme": "stream", "visited_blocks": 16,
                "masked_blocks": 16, "grid_blocks": 16}
# the window-less forward at its own 1024 x 1024: the steps under the
# diagonal unmasked, nothing fetched past it
_FWD_1024_T4096 = {"scheme": "stream", "block_q": 1024, "block_k": 1024,
                   "visited_blocks": 10, "masked_blocks": 4,
                   "fetched_blocks": 9, "grid_blocks": 16}
_FWD_1024_T8192 = {"scheme": "stream", "block_q": 1024, "block_k": 1024,
                   "visited_blocks": 36, "masked_blocks": 8,
                   "fetched_blocks": 35, "grid_blocks": 64}
_TRINITY_FULL = {
    "block_q": 1024, "block_k": 512, "nq": 8, "nk": 16,
    "operand_dtype": "bfloat16", "fwd": _FWD_1024_T8192,
    "dq": _STREAM_8192, "dkv": _STREAM_8192,
    "bwd": {"scheme": "stream_fused", "block_q": 1024, "block_k": 1024,
            "visited_blocks": 36, "masked_blocks": 36, "grid_blocks": 64,
            "block_matmuls": 5, "vmem_bytes": 29491200}}


@pytest.mark.parametrize("t,d,q_per_kv,want", [
    (1024, 64, 1, {   # both GPT cells' call
        "block_q": 256, "block_k": 256, "nq": 4, "nk": 4,
        "operand_dtype": "bfloat16", "dq": _HEAD_1024, "dkv": _HEAD_1024,
        "fwd": {**_HEAD_1024, "block_q": 256, "block_k": 256,
                "fetched_blocks": 4},
        "bwd": {"scheme": "head", "block_q": 256, "block_k": 256,
                "visited_blocks": 10, "masked_blocks": 4, "grid_blocks": 16,
                "block_matmuls": 5, "vmem_bytes": 5517312}}),
    (8192, 256, 1, {   # the glm-4.7-flash cell's call
        "block_q": 1024, "block_k": 512, "nq": 8, "nk": 16,
        "operand_dtype": "bfloat16", "fwd": _FWD_1024_T8192,
        "dq": _STREAM_8192, "dkv": _STREAM_8192,
        "bwd": {"scheme": "stream_fused", "block_q": 1024, "block_k": 1024,
                "visited_blocks": 36, "masked_blocks": 36, "grid_blocks": 64,
                "block_matmuls": 5, "vmem_bytes": 42074112}}),
    (4096, 128, 1, {   # the ouro-2.6b cell's call
        "block_q": 1024, "block_k": 512, "nq": 4, "nk": 8,
        "operand_dtype": "bfloat16", "fwd": _FWD_1024_T4096,
        "dq": _RESIDENT_4096, "dkv": _RESIDENT_4096,
        "bwd": {"scheme": "stream_fused", "block_q": 1024, "block_k": 1024,
                "visited_blocks": 10, "masked_blocks": 10, "grid_blocks": 16,
                "block_matmuls": 5, "vmem_bytes": 25231360}}),
    (4096, 64, 1, {   # the gpt2-small.train-b2-t4096 cell's call
        "block_q": 1024, "block_k": 1024, "nq": 4, "nk": 4,
        "operand_dtype": "bfloat16", "fwd": _FWD_1024_T4096,
        "dq": _STREAM_4096, "dkv": _STREAM_4096,
        "bwd": {"scheme": "stream_fused", "block_q": 1024, "block_k": 1024,
                "visited_blocks": 10, "masked_blocks": 10, "grid_blocks": 16,
                "block_matmuls": 5, "vmem_bytes": 25231360}}),
    (8192, 128, 1, _TRINITY_FULL),   # ... ungrouped (PR 34's parent)
    (8192, 128, 8, {   # trinity-mini's two full layers
        **_TRINITY_FULL,
        "kv_group": {"q_per_kv": 8, "kv_read": "index_map",
                     "dkv_sum": "xla_f32", "dkv_partial_bytes": 33554432}}),
], ids=["gpt-cells", "glm-cell", "ouro-cell", "gpt2-small-t4096-cell",
        "full-layers-ungrouped", "trinity-full-layers"])
def test_plans_of_the_cells_that_must_not_move(t, d, q_per_kv, want):
    """`flash_plan` at the window-less shapes of the cells, key for key
    against a literal: grouped heads, the windowed calls' tiles and the
    fused backward's engage rule moved none of the backward's keys, and
    "fwd" names the forward's own scheme, tiles and counts (the head
    kernels' as before, with their tiles and one fetch of the head;
    every other call on the streaming grid at 1024 x 1024); an
    ungrouped plan carries no `kv_group` key."""
    assert F.flash_plan(t, d, dtype=jnp.bfloat16, causal=True,
                        q_per_kv=q_per_kv) == want


_SLIDING_8192 = {"scheme": "resident", "visited_blocks": 70,
                 "masked_blocks": 70, "grid_blocks": 256}


@pytest.mark.parametrize("t,d,window,q_per_kv,want", [
    (8192, 128, 2047, 8, {   # trinity-mini's sliding layers
        "block_q": 512, "block_k": 512, "nq": 16, "nk": 16,
        "operand_dtype": "bfloat16", "fwd": _SLIDING_8192,
        "dq": _SLIDING_8192, "dkv": _SLIDING_8192,
        "bwd": {"scheme": "resident_fused", "block_q": 512, "block_k": 512,
                "visited_blocks": 70, "masked_blocks": 70,
                "grid_blocks": 256, "block_matmuls": 5,
                "vmem_bytes": 22675456},
        "kv_group": {"q_per_kv": 8, "kv_read": "index_map",
                     "dkv_sum": "xla_f32", "dkv_partial_bytes": 33554432}}),
    (4096, 64, 255, 1, {   # a window under 512: 128-row tiles, the pair
        "block_q": 128, "block_k": 128, "nq": 32, "nk": 32,
        "operand_dtype": "bfloat16",
        **{which: {"scheme": "resident", "visited_blocks": 93,
                   "masked_blocks": 93, "grid_blocks": 1024}
           for which in ("fwd", "dq", "dkv")},
        "bwd": {"scheme": "resident", "block_q": 128, "block_k": 128,
                "visited_blocks": 93, "masked_blocks": 93,
                "grid_blocks": 1024, "block_matmuls": 7,
                "vmem_bytes": 2555904}}),
], ids=["trinity-sliding-layers", "window-255-d64"])
def test_windowed_plans_keep_the_resident_forward(t, d, window, q_per_kv,
                                                  want):
    """A windowed call's forward stays on the loops at the call's
    tiles: its whole plan, key for key, as the tree had it before the
    window-less forward moved to the streaming grid."""
    assert F.flash_plan(t, d, dtype=jnp.bfloat16, causal=True,
                        window=window, q_per_kv=q_per_kv) == want


@pytest.mark.parametrize("blocks,scheme,matmuls", [
    ((256, 256), "resident_fused", 5), ((256, 128), "stream", 7)],
    ids=["square", "rect"])
def test_windowed_call_keeps_the_pair_at_rect_tiles(monkeypatch, blocks,
                                                   scheme, matmuls):
    """Under a window the fused backward needs square tiles (PR 37):
    rectangular ones keep the pair of the call's scheme."""
    monkeypatch.setattr(F, "_FORCE_SCHEME", "stream")
    plan = F.flash_plan(2048, 64, causal=True, window=256,
                        block_q=blocks[0], block_k=blocks[1])
    assert plan["bwd"]["scheme"] == scheme
    assert plan["bwd"]["block_matmuls"] == matmuls


@pytest.mark.parametrize("t,d,blocks,visited,grid", [
    (8192, 256, None, 36, 64),   # the fused kernel's own 1024 x 1024
    (8192, 256, (1024, 512), 72, 128),   # at the forward's tiles
    (8192, 256, (512, 512), 136, 256),
    (4096, 128, None, 10, 16),   # the ouro-2.6b cell's call
    (4096, 128, (1024, 512), 20, 32),
])
def test_bwd_plan_counts_the_steps_the_kernel_computes(t, d, blocks,
                                                       visited, grid):
    """At the glm and `ouro-2.6b` cells' shapes: `visited_blocks` of
    "bwd" is the number of grid steps whose `pl.when(_diag_ok(...))`
    holds, which is the number of blocks with a visible pair; every one
    of them builds the mask. The clamped q/dO index map names, for
    every computing step, the step's own q-block."""
    bq, bk = blocks or (None, None)
    bwd = F.flash_plan(t, d, dtype=jnp.bfloat16, causal=True,
                       block_q=bq, block_k=bk)["bwd"]
    assert bwd["scheme"] == "stream_fused" and bwd["block_matmuls"] == 5
    bq, bk = bwd["block_q"], bwd["block_k"]
    nq, nk = t // bq, t // bk
    ok = np.array([[bool(F._diag_ok(iq, jk, True, bq, bk))
                    for jk in range(nk)] for iq in range(nq)])
    assert (ok == _visible_block_mask(t, bq, bk, None)).all()
    assert bwd["visited_blocks"] == bwd["masked_blocks"] == ok.sum() \
        == visited
    assert bwd["grid_blocks"] == nq * nk == grid
    for jk in range(nk):
        lo, _ = F._q_span(jk, nq, causal=True, window=None, block_q=bq,
                          block_k=bk)
        fetched = [max(iq, lo) for iq in range(nq)]
        assert all(fetched[iq] == iq for iq in range(nq) if ok[iq, jk])
        assert len(set(fetched)) == ok[:, jk].sum()


@pytest.mark.parametrize("scheme", ["resident", "stream"])
@pytest.mark.parametrize("causal,window,blocks", [
    (False, None, (128, 128)),
    (True, None, (256, 128)),   # rect blocks across the diagonal
    (True, 300, (256, 64)),     # m=4 window, non-block-multiple size
    (True, 64, (128, 128)),     # whole-block skipping at the edge
])
def test_both_schemes_match_plain_fwd_and_grads(monkeypatch, scheme,
                                                causal, window,
                                                blocks):
    """Numerics pin for the new kernels across causal x window x block
    shapes, fwd AND grads, for BOTH execution schemes."""
    monkeypatch.setattr(F, "_FORCE_SCHEME", scheme)
    with jax.default_matmul_precision("highest"):
        q, k, v = qkv(t=512, d=64)
        g = jax.random.normal(jax.random.PRNGKey(9), q.shape)
        bq, bk = blocks

        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, window=window,
                block_q=bq, block_k=bk), q, k, v)
        ref, ref_vjp = jax.vjp(
            lambda q, k, v: _plain_attention(
                q, k, v, causal, 64 ** -0.5, window=window), q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        for name, a, r in zip("dq dk dv".split(), vjp(g), ref_vjp(g)):
            scale = float(jnp.max(jnp.abs(r))) or 1.0
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=0, atol=2e-4 * scale,
                                       err_msg=f"{scheme} {name}")


def test_flops_accounting_counts_visible_pairs_only():
    full = F.flash_attention_flops(1, 1024, 1, 64, causal=False)
    tri = F.flash_attention_flops(1, 1024, 1, 64, causal=True)
    win = F.flash_attention_flops(1, 1024, 1, 64, causal=True,
                                  window=128)
    assert full == 4 * 1024 * 1024 * 64
    assert tri == 4 * (1024 * 1025 // 2) * 64
    assert win < tri < full
    # exact windowed pair count, brute-forced
    pairs = sum(min(qp, 128) + 1 for qp in range(1024))
    assert win == 4 * pairs * 64
    assert F.flash_attention_flops(
        1, 1024, 1, 64, causal=True, backward=True) == 3 * tri


def test_flash_plan_reports_plain_fallback():
    # > 1024 with no power-of-two divisor >= 128: no tiling exists
    assert F.flash_plan(3000, 64)["scheme"] == "plain"


def test_flash_efficiency_smoke():
    """The benchmark artifact the acceptance criterion pins: runs on
    the CPU interpreter at smoke shapes and reports timings + plan
    (efficiency is None off known TPU kinds)."""
    from kungfu_tpu.benchmarks.flash_eff import measure_flash_efficiency

    meta = measure_flash_efficiency(batch=1, seq=128, heads=2,
                                    head_dim=32, iters=1, warmup=1)
    assert meta["fwd_ms"] > 0 and meta["fwdbwd_ms"] > 0
    # interpreter-mode timings are arbitrarily slow under CI load, so
    # the (3-decimal-rounded) TFLOP/s may legitimately round to 0.0
    assert meta["fwdbwd_tflops"] >= 0
    assert meta["efficiency_vs_bf16_peak"] is None  # CPU smoke
    assert meta["plan"]["fwd"]["scheme"] in ("resident", "stream")
    assert meta["plan"]["bwd"]["block_matmuls"] in (5, 7)
