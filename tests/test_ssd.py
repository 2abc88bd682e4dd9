"""`ops/ssd.py`'s chunked scan against the recurrence it computes, one
position at a time, on the CPU in f32 (the forward kernel in Pallas's
interpreter): outputs, the final state, the chunks' entry states and
the gradients of all six inputs, at chunk sizes 8, 16 and 32, lengths
that are no multiple of the chunk, and decays near 0 and near 1. Then
what the module promises of its structure: no loop over positions, f32
decays and states under bf16 inputs, heads a program and passes of
heads, one trace for many layers, and the plan at the published
shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kungfu_tpu.ops import ssd as S
from kungfu_tpu.ops.ssd import ssd, ssd_plan
from kungfu_tpu.trace.scopes import SSD

from test_glm_moe import rel_err, sub_jaxprs


def recurrence(x, dt, A, B, C, D):
    """y [B, T, H, P] and the final state [B, H, P, N], position by
    position: S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t
    + D x_t."""
    b, _, h, p = x.shape

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = (jnp.exp(dt_t * A)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None])
        return state, (jnp.einsum("bhpn,bn->bhp", state, c_t)
                       + D[:, None] * x_t)

    final, y = jax.lax.scan(
        step, jnp.zeros((b, h, p, B.shape[-1])),
        tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1), final


# dt = softplus(N(0, 1) + shift) times A = -exp(N(0, 1) + scale): a
# decay exp(dt A) a step near 1 ("slow": the state carries across many
# chunks), in between, or near 0 ("fast": a few 1e-30 and below, so
# the in-chunk sums run to -1e3 and must not overflow anything)
DECAYS = {"slow": (-4.0, -3.0), "mid": (0.0, 0.0), "fast": (3.0, 4.0)}


def inputs(t, decay, b=2, h=4, p=8, n=16, seed=0, dtype=jnp.float32):
    shift, scale = DECAYS[decay]
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, t, h)) + shift)
    A = -jnp.exp(jax.random.normal(k[2], (h,)) + scale)
    B = jax.random.normal(k[3], (b, t, n))
    C = jax.random.normal(k[4], (b, t, n))
    D = jax.random.normal(k[5], (h,))
    return (x.astype(dtype), dt, A, B.astype(dtype), C.astype(dtype), D)


CASES = [(8, 48), (8, 50), (16, 64), (16, 41), (32, 96), (32, 20)]


def _ids(case):
    chunk, t = case
    return f"q{chunk}-t{t}"


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("case", CASES, ids=_ids)
def test_outputs_and_final_state_match_the_recurrence(case, decay):
    chunk, t = case
    args = inputs(t, decay)
    y, final = ssd(*args, chunk=chunk)
    want_y, want_final = recurrence(*args)
    assert y.shape == want_y.shape and final.shape == want_final.shape
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(final, want_final, rtol=2e-5, atol=2e-5)
    step = jnp.exp(args[1] * args[2])       # each position's decay
    if decay == "slow":   # the state carries across the chunks
        assert float(step.min()) > 0.9
    if decay == "fast":
        assert float(step.max()) < 0.05


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("case", CASES, ids=_ids)
def test_gradients_of_all_six_inputs_match_the_recurrence(case, decay):
    """Through y AND the final state, each under a cotangent of its
    own, so that the backward's pass between chunks starts from a
    state's cotangent too."""
    chunk, t = case
    args = inputs(t, decay)
    k = jax.random.split(jax.random.PRNGKey(7), 2)
    wy = jax.random.normal(k[0], args[0].shape)
    wf = jax.random.normal(k[1], (2, 4, 8, 16))

    def objective(fn):
        def of(*a):
            y, final = fn(*a)
            return jnp.sum(y * wy) + jnp.sum(final * wf)
        return jax.grad(of, argnums=range(6))

    got = objective(lambda *a: ssd(*a, chunk=chunk))(*args)
    want = objective(recurrence)(*args)
    # under the strongest decays dA is ~1e-14, the rounding left of
    # summing O(1) terms: each gradient is held to 3e-5 of its own size
    # or 1e-7 of the largest gradient's, whichever is more
    scale = max(float(jnp.abs(w).max()) for w in want)
    for name, g, w in zip("x dt A B C D".split(), got, want):
        assert bool(jnp.isfinite(g).all()), name
        err = float(jnp.abs(g - w).max())
        assert err <= 3e-5 * float(jnp.abs(w).max()) + 1e-7 * scale, (
            name, err, rel_err(g, w))


def test_passes_of_heads_change_nothing(monkeypatch):
    """Four heads in one pass, or in passes of two and of one (a smaller
    `_PASS_BYTES`): the same outputs and gradients."""
    args = inputs(40, "mid")
    grad = jax.grad(lambda *a: jnp.sum(ssd(*a, chunk=8)[0] ** 2),
                    argnums=range(6))
    whole = ssd(*args, chunk=8)[0], grad(*args)
    one = 2 * 5 * 8 * 8 * 4     # a head's [B, chunks, Q, Q] f32
    for per_pass in (2, 1):
        monkeypatch.setattr(S, "_PASS_BYTES", per_pass * one)
        assert S._heads_per_pass(2, 5, 4, 8) == per_pass
        y, _ = ssd(*args, chunk=8)
        np.testing.assert_allclose(y, whole[0], rtol=1e-6, atol=1e-6)
        for g, w in zip(grad(*args), whole[1]):
            assert rel_err(g, w) < 1e-6


@pytest.mark.parametrize("hg", [1, 2])
def test_heads_a_program_change_nothing(monkeypatch, hg):
    """The forward kernel over programs of one or two heads, or of all
    four: the same outputs, final state and entry states."""
    args = inputs(40, "slow")
    whole = S._forward_at(*args, 8)
    monkeypatch.setattr(S, "_heads_per_program", lambda *a: hg)
    for got, want in zip(S._forward_at(*args, 8), whole):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_entry_states_match_the_recurrence():
    """The state each chunk enters with, which the backward takes from
    the forward kernel: the recurrence's state after the chunks before
    it, under decays slow enough that it carries across all of them."""
    chunk, t = 16, 64
    args = inputs(t, "slow")
    _, final, s_in = S._forward_at(*args, chunk)
    assert s_in.shape == (2, t // chunk, 4, 8, 16)
    assert s_in.dtype == jnp.float32
    np.testing.assert_array_equal(s_in[:, 0], 0.0)
    for c in range(1, t // chunk):
        _, want = recurrence(*(a[:, :c * chunk] if a.ndim > 1 else a
                               for a in args))
        assert float(jnp.abs(want).max()) > 0.1     # carried, not ~0
        np.testing.assert_allclose(s_in[:, c], want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(final, recurrence(*args)[1], rtol=2e-5,
                               atol=2e-5)


def test_extreme_decays_stay_finite():
    """Steps of dt A down to -1e4: every exp the scan forms is of a
    number <= 0, so nothing overflows and no inf meets a zero in the
    backward."""
    x, dt, _, B, C, D = inputs(64, "mid")
    A = -jnp.full((4,), 1e4)
    y, final = ssd(x, dt, A, B, C, D, chunk=16)
    want_y, want_final = recurrence(x, dt, A, B, C, D)
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    grads = jax.grad(lambda *a: jnp.sum(ssd(*a, chunk=16)[0]),
                     argnums=range(6))(x, dt, A, B, C, D)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


def test_no_loop_runs_over_positions():
    """T 256 in chunks of 16: the forward kernel's grid runs over the 16
    chunks innermost, the backward's one loop over them (the pass
    between chunks) or over passes of heads; none has a trip a
    position."""
    args = inputs(256, "mid", h=4)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ssd(*a, chunk=16)[0]), argnums=range(6)))(*args)
    eqns = list(sub_jaxprs(jaxpr.jaxpr))
    lengths = [e.params["length"] for e in eqns if e.primitive.name == "scan"]
    assert 16 in lengths
    assert max(lengths) == 16
    [kernel] = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert kernel.params["grid_mapping"].grid == (2, 1, 16)


def test_bf16_inputs_keep_f32_decays_states_and_accumulation():
    """Under bf16 x, B and C: the carried state of the forward kernel
    (its scratch) and of the backward's pass between chunks are f32,
    every exp is f32, and every matmul, the kernel's included, takes
    bf16 operands with f32 accumulation."""
    args = inputs(64, "mid", dtype=jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ssd(*a, chunk=16)[0].astype(jnp.float32)),
        argnums=range(6)))(*args)
    eqns = list(sub_jaxprs(jaxpr.jaxpr))
    scans = [e for e in eqns if e.primitive.name == "scan"
             and e.params["length"] == 4]
    assert len(scans) == 1      # the backward's pass between chunks
    for e in scans:
        carry = e.params["num_carry"]
        dtypes = {v.aval.dtype for v in e.outvars[:carry]}
        assert dtypes == {jnp.dtype(jnp.float32)}, dtypes
    [kernel] = [e for e in eqns if e.primitive.name == "pallas_call"]
    scratch = kernel.params["jaxpr"].invars[-1].aval
    assert (scratch.shape, scratch.dtype) == ((4 * 8, 16), jnp.float32)
    in_kernel = list(sub_jaxprs(kernel.params["jaxpr"]))
    assert [e for e in in_kernel if e.primitive.name == "exp"]
    assert {e.outvars[0].aval.dtype for e in eqns
            if e.primitive.name == "exp"} == {jnp.dtype(jnp.float32)}
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert [e for e in in_kernel if e.primitive.name == "dot_general"]
    for e in dots:
        assert e.outvars[0].aval.dtype == jnp.float32
        assert {v.aval.dtype for v in e.invars} == {jnp.dtype(jnp.bfloat16)}
    y = ssd(*args, chunk=16)[0].astype(jnp.float32)
    want, _ = recurrence(*(a.astype(jnp.float32) for a in args))
    assert rel_err(y, want) < 1e-2


def test_layers_replay_one_trace_of_each_direction(monkeypatch):
    """Three calls of one shape in one program (a length no other case
    here takes, so that no earlier trace is cached): the forward's body
    and the backward's are each traced ONCE, and the program's ops sit
    under the `kf.ssd` scope both ways: the forward kernel's
    `pallas_call` under the forward's, the backward's matmuls under
    its own."""
    traced = []
    chunked = S._chunked
    monkeypatch.setattr(S, "_chunked", lambda *a: traced.append(1) or
                        chunked(*a))
    args = inputs(112, "mid")

    def three(x, *rest):
        for _ in range(3):
            x = x + ssd(x, *rest, chunk=16)[0]
        return jnp.sum(x)

    jaxpr = jax.make_jaxpr(jax.grad(three))(*args)
    assert len(traced) == 2

    def stacks(primitive):
        return {str(e.source_info.name_stack)
                for e in sub_jaxprs(jaxpr.jaxpr)
                if e.primitive.name == primitive}

    kernels = stacks("pallas_call")
    assert len(kernels) == 1
    assert [x for x in kernels if x.startswith(f"jvp({SSD})/{SSD}")], kernels
    way = f"transpose(jvp({SSD}))/{SSD}"
    assert [x for x in stacks("dot_general") if x.startswith(way)], way


def test_plan_at_the_published_shapes():
    """granite-4.0-h-micro: T 8192 in 32 chunks of 256, 64 heads of 64,
    state 128, bf16 x: the forward kernel over 32 heads a program
    ([256, 2048] blocks of x and y, a [2048, 128] f32 state), the
    backward 8 heads a pass (64 MiB of f32 decays), the state a
    sequence carries 2 MiB a layer."""
    plan = ssd_plan(1, 8192, 64, 64, 128, 256, dtype=jnp.bfloat16)
    fwd = plan.pop("fwd")
    assert fwd == {
        "form": "pallas_fused", "heads_per_program": 32, "grid": (1, 2, 32),
        "block_rows": (256, 2048), "block_state": (2048, 128),
        "vmem_bytes": S._fwd_vmem_bytes(32, 64, 128, 256, 2),
        "vmem_limit_bytes": S._FWD_VMEM_LIMIT}
    assert fwd["vmem_bytes"] <= S._FWD_VMEM_BUDGET < S._FWD_VMEM_LIMIT
    assert plan == {
        "form": "xla_chunked", "chunk": 256, "chunks": 32,
        "padded_positions": 0, "heads_per_pass": 8, "passes": 8,
        "state_bytes": 64 * 64 * 128 * 4, "pass_bytes": 64 * 2 ** 20,
        "largest_intermediate_bytes": 8192 * 64 * 64 * 4}
    assert ssd_plan(1, 8000, 64, 64, 128, 256, dtype=jnp.bfloat16)[
        "padded_positions"] == 192
