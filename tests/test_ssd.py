"""`ops/ssd.py`'s chunked scan against the recurrence it computes, one
position at a time, on the CPU in f32 (both kernels in Pallas's
interpreter): outputs, the final state, the
chunks' entry states and the gradients of all six inputs, at chunk
sizes 8, 16 and 32, lengths that are no multiple of the chunk, and
decays near 0 and near 1; both kernels again under the interpreter
that models the chip's memories. Then what the module promises of its
structure: no loop over positions, f32 decays, states and cotangents
under bf16 inputs, heads a program of either kernel, one trace for many
layers, and the plan at the published shapes."""

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


def test_gradients_through_the_final_state_alone():
    """Only the final state under a cotangent: the backward kernel's
    carried cotangent starts from it at the last chunk and reaches every
    input through the pass between chunks alone."""
    chunk, t = 16, 41
    args = inputs(t, "slow")
    wf = jax.random.normal(jax.random.PRNGKey(9), (2, 4, 8, 16))

    def objective(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a)[1] * wf),
                        argnums=range(6))

    got = objective(lambda *a: ssd(*a, chunk=chunk))(*args)
    want = objective(recurrence)(*args)
    scale = max(float(jnp.abs(w).max()) for w in want)
    for name, g, w in zip("x dt A B C D".split(), got, want):
        err = float(jnp.abs(g - w).max())
        assert err <= 3e-5 * float(jnp.abs(w).max()) + 1e-7 * scale, (
            name, err, rel_err(g, w))
    # the first chunk's x reaches the final state only across the others
    assert float(jnp.abs(want[0][:, :chunk]).max()) > 0.1


@pytest.mark.parametrize("hg", [1, 2])
def test_backward_heads_a_program_change_nothing(monkeypatch, hg):
    """The backward kernel over programs of one or two heads, or of all
    four: the same gradients of all six inputs, through y and the final
    state."""
    args = inputs(40, "slow")
    wf = jax.random.normal(jax.random.PRNGKey(9), (2, 4, 8, 16))

    def loss(*a):
        y, final = ssd(*a, chunk=8)
        return jnp.sum(y ** 2) + jnp.sum(final * wf)

    grad = jax.grad(loss, argnums=range(6))
    whole = grad(*args)
    monkeypatch.setattr(S, "_bwd_heads_per_program", lambda *a: hg)
    eqns = sub_jaxprs(jax.make_jaxpr(grad)(*args).jaxpr)
    grids = [e.params["grid_mapping"].grid for e in eqns
             if e.primitive.name == "pallas_call"]
    assert grids == [(2, 1, 5), (2, 4 // hg, 5)]
    for g, w in zip(grad(*args), whole):
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


_TPU_INTERPRETERS = {
    "nan-races": dict(uninitialized_memory="nan", detect_races=True),
    "eager-dma": dict(uninitialized_memory="nan",
                      dma_execution_mode="eager"),
}


@pytest.mark.parametrize("mode", list(_TPU_INTERPRETERS))
def test_kernels_under_the_tpu_interpreter(monkeypatch, mode):
    """Both kernels under the interpreter that models the chip's
    memories (each block moved by a DMA into a VMEM buffer that starts
    as NaN; reads and writes checked for races, or the DMAs done at
    once): the same outputs and gradients of all six inputs as the
    plain interpreter, through y and the final state, and no race.
    What the backward carries across its reversed chunks (the
    cotangent's scratch, dD's block revisited) is read only after it
    is written."""
    from jax._src.pallas.mosaic.interpret import (
        interpret_pallas_call as tpu_interpret)
    from jax.experimental.pallas import tpu as pltpu

    args = inputs(40, "slow")
    wf = jax.random.normal(jax.random.PRNGKey(9), (2, 4, 8, 16))

    def loss(*a):
        y, final = ssd(*a, chunk=8)
        return jnp.sum(y ** 2) + jnp.sum(final * wf)

    grad = jax.value_and_grad(loss, argnums=range(6))
    want = grad(*args)
    tpu_interpret.reset_tpu_interpret_mode_state()
    monkeypatch.setattr(S, "_interpret", lambda: pltpu.InterpretParams(
        **_TPU_INTERPRETERS[mode]))
    got = jax.block_until_ready(grad(*args))
    races = tpu_interpret.races
    assert not (races is not None and races.races_found)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for g, w in zip(got[1], want[1]):
        assert bool(jnp.isfinite(g).all())
        assert rel_err(g, w) < 1e-6


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


def _kernels(eqns):
    """The forward kernel's `pallas_call` and the backward's, told apart
    by their outputs (y, the entry states and the final state; dx and
    what the other five inputs' cotangents are summed from)."""
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert sorted(len(e.outvars) for e in kernels) == [3, 6]
    return sorted(kernels, key=lambda e: len(e.outvars))


def _chunk_of_step(kernel, step):
    """The chunk (the block index along T) that grid step (0, 0, step)
    reads of the kernel's first input, x."""
    index_map = kernel.params["grid_mapping"].block_mappings[0].index_map_jaxpr
    chunk = jax.core.eval_jaxpr(index_map.jaxpr, index_map.consts, 0, 0,
                                step)[1]
    return int(chunk)


def test_no_loop_runs_over_positions():
    """T 256 in chunks of 16: the forward kernel's grid runs over the 16
    chunks innermost and in order, the backward kernel's over them last
    first; no loop runs beside them (each kernel carries what passes
    between chunks), so nothing has a trip a position."""
    args = inputs(256, "mid", h=4)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ssd(*a, chunk=16)[0]), argnums=range(6)))(*args)
    eqns = list(sub_jaxprs(jaxpr.jaxpr))
    assert not [e for e in eqns if e.primitive.name in ("scan", "while")]
    fwd, bwd = _kernels(eqns)
    for kernel in (fwd, bwd):
        assert kernel.params["grid_mapping"].grid == (2, 1, 16)
    assert [_chunk_of_step(fwd, s) for s in (0, 15)] == [0, 15]
    assert [_chunk_of_step(bwd, s) for s in (0, 15)] == [15, 0]


def test_bf16_inputs_keep_f32_decays_states_and_accumulation():
    """Under bf16 x, B and C: the state the forward kernel carries and
    the cotangent the backward kernel carries (each one's scratch) are
    f32, every exp is f32, and every matmul, all inside the kernels,
    takes bf16 operands with f32 accumulation."""
    args = inputs(64, "mid", dtype=jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ssd(*a, chunk=16)[0].astype(jnp.float32)),
        argnums=range(6)))(*args)
    eqns = list(sub_jaxprs(jaxpr.jaxpr))
    dots = []
    for kernel in _kernels(eqns):
        scratch = kernel.params["jaxpr"].invars[-1].aval
        assert (scratch.shape, scratch.dtype) == ((4 * 8, 16), jnp.float32)
        in_kernel = list(sub_jaxprs(kernel.params["jaxpr"]))
        assert [e for e in in_kernel if e.primitive.name == "exp"]
        mine = [e for e in in_kernel if e.primitive.name == "dot_general"]
        assert mine
        dots += mine
    assert {e.outvars[0].aval.dtype for e in eqns
            if e.primitive.name == "exp"} == {jnp.dtype(jnp.float32)}
    dots += [e for e in eqns if e.primitive.name == "dot_general"]
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
    `pallas_call` under the forward's, the backward kernel's under its
    own."""
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
    assert len(kernels) == 2
    assert [x for x in kernels if x.startswith(f"jvp({SSD})/{SSD}")], kernels
    way = f"transpose(jvp({SSD}))/{SSD}"
    assert [x for x in kernels if x.startswith(way)], kernels


def test_plan_at_the_published_shapes():
    """granite-4.0-h-micro: T 8192 in 32 chunks of 256, 64 heads of 64,
    state 128, bf16 x: the forward kernel over 32 heads a program
    ([256, 2048] blocks of x and y, a [2048, 128] f32 state), the
    backward kernel over 32 too ([256, 2048] blocks of x, dy and dx, a
    [2048, 128] f32 cotangent), each inside the VMEM it states; the
    state a sequence carries 2 MiB a layer, the chunks' entry states
    the largest array the scan forms."""
    plan = ssd_plan(1, 8192, 64, 64, 128, 256, dtype=jnp.bfloat16)
    fwd, bwd = plan.pop("fwd"), plan.pop("bwd")
    assert fwd == {
        "form": "pallas_fused", "heads_per_program": 32, "grid": (1, 2, 32),
        "block_rows": (256, 2048), "block_state": (2048, 128),
        "vmem_bytes": S._fwd_vmem_bytes(32, 64, 128, 256, 2),
        "vmem_limit_bytes": S._FWD_VMEM_LIMIT}
    assert fwd["vmem_bytes"] <= S._FWD_VMEM_BUDGET < S._FWD_VMEM_LIMIT
    assert bwd == {
        "form": "pallas_fused", "heads_per_program": 32, "grid": (1, 2, 32),
        "block_rows": (256, 2048), "block_state": (2048, 128),
        "vmem_bytes": S._bwd_vmem_bytes(32, 64, 128, 256, 2),
        "vmem_limit_bytes": S._BWD_VMEM_LIMIT}
    assert bwd["vmem_bytes"] <= S._BWD_VMEM_BUDGET < S._BWD_VMEM_LIMIT
    assert plan == {
        "chunk": 256, "chunks": 32, "padded_positions": 0,
        "state_bytes": 64 * 64 * 128 * 4,
        "largest_intermediate_bytes": 32 * 64 * 64 * 128 * 4}
    assert ssd_plan(1, 8000, 64, 64, 128, 256, dtype=jnp.bfloat16)[
        "padded_positions"] == 192
