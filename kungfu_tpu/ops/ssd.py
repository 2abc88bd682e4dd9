"""The chunked state-space-duality (SSD) scan of Mamba-2 (Dao & Gu,
"Transformers are SSMs", arXiv:2405.21060, section 7), forward and
backward.

Per head h of H, with P channels a head and ONE group of B and C that
every head reads:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T      S [P, N], S_0 = 0
    y_t = S_t C_t + D x_t

`dt` is the step size after its softplus (> 0) and A < 0, so every
decay exp(dt A) lies in (0, 1]. No loop runs over positions. In chunks
of Q positions (`chunk`), with cs_i the running sum of dt A inside a
chunk (position i counted):

- in a chunk: y_i = sum over j <= i of (C_i . B_j) exp(cs_i - cs_j)
  dt_j x_j, one masked [Q, Q] matrix a head times the chunk's x. The
  exponent is masked to -inf above the diagonal BEFORE the exp, so
  every exp this module forms is of a number <= 0: nothing overflows
  however long the chunk or strong the decay;
- a chunk's end state: sum over j of exp(cs_last - cs_j) dt_j x_j B_j^T;
- between chunks: a pass over the chunks (`lax.scan`, one step a chunk,
  elementwise in f32) carries S_in, the state each chunk enters with;
- from the states: y_i += exp(cs_i) S_in C_i.

The decays, their sums and the states passed between chunks are f32.
The matmuls take their operands in x's dtype (bf16 in a bf16 model) and
accumulate in f32. The [chunks, H, Q, Q] decays and masked products are
formed a pass of `heads_per_pass` heads at a time (`_PASS_BYTES`), so
the largest of them at the published shapes is 64 MiB, and none
outlives its layer. The backward is written out (`_backward`), not
derived: it recomputes what the forward formed from the inputs and the
chunks' entry states, which are the only residual beside the inputs.

Forward and backward are each one inlined jit, as `ops/flash.py::
_stream_fwd` is, so that a model's layers 2..L replay the first one's
trace. `ssd_plan` says what runs at a shape.

Forms timed isolated on one TPU v5e (2026-10-16, median of 20 calls,
`jax.block_until_ready`) at B 1, T 8192, H 64, P 64, N 128, Q 256, bf16
x, B and C: the benchmark's one Mamba-2 configuration. The in-chunk part's forward, the masked [Q, Q] products times x
with C B^T and the running sums, is where the two forms differ:

    XLA, passes of 8 heads (this module)        1.99 ms
    Pallas, grid (B, chunks, heads), one
      program a chunk and head, C B^T from XLA  3.41 ms

The Pallas form's 2048 programs each do one [256, 256] x [256, 64]
matmul at half the MXU's width, and its x and outputs cross HBM in a
head-major layout XLA transposes to and from; it was not taken further.
This XLA form whole: forward 4.62 ms a layer, forward + backward 8.02.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..trace.scopes import SSD

F32 = jnp.float32

#: the largest [B, chunks, heads, Q, Q] f32 array a pass of heads forms
_PASS_BYTES = 64 * 1024 * 1024


def _heads_per_pass(b: int, nc: int, h: int, chunk: int) -> int:
    """The most heads (a divisor of h) whose [B, chunks, heads, Q, Q] f32
    decays fit `_PASS_BYTES`; at least one."""
    one = b * nc * chunk * chunk * 4
    return max([g for g in range(1, h + 1)
                if h % g == 0 and g * one <= _PASS_BYTES] or [1])


def ssd_plan(batch: int, seq: int, heads: int, head_dim: int, state: int,
             chunk: int) -> dict:
    """What `ssd` runs at this shape: its chunks (the sequence padded to
    a whole number of them), the heads a pass of the in-chunk work
    takes, the bytes of the state a sequence carries ([B, H, P, N] f32:
    what a server would keep a sequence a layer) and of the largest
    array it forms (a pass's [B, chunks, heads, Q, Q] f32 decays, the
    chunks' [B, chunks, H, P, N] f32 states, or a [B, T, H, P] f32
    output)."""
    nc = -(-seq // chunk)
    per_pass = _heads_per_pass(batch, nc, heads, chunk)
    pass_bytes = batch * nc * per_pass * chunk * chunk * 4
    states = batch * nc * heads * head_dim * state * 4
    rows = batch * nc * chunk * heads * head_dim * 4
    return {"form": "xla_chunked", "chunk": chunk, "chunks": nc,
            "padded_positions": nc * chunk - seq,
            "heads_per_pass": per_pass, "passes": heads // per_pass,
            "state_bytes": batch * heads * head_dim * state * 4,
            "pass_bytes": pass_bytes,
            "largest_intermediate_bytes": max(pass_bytes, states, rows)}


def _split_heads(a, ng):
    """[B, c, Q, H, ...] -> [ng, B, c, Q, H / ng, ...]."""
    b, nc, q, h = a.shape[:4]
    return jnp.moveaxis(a.reshape(b, nc, q, ng, h // ng, *a.shape[4:]), 3, 0)


def _join_heads(a):
    """The inverse of `_split_heads`."""
    a = jnp.moveaxis(a, 0, 3)
    return a.reshape(*a.shape[:3], -1, *a.shape[5:])


def _below(q, diagonal=False):
    """[Q, Q]: i > j, or i >= j with the `diagonal`."""
    i = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    j = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return i >= j if diagonal else i > j


def _in_chunk_decay(cs):
    """cs [B, c, Q, h] -> L [B, c, h, Q, Q]: exp(cs_i - cs_j) where
    i >= j, else 0. The exponent is masked before the exp: cs falls
    along a chunk, so what is left is <= 0."""
    rows = jnp.swapaxes(cs, -1, -2)
    return jnp.exp(jnp.where(_below(cs.shape[2], diagonal=True),
                             rows[..., :, None] - rows[..., None, :],
                             -jnp.inf))


def _chunked(x, dt, A, B, C, chunk):
    b, t, h, p = x.shape
    nc, n = t // chunk, B.shape[-1]
    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h).astype(F32)
    cs = jnp.cumsum(dtc * A.astype(F32), axis=2)     # [B, c, Q, H], <= 0
    return (xc, dtc, B.reshape(b, nc, chunk, n), C.reshape(b, nc, chunk, n),
            cs, cs[:, :, -1])


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=F32)


@functools.partial(jax.jit, inline=True, static_argnames=("chunk", "per_pass"))
@jax.named_scope(SSD)
def _forward(x, dt, A, B, C, D, *, chunk, per_pass):
    """(y, final state, S_in) at a length that is whole chunks."""
    cdt = x.dtype
    xc, dtc, Bc, Cc, cs, last = _chunked(x, dt, A, B, C, chunk)
    # each chunk's end state from its own positions
    w = dtc * jnp.exp(last[:, :, None] - cs)
    s_chunk = _mm("bcjhp,bcjn->bchpn", (xc * w[..., None]).astype(cdt), Bc)

    def across(state, step):
        decay, s = step
        return decay[..., None, None] * state + s, state

    final, s_in = lax.scan(across, jnp.zeros_like(s_chunk[:, 0]),
                           (jnp.moveaxis(jnp.exp(last), 1, 0),
                            jnp.moveaxis(s_chunk, 1, 0)))
    s_in = jnp.moveaxis(s_in, 0, 1)                 # [B, c, H, P, N]
    y = _mm("bcin,bchpn->bcihp", Cc, s_in.astype(cdt)) * jnp.exp(cs)[..., None]
    # in each chunk, `per_pass` heads at a time
    g = _mm("bcin,bcjn->bcij", Cc, Bc)
    ng = x.shape[2] // per_pass

    def in_chunk(args):
        cs_g, dt_g, x_g = args
        m = (g[:, :, None] * _in_chunk_decay(cs_g)
             * jnp.swapaxes(dt_g, -1, -2)[..., None, :])
        return _mm("bchij,bcjhp->bcihp", m.astype(cdt), x_g)

    y = y + _join_heads(lax.map(in_chunk, (
        _split_heads(cs, ng), _split_heads(dtc, ng), _split_heads(xc, ng))))
    y = y + D.astype(F32)[:, None] * xc.astype(F32)
    return y.reshape(x.shape).astype(cdt), final, s_in


@functools.partial(jax.jit, inline=True, static_argnames=("chunk", "per_pass"))
@jax.named_scope(SSD)
def _backward(x, dt, A, B, C, D, s_in, dy, dfinal, *, chunk, per_pass):
    cdt = x.dtype
    xc, dtc, Bc, Cc, cs, last = _chunked(x, dt, A, B, C, chunk)
    x32 = xc.astype(F32)
    dyc = dy.reshape(xc.shape)
    dy32 = dyc.astype(F32)
    dD = jnp.sum(dy32 * x32, axis=(0, 1, 2, 4))
    dx = D.astype(F32)[:, None] * dy32
    # y += exp(cs_i) S_in C_i
    e = jnp.exp(cs)
    dye = (dy32 * e[..., None]).astype(cdt)
    s_in_c = s_in.astype(cdt)
    ds = _mm("bcihp,bcin->bchpn", dye, Cc)           # dL/dS_in, this chunk's
    dC = _mm("bcihp,bchpn->bcin", dye, s_in_c)
    dcs = e * jnp.sum(dy32 * _mm("bcin,bchpn->bcihp", Cc, s_in_c), axis=-1)

    # the pass between chunks, reversed: g_c = ds_c + decay_c g_{c+1},
    # g_C = dfinal; chunk c's state takes g_{c+1}
    def back(g, step):
        decay, state, ds_c = step
        dlast = decay * jnp.sum(g * state, axis=(-2, -1))
        return ds_c + decay[..., None, None] * g, (g, dlast)

    _, (ds_chunk, dlast) = lax.scan(
        back, dfinal.astype(F32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (jnp.exp(last), s_in, ds)),
        reverse=True)
    ds_chunk = jnp.moveaxis(ds_chunk, 0, 1).astype(cdt)
    dlast = jnp.moveaxis(dlast, 0, 1)                # [B, c, H]
    # each chunk's end state: sum_j exp(last - cs_j) dt_j x_j B_j^T
    wexp = jnp.exp(last[:, :, None] - cs)
    w = dtc * wexp
    dxw = _mm("bchpn,bcjn->bcjhp", ds_chunk, Bc)
    dB = _mm("bcjhp,bchpn->bcjn", (x32 * w[..., None]).astype(cdt), ds_chunk)
    dx = dx + dxw * w[..., None]
    dw = jnp.sum(dxw * x32, axis=-1)
    ddt = dw * wexp
    # d/d(last - cs_j); at the chunk's last position that exponent is 0
    # whatever cs is, so its term is left out rather than added to cs
    # and taken off again: the two would cancel only to f32 rounding of
    # an O(1) number, and swamp the strong decays' tiny gradients
    dlog = (dw * w).at[:, :, -1].set(0.0)
    dcs = dcs - dlog
    dlast = dlast + jnp.sum(dlog, axis=2)
    # in each chunk, `per_pass` heads at a time: m = (C_i.B_j) L_ij dt_j
    g = _mm("bcin,bcjn->bcij", Cc, Bc)
    ng = x.shape[2] // per_pass

    def in_chunk(args):
        cs_g, dt_g, x_g, dy_g = args
        decay = _in_chunk_decay(cs_g)
        dt_j = jnp.swapaxes(dt_g, -1, -2)[..., None, :]
        m = g[:, :, None] * decay * dt_j
        dm = _mm("bcihp,bcjhp->bchij", dy_g, x_g)
        dx_g = _mm("bchij,bcihp->bcjhp", m.astype(cdt), dy_g)
        ldm = decay * dm
        # dm * m below the diagonal: on it the exponent cs_i - cs_i is 0
        # whatever cs is (as `dlog` above)
        z = jnp.where(_below(cs.shape[2]), ldm * g[:, :, None] * dt_j,
                      0.0)
        dcs_g = jnp.sum(z, axis=-1) - jnp.sum(z, axis=-2)
        ddt_g = jnp.sum(ldm * g[:, :, None], axis=-2)
        return (dx_g, jnp.swapaxes(dcs_g, -1, -2),
                jnp.swapaxes(ddt_g, -1, -2), jnp.sum(ldm * dt_j, axis=2))

    dx_d, dcs_d, ddt_d, dg = lax.map(in_chunk, tuple(
        _split_heads(a, ng) for a in (cs, dtc, xc, dyc)))
    dx = dx + _join_heads(dx_d)
    dcs = dcs + _join_heads(dcs_d)
    ddt = ddt + _join_heads(ddt_d)
    dg = jnp.sum(dg, axis=0).astype(cdt)
    dC = dC + _mm("bcij,bcjn->bcin", dg, Bc)
    dB = dB + _mm("bcij,bcin->bcjn", dg, Cc)
    # cs_i = sum over k <= i of dt_k A: a reversed running sum
    dcs = dcs.at[:, :, -1].add(dlast)
    da = lax.cumsum(dcs, axis=2, reverse=True)
    ddt = ddt + da * A.astype(F32)
    dA = jnp.sum(da * dtc, axis=(0, 1, 2))
    return (dx.reshape(x.shape).astype(x.dtype),
            ddt.reshape(dt.shape).astype(dt.dtype), dA.astype(A.dtype),
            dB.reshape(B.shape).astype(B.dtype),
            dC.reshape(C.shape).astype(C.dtype), dD.astype(D.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd(x, dt, A, B, C, D, chunk, per_pass):
    y, final, _ = _forward(x, dt, A, B, C, D, chunk=chunk, per_pass=per_pass)
    return y, final


def _ssd_fwd(x, dt, A, B, C, D, chunk, per_pass):
    y, final, s_in = _forward(x, dt, A, B, C, D, chunk=chunk,
                              per_pass=per_pass)
    return (y, final), (x, dt, A, B, C, D, s_in)


def _ssd_bwd(chunk, per_pass, res, cot):
    dy, dfinal = cot
    return _backward(*res, dy, dfinal, chunk=chunk, per_pass=per_pass)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x, dt, A, B, C, D, *, chunk: int):
    """(y, final) of x [B, T, H, P], dt [B, T, H] (the step size after
    its softplus), A [H] (< 0), B and C [B, T, N] (one group for every
    head) and D [H]: y [B, T, H, P] and the state after the last
    position, [B, H, P, N] in f32. Differentiable in all six.

    T need not be a multiple of `chunk`: the tail chunk is padded with
    zero steps, which neither decay the state nor add to it."""
    t = x.shape[1]
    pad = -t % chunk
    with jax.named_scope(SSD):
        if pad:
            x, dt, B, C = (
                jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                for a in (x, dt, B, C))
        per_pass = _heads_per_pass(x.shape[0], x.shape[1] // chunk,
                                   x.shape[2], chunk)
        y, final = _ssd(x, dt, A, B, C, D, chunk, per_pass)
        y = y[:, :t]
    return y, final
