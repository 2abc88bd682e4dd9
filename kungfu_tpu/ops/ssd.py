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
- between chunks: S_in, the state each chunk enters with, carried from
  one chunk to the next elementwise in f32 (one step a chunk);
- from the states: y_i += exp(cs_i) S_in C_i.

The decays, their sums and the states passed between chunks are f32.
The matmuls take their operands in x's dtype (bf16 in a bf16 model) and
accumulate in f32.

The forward is ONE `pallas_call` (`_fwd_kernel`) over (batch, groups
of `hg` heads, chunks), the chunks innermost and in order. A program
forms C B^T and the mask once for its heads, then for each head the
chunk's masked [Q, Q] decays and products (`_in_chunk`), times its
lanes of x; the output from the entry state
for all its heads in one matmul (C against the [hg P, N] state); the
D skip; and the chunk's contribution to the state, one matmul (x w)^T
B. The state is carried across the chunks in an f32 VMEM scratch and
written out as each chunk's entry state S_in ([B, chunks, H, P, N]
f32, the backward's one residual beside the inputs) and as the final
state. x and y cross HBM as lane blocks of [B, T, H P], their natural
layout; XLA forms only the decays' running sums ([B, T, H] f32) and
lays them and the step sizes out by rows and by columns of a program's
heads. `hg` (`_heads_per_program`) is the most heads whose blocks fit
`_FWD_VMEM_BUDGET`. Off the TPU the kernel runs in Pallas's
interpreter (`_interpret`).

The backward is written out in XLA (`_backward`), not derived: it
recomputes what the forward formed from the inputs and S_in, its
in-chunk work a pass of `heads_per_pass` heads at a time
(`_PASS_BYTES`: the largest [B, chunks, heads, Q, Q] f32 array at the
published shapes is 64 MiB, and none outlives its layer), its pass
between chunks a reversed `lax.scan`. It is most of the scan's time:
in a step of the benchmark's Mamba-2 model on one TPU v5e, 8.7 ms of a
layer's 9.6, spent above all relaying x, dy and its outputs to and
from passes of heads.

Forward and backward are each one inlined jit, as `ops/flash.py::
_stream_fwd` is, so that a model's layers 2..L replay the first one's
trace. `ssd_plan` says what runs at a shape.

Forms of the forward timed isolated on one TPU v5e (2026-10-18; 50
calls dispatched back to back, one wait, the median of three rounds)
at B 1, T 8192, H 64, P 64, N 128, Q 256, bf16 x, B and C: the
benchmark's one Mamba-2 configuration. ms a call, the running sums and
an isolated x's copies to and from [B, T, H P] included (in a model x
is a reshape of [B, T, H P]: there the kernel alone read 0.42 ms a
layer at 32 heads a program):

    XLA: a lax.map over passes of 8 heads, a lax.scan over chunks  4.13
    this kernel, hg heads a program:   hg 4    1.19
                                       hg 8    0.90
                                       hg 16   0.79
                                       hg 32   0.75
                                       hg 64   0.79

Forward and backward: 9.93 ms with XLA's forward, 6.91 with the
kernel's (hg 16). More heads a program means fewer, wider programs and
one C B^T for more heads; at 64 the grid runs one program a chunk, in
order, and lost. So `hg` is the most heads whose buffers fit the
scoped VMEM Mosaic gives a kernel by default (32 here). Forming a
chunk's [Q, Q] work in [128, 128] blocks, the one above the diagonal
skipped, timed the same (0.90 / 0.79 / 0.75 at hg 8 / 16 / 32) and was
not kept. Taking one piece out at a time (hg 32, wrong numbers, 0.740
ms whole): each head's work 0.211 ms, of it the lane broadcasts of its
[Q, 1] columns (exp(cs_i) and w 0.100, cs_i 0.083); its exps, mask
and matmul no measurable part; S_in's stores 0.008. An earlier Pallas
form, one program a chunk and head with C B^T read from XLA and x in
a head-major layout, lost to XLA on the in-chunk part alone (3.41 ms
against 1.99, a wait after each call).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..trace.scopes import SSD

F32 = jnp.float32
_LANES = 128
_NT = ((1,), (1,)), ((), ())     # a @ b.T
_TN = ((0,), (0,)), ((), ())     # a.T @ b

#: the most VMEM the forward kernel's buffers may take (`_fwd_vmem_bytes`):
#: the scoped VMEM Mosaic gives a kernel by default on a TPU v5e
_FWD_VMEM_BUDGET = 16 * 1024 * 1024
#: the scoped VMEM the forward kernel states
_FWD_VMEM_LIMIT = 32 * 1024 * 1024

#: the largest [B, chunks, heads, Q, Q] f32 array a pass of heads forms
_PASS_BYTES = 64 * 1024 * 1024


def _heads_per_pass(b: int, nc: int, h: int, chunk: int) -> int:
    """The most heads (a divisor of h) whose [B, chunks, heads, Q, Q] f32
    decays fit `_PASS_BYTES`; at least one."""
    one = b * nc * chunk * chunk * 4
    return max([g for g in range(1, h + 1)
                if h % g == 0 and g * one <= _PASS_BYTES] or [1])


def _fwd_vmem_bytes(hg: int, p: int, n: int, chunk: int,
                    itemsize: int) -> int:
    """The forward kernel's VMEM at `hg` heads a program: its blocks,
    two buffers each (x and y; B and C; the step sizes and their sums
    by rows and by columns, padded to the (8, 128) tile; D's lanes; the
    entry and final states), the carried state, and the values a chunk
    forms (C B^T, the mask and one head's decays and products, all
    [Q, Q]; the output from the states; the state read)."""
    def tile(rows, cols, size=4):
        return -(-rows // 8) * 8 * -(-cols // _LANES) * _LANES * size

    lanes = hg * p
    blocks = (2 * tile(chunk, lanes, itemsize) + 2 * tile(chunk, n, itemsize)
              + 2 * tile(hg, chunk) + 2 * tile(chunk, hg) + tile(1, lanes)
              + 2 * tile(lanes, n))
    return (2 * blocks + tile(lanes, n) + 4 * tile(chunk, chunk)
            + tile(chunk, lanes) + tile(lanes, n))


def _heads_per_program(h: int, p: int, n: int, chunk: int,
                       itemsize: int) -> int:
    """The most heads (a divisor of h whose channels fill whole blocks
    of lanes, or h itself) whose kernel buffers fit `_FWD_VMEM_BUDGET`;
    at least the fewest such heads."""
    fits = [g for g in range(1, h + 1)
            if h % g == 0 and (g * p % _LANES == 0 or g == h)]
    return max([g for g in fits
                if _fwd_vmem_bytes(g, p, n, chunk, itemsize)
                <= _FWD_VMEM_BUDGET] or fits[:1])


def _interpret() -> bool:
    """Pallas's interpreter off the TPU (the CPU suite), Mosaic on it."""
    return jax.default_backend() != "tpu"


def ssd_plan(batch: int, seq: int, heads: int, head_dim: int, state: int,
             chunk: int, *, dtype) -> dict:
    """What `ssd` runs at this shape, x in `dtype`: its chunks (the
    sequence padded to a whole number of them); under "fwd" the forward
    kernel's heads a program, grid, blocks and VMEM (`_fwd_vmem_bytes`,
    and the limit it states); the heads a pass of the backward's
    in-chunk work takes; the bytes of the state a sequence carries
    ([B, H, P, N] f32: what a server would keep a sequence a layer) and
    of the largest array the scan forms (a backward pass's [B, chunks,
    heads, Q, Q] f32 decays, the chunks' [B, chunks, H, P, N] f32
    entry states, or a [B, T, H, P] f32 output)."""
    nc = -(-seq // chunk)
    itemsize = jnp.dtype(dtype).itemsize
    hg = _heads_per_program(heads, head_dim, state, chunk, itemsize)
    per_pass = _heads_per_pass(batch, nc, heads, chunk)
    pass_bytes = batch * nc * per_pass * chunk * chunk * 4
    states = batch * nc * heads * head_dim * state * 4
    rows = batch * nc * chunk * heads * head_dim * 4
    return {"form": "xla_chunked", "chunk": chunk, "chunks": nc,
            "padded_positions": nc * chunk - seq,
            "fwd": {"form": "pallas_fused", "heads_per_program": hg,
                    "grid": (batch, heads // hg, nc),
                    "block_rows": (chunk, hg * head_dim),
                    "block_state": (hg * head_dim, state),
                    "vmem_bytes": _fwd_vmem_bytes(hg, head_dim, state, chunk,
                                                  itemsize),
                    "vmem_limit_bytes": _FWD_VMEM_LIMIT},
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


def _lane_heads(hg: int, p: int) -> int:
    """Heads whose channels share one block of lanes: 128 / P where P
    divides 128 (two heads of 64), one where P is 128 or more; a
    divisor of hg. A head's in-chunk matmul runs against its whole
    block of lanes, at the MXU's cost of its own P columns, and the
    kernel keeps the head's lanes: no slice or store at an offset that
    is not a block's."""
    return math.gcd(_LANES // p if _LANES % p == 0 else 1, hg)


def _last_as_row(col, width):
    """A column [Q, 1]'s last element as a row [1, width]: broadcast
    along the lanes, then sliced (Mosaic broadcasts one element along
    the sublanes and the lanes in two steps, not one)."""
    tail = min(8, col.shape[0])
    return jnp.broadcast_to(col[-tail:], (tail, width))[tail - 1:]


def _in_chunk(g, below, cs_i, cs_j, dt_j, xb):
    """One head's y_i = sum over j <= i of (C_i . B_j) exp(cs_i - cs_j)
    dt_j x_j in a chunk, against the head's block of lanes of x: [Q,
    lanes] f32. The exponent is masked before the exp."""
    decays = jnp.exp(jnp.where(below, cs_i - cs_j, -jnp.inf))
    m = (g * decays * dt_j).astype(xb.dtype)
    return jnp.dot(m, xb, preferred_element_type=F32)


def _fwd_kernel(x_ref, b_ref, c_ref, cs_row_ref, dt_row_ref, cs_col_ref,
                dt_col_ref, d_ref, y_ref, s_in_ref, final_ref, s_ref, *,
                p):
    """One chunk of `hg` heads: program (batch, group of heads, chunk),
    the chunks innermost and in order, the group's state [hg P, N] f32
    carried across them in `s_ref`."""
    c = pl.program_id(2)
    q, hg = cs_col_ref.shape
    n = b_ref.shape[1]
    cdt = x_ref.dtype

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    state = s_ref[...]
    s_in_ref[...] = state
    bm, cm = b_ref[...], c_ref[...]
    g = lax.dot_general(cm, bm, _NT, preferred_element_type=F32)  # C B^T
    below = _below(q, diagonal=True)
    # y from the entry state, the group's heads in one matmul: C S^T
    from_state = lax.dot_general(cm, state.astype(cdt), _NT,
                                 preferred_element_type=F32)
    cs_col = cs_col_ref[...]
    last = cs_col[q - 1:, :]                                      # [1, hg]
    e = jnp.exp(cs_col)
    w = dt_col_ref[...] * jnp.exp(last - cs_col)
    r = _lane_heads(hg, p)
    lanes = r * p
    head_of_lane = lax.broadcasted_iota(jnp.int32, (1, lanes), 1) // p
    for blk in range(hg // r):
        cols = slice(blk * lanes, (blk + 1) * lanes)
        xb = x_ref[:, cols]
        y = scale = wl = jnp.zeros((q, lanes), F32)
        for k in range(r):
            h = blk * r + k
            mine = head_of_lane == k
            y = jnp.where(mine, _in_chunk(g, below, cs_col[:, h:h + 1],
                                          cs_row_ref[h:h + 1, :],
                                          dt_row_ref[h:h + 1, :], xb), y)
            scale = jnp.where(mine, e[:, h:h + 1], scale)
            wl = jnp.where(mine, w[:, h:h + 1], wl)
        x32 = xb.astype(F32)
        y = y + from_state[:, cols] * scale + d_ref[:, cols] * x32
        y_ref[:, cols] = y.astype(y_ref.dtype)
        # the chunk's own contribution to these heads' states: (x w)^T B
        add = lax.dot_general((x32 * wl).astype(cdt), bm, _TN,
                              preferred_element_type=F32)
        for k in range(r):
            h = blk * r + k
            rows = slice(h * p, (h + 1) * p)
            kept = jnp.exp(_last_as_row(cs_col[:, h:h + 1], n))
            s_ref[rows, :] = kept * state[rows, :] + add[k * p:(k + 1) * p, :]

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        final_ref[...] = s_ref[...]


@functools.partial(jax.jit, inline=True,
                   static_argnames=("chunk", "hg", "interpret"))
@jax.named_scope(SSD)
def _forward(x, dt, A, B, C, D, *, chunk, hg, interpret):
    """(y, final state, S_in) at a length that is whole chunks: one
    `pallas_call` over (batch, groups of `hg` heads, chunks)."""
    b, t, h, p = x.shape
    n, ng, nc = B.shape[-1], h // hg, t // chunk
    _, dtc, _, _, cs, _ = _chunked(x, dt, A, B, C, chunk)

    def groups(a):                   # [B, c, Q, H] -> [B, H / hg, T, hg]
        return a.reshape(b, t, ng, hg).transpose(0, 2, 1, 3)

    cs_col, dt_col = groups(cs), groups(dtc)
    cs_row, dt_row = (jnp.swapaxes(a, 2, 3) for a in (cs_col, dt_col))
    d_lanes = jnp.repeat(D.astype(F32), p)[None]                # [1, H P]
    rows = pl.BlockSpec((None, chunk, hg * p), lambda i, j, k: (i, k, j))
    bc = pl.BlockSpec((None, chunk, n), lambda i, j, k: (i, k, 0))
    by_row = pl.BlockSpec((None, None, hg, chunk),
                          lambda i, j, k: (i, j, 0, k))
    by_col = pl.BlockSpec((None, None, chunk, hg),
                          lambda i, j, k: (i, j, k, 0))
    states = pl.BlockSpec((None, hg * p, n), lambda i, j, k: (i, j, 0))
    y, s_in, final = pl.pallas_call(
        functools.partial(_fwd_kernel, p=p),
        grid=(b, ng, nc),
        in_specs=[rows, bc, bc, by_row, by_row, by_col, by_col,
                  pl.BlockSpec((1, hg * p), lambda i, j, k: (0, j))],
        out_specs=[rows,
                   pl.BlockSpec((None, None, hg * p, n),
                                lambda i, j, k: (i, k, j, 0)),
                   states],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * p), x.dtype),
                   jax.ShapeDtypeStruct((b, nc, h * p, n), F32),
                   jax.ShapeDtypeStruct((b, h * p, n), F32)],
        scratch_shapes=[pltpu.VMEM((hg * p, n), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_FWD_VMEM_LIMIT),
        interpret=interpret,
    )(x.reshape(b, t, h * p), B, C, cs_row, dt_row, cs_col, dt_col, d_lanes)
    return (y.reshape(x.shape), final.reshape(b, h, p, n),
            s_in.reshape(b, nc, h, p, n))


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


def _forward_at(x, dt, A, B, C, D, chunk):
    """`_forward` over as many heads a program as `_heads_per_program`
    gives at these shapes."""
    _, _, h, p = x.shape
    hg = _heads_per_program(h, p, B.shape[-1], chunk, x.dtype.itemsize)
    return _forward(x, dt, A, B, C, D, chunk=chunk, hg=hg,
                    interpret=_interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd(x, dt, A, B, C, D, chunk, per_pass):
    y, final, _ = _forward_at(x, dt, A, B, C, D, chunk)
    return y, final


def _ssd_fwd(x, dt, A, B, C, D, chunk, per_pass):
    y, final, s_in = _forward_at(x, dt, A, B, C, D, chunk)
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
