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

The backward is ONE `pallas_call` too (`_bwd_kernel`), written out,
not derived: over (batch, groups of `hg` heads, chunks), the chunks
innermost and LAST FIRST (the index maps read block nc - 1 - k). The
cotangent of the group's state, [hg P, N] f32, is carried across the
chunks in a VMEM scratch, seeded from the final state's cotangent, as
the forward carries the state: a program reads it as the cotangent of
its chunk's end state and leaves the cotangent of the state the chunk
entered with (g <- ds + exp(cs_last) g). It recomputes what the
forward formed from the inputs and S_in, the forward's one residual:
C B^T once a program; then, a block of lanes (two heads of 64) at a
time, the entry state's terms (dy exp(cs_i) against C and S_in) and
the end state's (g B^T against x and the w_j, (x w) g), and for each
head the chunk's masked [Q, Q] decays, products and their cotangents,
with dG = d(C B^T) accumulated over the heads. A block's x and dy are
also transposed to [lanes, Q], and what is summed over a head's
channels, and dx, are formed that way: so each such sum runs down the
sublanes and lands as a row of positions, and dx needs no transposed
[Q, Q] product a head. dcs and ddt leave as rows [hg, Q], dB and dC as
f32 partials a group, dD's as lanes summed over the chunks in place;
x, dy and dx cross HBM as lane blocks of [B, T, H P]. XLA keeps only
[B, T, H] and [B, T, N] work: the partials' sums, the reversed running
sum that takes dcs to ddt and dA, the casts. Its `hg`
(`_bwd_heads_per_program`) is the most heads whose blocks fit
`_BWD_VMEM_BUDGET`.

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

Forms of the backward timed the same way at the same shapes
(2026-10-19; a random final-state cotangent; x and dy copied from
[B, T, H P] inside each call; in the model the kernel alone read 0.97
ms a layer at 32 heads a program):

    XLA: passes of 8 heads, a reversed lax.scan over chunks   8.58
    this kernel, hg heads a program:   hg 8    1.49
                                       hg 16   1.37
                                       hg 32   1.31

Forward and backward: 9.10 ms with XLA's backward, 1.75 with the
kernel's. dx equals XLA's to 1.5e-4 of its largest element (one bf16
rounding of a sum taken in another order), dB and dC to 4.7e-3 (the
same, in their bf16), the f32 cotangents to 4e-6. A first form of the
kernel, which summed over each head's channels across the lanes,
laid each sum into a column of [Q, hg] and took dx from m^T dy, read
2.55 / 2.48 / 2.41 ms at hg 8 / 16 / 32; taking one piece out at a
time (hg 32, wrong numbers, 2.19 ms whole on that machine): each
head's work 1.42 ms, of it the column sums and their laying out 0.95
and the transposed [Q, Q] product 0.42, while swapping the cross-lane
reductions alone for a slice saved nothing. The transposes, cross-lane
sums and lane broadcasts, not the MXU or the exps, bound it; the form
kept works a block of lanes transposed to turn those sums into sums
down the sublanes. Transposing x and dy in bf16 in place of f32 timed
the same (1.32 ms at hg 32).
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
#: the most VMEM the backward kernel's buffers may take (`_bwd_vmem_bytes`):
#: room for 32 heads of 64 a program at chunk 256, the fastest timed (the
#: module's docstring); 64 would take 27.75 MiB
_BWD_VMEM_BUDGET = 24 * 1024 * 1024
#: the scoped VMEM the backward kernel states
_BWD_VMEM_LIMIT = 40 * 1024 * 1024


def _tile(rows: int, cols: int, size: int = 4) -> int:
    """Bytes of a [rows, cols] buffer padded to the (8, 128) tile."""
    return -(-rows // 8) * 8 * -(-cols // _LANES) * _LANES * size


def _fwd_vmem_bytes(hg: int, p: int, n: int, chunk: int,
                    itemsize: int) -> int:
    """The forward kernel's VMEM at `hg` heads a program: its blocks,
    two buffers each (x and y; B and C; the step sizes and their sums
    by rows and by columns, padded to the (8, 128) tile; D's lanes; the
    entry and final states), the carried state, and the values a chunk
    forms (C B^T, the mask and one head's decays and products, all
    [Q, Q]; the output from the states; the state read)."""
    lanes = hg * p
    blocks = (2 * _tile(chunk, lanes, itemsize) + 2 * _tile(chunk, n, itemsize)
              + 2 * _tile(hg, chunk) + 2 * _tile(chunk, hg) + _tile(1, lanes)
              + 2 * _tile(lanes, n))
    return (2 * blocks + _tile(lanes, n) + 4 * _tile(chunk, chunk)
            + _tile(chunk, lanes) + _tile(lanes, n))


def _bwd_vmem_bytes(hg: int, p: int, n: int, chunk: int,
                    itemsize: int) -> int:
    """The backward kernel's VMEM at `hg` heads a program: its blocks,
    two buffers each (x, dy and dx; B and C; the entry states and the
    final state's cotangent; the step sizes and their sums by rows, the
    sums by columns; dcs and ddt by rows; D's lanes and dD's; the dB and
    dC partials), the carried cotangent, and the values a chunk forms
    (C B^T, the dG accumulator, the masks and one head's decays,
    products and their cotangents, all [Q, Q]; a block of lanes' x and
    dy, their transposes and what is worked on transposed, [lanes, Q];
    its rows of the cotangent and the entry state, f32 and cast)."""
    lanes = hg * p
    blocks = (3 * _tile(chunk, lanes, itemsize) + 2 * _tile(chunk, n, itemsize)
              + 2 * _tile(lanes, n) + 4 * _tile(hg, chunk) + _tile(chunk, hg)
              + 2 * _tile(1, lanes) + 2 * _tile(chunk, n))
    return (2 * blocks + _tile(lanes, n) + 8 * _tile(chunk, chunk)
            + 12 * _tile(_LANES, chunk) + 4 * _tile(_LANES, n))


def _most_heads(h: int, p: int, fits) -> int:
    """The most heads (a divisor of h whose channels fill whole blocks
    of lanes, or h itself) for which `fits(heads)`; at least the fewest
    such heads."""
    whole = [g for g in range(1, h + 1)
             if h % g == 0 and (g * p % _LANES == 0 or g == h)]
    return max([g for g in whole if fits(g)] or whole[:1])


def _heads_per_program(h: int, p: int, n: int, chunk: int,
                       itemsize: int) -> int:
    """The forward kernel's heads a program: the most whose buffers fit
    `_FWD_VMEM_BUDGET`."""
    return _most_heads(h, p, lambda g: _fwd_vmem_bytes(
        g, p, n, chunk, itemsize) <= _FWD_VMEM_BUDGET)


def _bwd_heads_per_program(h: int, p: int, n: int, chunk: int,
                           itemsize: int) -> int:
    """The backward kernel's heads a program: the most whose buffers fit
    `_BWD_VMEM_BUDGET`."""
    return _most_heads(h, p, lambda g: _bwd_vmem_bytes(
        g, p, n, chunk, itemsize) <= _BWD_VMEM_BUDGET)


def _interpret() -> bool:
    """Pallas's interpreter off the TPU (the CPU suite), Mosaic on it."""
    return jax.default_backend() != "tpu"


def ssd_plan(batch: int, seq: int, heads: int, head_dim: int, state: int,
             chunk: int, *, dtype) -> dict:
    """What `ssd` runs at this shape, x in `dtype`: its chunks (the
    sequence padded to a whole number of them); under "fwd" and "bwd"
    each kernel's heads a program, grid, blocks and VMEM
    (`_fwd_vmem_bytes`, `_bwd_vmem_bytes`, and the limit each states);
    the bytes of the state a sequence carries ([B, H, P, N] f32: what a
    server would keep a sequence a layer) and of the largest array the
    scan forms (the chunks' [B, chunks, H, P, N] f32 entry states, or
    the backward's [B, groups, T, N] f32 partials of dB and dC)."""
    nc = -(-seq // chunk)
    itemsize = jnp.dtype(dtype).itemsize

    def kernel(hg, vmem_bytes, limit):
        return {"form": "pallas_fused", "heads_per_program": hg,
                "grid": (batch, heads // hg, nc),
                "block_rows": (chunk, hg * head_dim),
                "block_state": (hg * head_dim, state),
                "vmem_bytes": vmem_bytes(hg, head_dim, state, chunk,
                                         itemsize),
                "vmem_limit_bytes": limit}

    fwd = _heads_per_program(heads, head_dim, state, chunk, itemsize)
    bwd = _bwd_heads_per_program(heads, head_dim, state, chunk, itemsize)
    states = batch * nc * heads * head_dim * state * 4
    partials = batch * heads // bwd * nc * chunk * state * 4
    return {"chunk": chunk, "chunks": nc, "padded_positions": nc * chunk - seq,
            "fwd": kernel(fwd, _fwd_vmem_bytes, _FWD_VMEM_LIMIT),
            "bwd": kernel(bwd, _bwd_vmem_bytes, _BWD_VMEM_LIMIT),
            "state_bytes": batch * heads * head_dim * state * 4,
            "largest_intermediate_bytes": max(states, partials)}


def _below(q, diagonal=False):
    """[Q, Q]: i > j, or i >= j with the `diagonal`."""
    i = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    j = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return i >= j if diagonal else i > j


def _chunked(x, dt, A, B, C, chunk):
    b, t, h, p = x.shape
    nc, n = t // chunk, B.shape[-1]
    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h).astype(F32)
    cs = jnp.cumsum(dtc * A.astype(F32), axis=2)     # [B, c, Q, H], <= 0
    return (xc, dtc, B.reshape(b, nc, chunk, n), C.reshape(b, nc, chunk, n),
            cs, cs[:, :, -1])


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


def _rows_of_sums(a):
    """a [Q, Q]'s sums along its rows, as a row [1, Q]: its tiles of
    lanes added, transposed, and summed down the sublanes (no
    cross-lane reduction, and no column to lay into the lanes)."""
    q = a.shape[1]
    if q > _LANES and q % _LANES == 0:
        a = sum(a[:, i:i + _LANES] for i in range(0, q, _LANES))
    return jnp.sum(a.T, axis=0, keepdims=True)


def _bwd_kernel(x_ref, dy_ref, b_ref, c_ref, s_in_ref, dfinal_ref,
                cs_row_ref, dt_row_ref, cs_col_ref, d_ref, dx_ref, dcs_ref,
                ddt_ref, db_ref, dc_ref, dd_ref, g_ref, *, p):
    """One chunk of `hg` heads, backward: program (batch, group of heads,
    chunk), the chunks innermost and LAST FIRST. `g_ref` carries the
    cotangent of the group's state [hg P, N] f32 across them: on entry
    to a program that of the chunk's end state, on exit that of the
    state it entered with. A block of lanes is also worked on
    transposed, [lanes, Q], so that what is summed over a head's
    channels is summed down the sublanes and lands as a row of
    positions: dcs and ddt leave as rows [hg, Q], dB and dC as this
    group's f32 partials, dD's as lanes summed over the chunks in
    place."""
    c = pl.program_id(2)
    hg, q = cs_row_ref.shape
    n = b_ref.shape[1]
    cdt = x_ref.dtype

    @pl.when(c == 0)
    def _():
        g_ref[...] = dfinal_ref[...]
        dd_ref[...] = jnp.zeros_like(dd_ref)

    bm, cm = b_ref[...], c_ref[...]
    gcb = lax.dot_general(cm, bm, _NT, preferred_element_type=F32)  # C B^T
    below, strictly = _below(q, diagonal=True), _below(q)
    cs_col, cs_row, dt_row = cs_col_ref[...], cs_row_ref[...], dt_row_ref[...]
    last = cs_row[:, q - 1:]                                      # [hg, 1]
    e = jnp.exp(cs_row)
    wexp = jnp.exp(last - cs_row)
    w = dt_row * wexp
    r = _lane_heads(hg, p)
    lanes = r * p
    head_of_lane = lax.broadcasted_iota(jnp.int32, (1, lanes), 1) // p
    head_of_row = lax.broadcasted_iota(jnp.int32, (lanes, 1), 0) // p
    head = lax.broadcasted_iota(jnp.int32, (hg, 1), 0)
    # per head, by position: dcs, ddt and dw (d/dw_j); g . S_in
    dcs = ddt = dw = jnp.zeros((hg, q), F32)
    g_s = jnp.zeros((hg, 1), F32)
    dg = jnp.zeros((q, q), F32)                   # d(C B^T), over the heads
    db = dc = jnp.zeros((q, n), F32)
    for blk in range(hg // r):
        cols = slice(blk * lanes, (blk + 1) * lanes)
        xb, dyb = x_ref[:, cols], dy_ref[:, cols]
        x32, dy32 = xb.astype(F32), dyb.astype(F32)
        xt32, dyt32 = x32.T, dy32.T                           # [lanes, Q]
        xt, dyt = xt32.astype(cdt), dyt32.astype(cdt)
        # g_{c+1} (the cotangent of the chunk's end state) and S_in, these
        # heads' rows
        g, s_in = g_ref[cols, :], s_in_ref[cols, :]
        g_c, s_c = g.astype(cdt), s_in.astype(cdt)
        el = wl = jnp.zeros((lanes, q), F32)
        for k in range(r):
            h = blk * r + k
            el = jnp.where(head_of_row == k, e[h:h + 1], el)
            wl = jnp.where(head_of_row == k, w[h:h + 1], wl)
        # y_i += exp(cs_i) S_in C_i: the entry state's cotangent (ds), dC,
        # and dcs_i through exp(cs_i)
        dye = (dyt32 * el).astype(cdt)
        ds = jnp.dot(dye, cm, preferred_element_type=F32)
        dc = dc + lax.dot_general(dye, s_c, _TN, preferred_element_type=F32)
        by_state = dyt32 * lax.dot_general(s_c, cm, _NT,
                                           preferred_element_type=F32)
        # the chunk's end state += sum_j w_j x_j B_j^T, against g
        dxw = lax.dot_general(g_c, bm, _NT, preferred_element_type=F32)
        db = db + lax.dot_general((xt32 * wl).astype(cdt), g_c, _TN,
                                  preferred_element_type=F32)
        dxw_x = dxw * xt32
        dxt = dxw * wl                                        # dx^T
        for k in range(r):
            h = blk * r + k
            mine = slice(k * p, (k + 1) * p)
            cs_i = cs_col[:, h:h + 1]
            cs_j, dt_j = cs_row[h:h + 1], dt_row[h:h + 1]
            # in the chunk: m = (C_i . B_j) exp(cs_i - cs_j) dt_j, y = m x
            decays = jnp.exp(jnp.where(below, cs_i - cs_j, -jnp.inf))
            m = gcb * decays * dt_j
            dm = jnp.dot(jnp.where(head_of_lane == k, dyb, 0), xt,
                         preferred_element_type=F32)
            dxt = dxt + jnp.dot(jnp.where(head_of_row == k, dyt, 0),
                                m.astype(cdt), preferred_element_type=F32)
            ldm = decays * dm
            t = ldm * gcb
            # dm * m below the diagonal: on it the exponent cs_i - cs_i is
            # 0 whatever cs is (as `dlog` below)
            z = jnp.where(strictly, t * dt_j, 0.0)
            dg = dg + ldm * dt_j
            entry = jnp.sum(by_state[mine], axis=0, keepdims=True)
            dcs = jnp.where(head == h, e[h:h + 1] * entry + _rows_of_sums(z)
                            - jnp.sum(z, axis=0, keepdims=True), dcs)
            ddt = jnp.where(head == h, jnp.sum(t, axis=0, keepdims=True), ddt)
            dw = jnp.where(head == h, jnp.sum(dxw_x[mine], axis=0,
                                              keepdims=True), dw)
        dx_ref[:, cols] = (dxt.T + d_ref[:, cols] * dy32).astype(dx_ref.dtype)
        dd_ref[:, cols] += jnp.sum(dy32 * x32, axis=0, keepdims=True)
        # the state this chunk entered with: g <- ds + exp(cs_last) g
        for k in range(r):
            h = blk * r + k
            rows = slice(k * p, (k + 1) * p)
            gs = jnp.sum(g[rows] * s_in[rows], axis=0, keepdims=True)
            g_s = jnp.where(head == h, jnp.sum(gs, axis=1, keepdims=True),
                            g_s)
            kept = jnp.exp(_last_as_row(cs_col[:, h:h + 1], n))
            g_ref[h * p:(h + 1) * p, :] = kept * g[rows] + ds[rows]
    dgc = dg.astype(cdt)
    dc_ref[...] = dc + jnp.dot(dgc, bm, preferred_element_type=F32)
    db_ref[...] = db + lax.dot_general(dgc, cm, _TN,
                                       preferred_element_type=F32)
    # d/d(cs_last - cs_j); at the chunk's last position that exponent is
    # 0 whatever cs is, so its term is left out rather than added to cs
    # and taken off again: the two would cancel only to f32 rounding of
    # an O(1) number, and swamp the strong decays' tiny gradients
    at_last = lax.broadcasted_iota(jnp.int32, (hg, q), 1) == q - 1
    dlog = jnp.where(at_last, 0.0, dw * w)
    dlast = jnp.exp(last) * g_s + jnp.sum(dlog, axis=1, keepdims=True)
    dcs_ref[...] = dcs - dlog + jnp.where(at_last, dlast, 0.0)
    ddt_ref[...] = ddt + dw * wexp


def _grouped(cs, dtc, hg):
    """The running sums and step sizes [B, c, Q, H] by columns [B, H / hg,
    T, hg] and by rows [B, H / hg, hg, T] of each group of `hg` heads."""
    b, nc, q, h = cs.shape

    def cols(a):
        return a.reshape(b, nc * q, h // hg, hg).transpose(0, 2, 1, 3)

    cs_col, dt_col = cols(cs), cols(dtc)
    return (cs_col, dt_col) + tuple(jnp.swapaxes(a, 2, 3)
                                    for a in (cs_col, dt_col))


@functools.partial(jax.jit, inline=True,
                   static_argnames=("chunk", "hg", "interpret"))
@jax.named_scope(SSD)
def _forward(x, dt, A, B, C, D, *, chunk, hg, interpret):
    """(y, final state, S_in) at a length that is whole chunks: one
    `pallas_call` over (batch, groups of `hg` heads, chunks)."""
    b, t, h, p = x.shape
    n, ng, nc = B.shape[-1], h // hg, t // chunk
    _, dtc, _, _, cs, _ = _chunked(x, dt, A, B, C, chunk)
    cs_col, dt_col, cs_row, dt_row = _grouped(cs, dtc, hg)
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


@functools.partial(jax.jit, inline=True,
                   static_argnames=("chunk", "hg", "interpret"))
@jax.named_scope(SSD)
def _backward(x, dt, A, B, C, D, s_in, dy, dfinal, *, chunk, hg, interpret):
    """The six inputs' cotangents at a length that is whole chunks: one
    `pallas_call` over (batch, groups of `hg` heads, chunks last first),
    then XLA's [B, T, H] and [B, T, N] sums."""
    b, t, h, p = x.shape
    n, ng, nc = B.shape[-1], h // hg, t // chunk
    _, dtc, _, _, cs, _ = _chunked(x, dt, A, B, C, chunk)
    cs_col, _, cs_row, dt_row = _grouped(cs, dtc, hg)
    d_lanes = jnp.repeat(D.astype(F32), p)[None]                # [1, H P]
    back = nc - 1
    rows = pl.BlockSpec((None, chunk, hg * p),
                        lambda i, j, k: (i, back - k, j))
    bc = pl.BlockSpec((None, chunk, n), lambda i, j, k: (i, back - k, 0))
    by_row = pl.BlockSpec((None, None, hg, chunk),
                          lambda i, j, k: (i, j, 0, back - k))
    by_col = pl.BlockSpec((None, None, chunk, hg),
                          lambda i, j, k: (i, j, back - k, 0))
    per_group = pl.BlockSpec((None, None, chunk, n),
                             lambda i, j, k: (i, j, back - k, 0))
    dx, dcs, ddt, dB, dC, dD = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p),
        grid=(b, ng, nc),
        in_specs=[rows, rows, bc, bc,
                  pl.BlockSpec((None, None, hg * p, n),
                               lambda i, j, k: (i, back - k, j, 0)),
                  pl.BlockSpec((None, hg * p, n), lambda i, j, k: (i, j, 0)),
                  by_row, by_row, by_col,
                  pl.BlockSpec((1, hg * p), lambda i, j, k: (0, j))],
        out_specs=[rows, by_row, by_row, per_group, per_group,
                   pl.BlockSpec((None, 1, hg * p), lambda i, j, k: (i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * p), x.dtype),
                   jax.ShapeDtypeStruct(cs_row.shape, F32),
                   jax.ShapeDtypeStruct(cs_row.shape, F32),
                   jax.ShapeDtypeStruct((b, ng, t, n), F32),
                   jax.ShapeDtypeStruct((b, ng, t, n), F32),
                   jax.ShapeDtypeStruct((b, 1, h * p), F32)],
        scratch_shapes=[pltpu.VMEM((hg * p, n), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
        interpret=interpret,
    )(x.reshape(b, t, h * p), dy.reshape(b, t, h * p), B, C,
      s_in.reshape(b, nc, h * p, n), dfinal.reshape(b, h * p, n).astype(F32),
      cs_row, dt_row, cs_col, d_lanes)

    def heads(a):                    # [B, H / hg, hg, T] -> [B, c, Q, H]
        return a.transpose(0, 3, 1, 2).reshape(b, nc, chunk, h)

    # cs_i = sum over k <= i of dt_k A: a reversed running sum
    da = lax.cumsum(heads(dcs), axis=2, reverse=True)
    ddt = heads(ddt) + da * A.astype(F32)
    dA = jnp.sum(da * dtc, axis=(0, 1, 2))
    dD = jnp.sum(dD.reshape(b, h, p), axis=(0, 2))
    return (dx.reshape(x.shape), ddt.reshape(dt.shape).astype(dt.dtype),
            dA.astype(A.dtype), jnp.sum(dB, axis=1).astype(B.dtype),
            jnp.sum(dC, axis=1).astype(C.dtype), dD.astype(D.dtype))


def _forward_at(x, dt, A, B, C, D, chunk):
    """`_forward` over as many heads a program as `_heads_per_program`
    gives at these shapes."""
    _, _, h, p = x.shape
    hg = _heads_per_program(h, p, B.shape[-1], chunk, x.dtype.itemsize)
    return _forward(x, dt, A, B, C, D, chunk=chunk, hg=hg,
                    interpret=_interpret())


def _backward_at(x, dt, A, B, C, D, s_in, dy, dfinal, chunk):
    """`_backward` over as many heads a program as
    `_bwd_heads_per_program` gives at these shapes."""
    _, _, h, p = x.shape
    hg = _bwd_heads_per_program(h, p, B.shape[-1], chunk, x.dtype.itemsize)
    return _backward(x, dt, A, B, C, D, s_in, dy, dfinal, chunk=chunk, hg=hg,
                     interpret=_interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd(x, dt, A, B, C, D, chunk):
    y, final, _ = _forward_at(x, dt, A, B, C, D, chunk)
    return y, final


def _ssd_fwd(x, dt, A, B, C, D, chunk):
    y, final, s_in = _forward_at(x, dt, A, B, C, D, chunk)
    return (y, final), (x, dt, A, B, C, D, s_in)


def _ssd_bwd(chunk, res, cot):
    dy, dfinal = cot
    return _backward_at(*res, dy, dfinal, chunk)


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
        y, final = _ssd(x, dt, A, B, C, D, chunk)
        y = y[:, :t]
    return y, final
