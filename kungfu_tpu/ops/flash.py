"""Flash attention as a Pallas TPU kernel.

The hot op of the long-context path (`parallel/sequence.py`): plain
attention materializes [T, T] scores in HBM; this kernel streams K/V
blocks through VMEM with online-softmax accumulation so HBM traffic is
O(T) per query block (FlashAttention, Dao et al. 2022 — on TPU the
win is HBM bandwidth, the usual bottleneck, not SRAM reuse).

Three execution schemes per kernel (fwd / dq / dkv; the head scheme
and the fused backwards compute dq, dk and dv in one),
selected by a VMEM-budget estimate in the style of
`ops/fused_ce.py:_pick_blocks` (`flash_plan` shows the decision for a
shape):

- **head** (causal, no window, a whole head inside `_VMEM_BUDGET` and
  at most `_HEAD_MAX_CHUNKS` chunks — every causal T <= 2048 at
  d = 64, the GPT cells among them): grid (B*H,); one program holds
  the head and walks it in `_HEAD_CHUNK`-row chunks with the causal
  schedule unrolled at trace time. What lies under a chunk's diagonal
  is ONE wide unmasked block step, only the diagonal's square builds
  the mask, nothing above it is touched: 62.5% of the square at
  T = 1024, 56% at 2048, in 2*nb - 1 steps a kernel. The backward is
  ONE kernel: with queries and keys both in VMEM, one pass over the
  scores feeds dq, dk and dv (five matmuls a step, not 3 + 4).
- **resident** (everything else whose estimate fits the budget): grid
  (B*H, outer-block); the streamed side (K/V for fwd/dq, Q/dO for dkv)
  is held in VMEM at FULL length per head and the kernel loops over
  its blocks with a `lax.fori_loop` whose bounds come from
  `_k_span`/`_q_span` — for causal and windowed attention the trip
  count genuinely shrinks per program (causal visits the lower
  triangle only, ~half the blocks; windows visit O(window) blocks),
  and no fully-masked block is ever visited, in ALL of fwd, dq and
  dkv. The resident side is DMA'd once per head instead of once per
  outer block (the streaming grid re-fetches every K/V block nq
  times). The forward of every such call runs here; the dq + dkv pair
  of loops serves only calls that `_tiles` gives several blocks under
  512 rows and windows at rectangular tiles, since the one-kernel
  backwards below take the rest (PR 33, PR 37). A model's sliding
  layers run their forward here (T 8192, d 128, window 2047: 512 x
  512) and their backward on `_bwd_res_kernel`.
- **stream** (fallback past the VMEM budget — long T, big D): the
  round-5 grid (B*H, outer, inner) with VMEM-scratch-carried online
  state. Causal masking skips compute via `pl.when`; sliding windows
  narrow the inner grid dim itself (`_window_span`, affine
  front-padded index maps).

Window-less, the backward of a resident or streaming call is ONE
kernel (`_bwd_stream_kernel`, "stream_fused" in `flash_plan`) on the
grid (B*H, nk, nq) wherever a head's f32 dq fits the VMEM limit that
kernel states (`_bwd_stream_tiles`: T <= 16384 at d = 256 bf16, <=
32768 at d <= 128); else the dq + dkv pair of the call's scheme.
Under a window at square tiles it is ONE kernel too (PR 37:
`_bwd_res_kernel`, "resident_fused"): grid (B*H, nk), the head's Q and
dO held whole as the resident dkv holds them, a loop over the k-block's
`_q_span` q-blocks whose step feeds dk, dv and dq as the streaming one
does, under the same limit (T <= 8192 at d = 256 bf16, <= 16384 at d
<= 128); else the pair.

All run the same block steps (`_fwd_step`, `_dq_step`, `_dkv_step`).
Their matmuls take their operands in the input's dtype when that is
bf16 (`_operand_dtype`) and accumulate in f32; scores, max, exp, sums,
lse and delta are f32 whatever the input.

What the chip said of the loops (one TPU v5e, 2026-10-01, PR 25; B*H =
96, T = 1024, d = 64, bf16, causal, fwd + bwd of the isolated kernel,
`benchmarks/flash_eff.py`'s timing): one 1024 x 1024 block 2.06 ms,
resident loops at 512 x 512 1.93, 512 x 256 2.19, 256 x 256 2.49,
128 x 128 4.10 — each block step of a loop costs 0.5-1.2 us beside
its matmuls (online-softmax rescale, MXU fill and drain, the [block,
d] accumulate), so smaller tiles lose what the skipped blocks win;
masking only the diagonal's blocks in a second `fori_loop` made every
tiling 1-7% SLOWER. The head kernels: 1.28 ms at 256-row chunks (1.31
at 128, 1.36 at 512) with a dq + dkv pair, 1.21 with the one-kernel
backward. Mosaic's default-precision f32 `dot_general` already was
one bf16 pass on the MXU: bf16 operands changed neither the time
(2.06 -> 2.09) nor one bit of the result on bf16 inputs.

What the chip said of the streaming backward (one TPU v5e; B*H = 20,
T = 8192, d = 256, bf16, causal — the `glm-4.7-flash` cell's call —
fwd + bwd of the isolated call, the forward alone 6.16 ms at its 1024 x
512. Measured 2026-10-02 by the builder of PR 30, which the driver
refused for one incorrect run nobody has reproduced (PERF.md section 6,
PR 31); the kernel is that one, and every line marked * measured the
same to 0.03 ms again on 2026-10-03, PR 31): the dq + dkv pair 23.43*
ms — each kernel rebuilds s = q k^T and dp = dO v^T and takes exp of
the block, seven block matmuls a step where the mathematics has five.
ONE kernel on grid (B*H, nk, nq) in transposed score space, dq summed
into a whole head's f32 accumulator in VMEM by the transposed-lhs
contraction dsT^T @ k: 18.59* at 1024 x 512 tiles, 17.56* at 1024 x
1024, 18.56* at 512 x 512, 17.76 at 512 x 1024, 19.26 at 2048 x 512.
Naming, for the steps above the diagonal, the first q-block that
computes (so the pipeline fetches no q/dO block for a step that skips)
took 0.85-1.6 ms more off: 17.01* / **16.71*** / 17.11* / 17.00 /
17.82, and 17.68 at 2048 x 1024 — where `_narrowed_kv` records a max()
in an index map as 28% slower, on an older JAX: that did not repeat.
What lost or tied: delta computed in the kernel's first k-sweep instead
of by one XLA reduction, +0.3 ms (18.90, 17.87: O's blocks ride the
pipeline); dq accumulated transposed ([d, T], k^T cached once a
k-block, every matmul NN) in place of the transposed-lhs contraction,
+0.06 (18.66, 17.63: the contraction costs nothing here, so the
untransposed orientation with its two TN forms was not tried);
building the mask only on the blocks the diagonal crosses, -0.07
(16.66) for a second copy of the step: not kept. So the backward went
17.27 -> 10.55 ms, with the transposes round it. Elsewhere (pair ->
fused, 1024 x 1024): non-causal 35.40 -> 27.36*; f32 38.52 -> 24.01*;
T 4096 6.43 -> 4.76; T 16384, 8 heads 36.22 -> 26.44; d = 128: T
8192, 32 heads 22.36 -> 16.14, T 16384, 8 heads 22.68 -> 17.88*, T
32768, 4 heads 42.61 -> 33.74. On the chip the fused kernel repeats
itself to the bit (400 calls at the cell's shape, and five 60-step
training runs of the cell at one seed), gives the pair's dv to the
bit, and differs from the pair's dq in at most 0.52% and dk in 0.013%
of elements, by a bf16 rounding of ds (delta's summation order): an L2
distance of 1.1e-4 where either stands 2.0e-3 from flash in f32.

What the chip said where the pair was the RESIDENT one (one TPU v5e,
2026-10-04, PR 33; bf16 and causal unless said; fwd + bwd of the
isolated call, transposes included, forward at the call's own tiles;
pair -> the fused kernel at its 1024 x 1024). B*H 16, T 4096, d 128 —
the `ouro-2.6b` cell's call, forward alone 0.91 ms at 1024 x 512 on
the loops: 3.02 -> **2.37**, so the backward went 2.11 -> 1.46 ms;
other tiles of the fused kernel 2.44 (1024 x 512), 2.45 (512 x 512),
2.44 (512 x 1024), 2.57 (2048 x 1024 and 2048 x 2048), 3.80 (256 x
256): the square of 1024 stays the pick at T 4096 too, though four
of its ten computing steps there lie on the diagonal. T 2048, d 128:
32 heads 2.07 -> 1.64 (1.62 at 512 x 512); non-causal, 16 heads (what
`parallel/sequence.py`'s Ulysses heads send) 1.23 -> 0.95. T 4096, d
128, non-causal 4.37 -> 3.42. T 2048, d 64, f32, 48 heads 3.31 ->
2.73. T 2048, d 256, 20 heads (dq past the budget, dkv inside: a
mixed pair) 2.01 -> 1.53 (1.44 at 512 x 512). ONE block (auto tiles
at T <= 1024: non-causal, or causal outside the head kernels), fused
at the call's tile: T 1024 non-causal d 64, 96 heads 1.73 -> 1.54; d
128, 64 heads 1.51 -> 1.21; T 512 non-causal, 192 heads 1.15 -> 1.08;
causal T 256, 384 heads 1.63 -> 1.36; T 128, 768 heads 1.55 -> 1.37;
T 1000 (no power of two), 96 heads 2.06 -> 1.60. **What lost**: T
1152, d 64, 96 heads, which `_tiles` gives 128 x 128 blocks (a 9 x 9
grid a head): 5.06 -> 5.32, a grid step costing more than a trip of
the loops (at 384 x 384 the fused kernel reads 3.04, at one 1152
block 3.06: tiles nobody picks for it yet); such calls keep the pair
(`_bwd_stream_tiles`). Already fused before PR 33, their pairs being
past the budget and so on the streaming grid: d 64 at T 4096 x 24
heads (4.36 -> 3.40; 3.49 at 1024 x 512, 3.50 at 512 x 512) and T 8192
x 12 (7.13 -> 5.58), f32 d 128 at T 4096 (3.77 -> 2.89). At the
cell's call the fused kernel repeats itself to the bit (60 draws, q
scaled 0.5 to 8), gives the resident pair's dv to the bit, and stands
from its dq and dk by an L2 distance of at most 3.2e-5 and 3.7e-5
(0.52% and 0.009% of elements differ, by one bf16 rounding of ds),
where either stands 2.0e-3 from flash in f32.

Grouped K/V heads (PR 34): q is [B, T, H, D] and k, v are [B, T, H_kv,
D] with H_kv dividing H; query head h reads K/V head h // (H / H_kv).
Nothing repeats K/V in HBM: rows fold as b * H + h, so the K/V block
specs of every scheme name row i // group in their index maps
(`_kv_row`), and a resident kernel's full-length K/V block keeps its
index over a group's consecutive rows, so it is fetched once a group.
The backward kernels' grids run over QUERY heads and emit one dk and dv
each in the input's dtype; a K/V head's gradient is their sum over its
group, one f32 XLA reduction outside the kernel (`_unbh_kv`). A kernel
that summed a group itself on the fused backward's grid would hold
`group` heads' f32 dq in VMEM (32 MB of scratch and as much of output
block at the cell's call where one head's is 4); the mirror-image
kernel (dk, dv whole in VMEM, dq by blocks) would not, and is not
written. What the chip said (one TPU v5e, 2026-10-04, PR 34; B 1, T
8192, 32 query heads on 4, d 128, bf16, causal, fwd + bwd of the
isolated call with the transposes and the group sum): window-less 15.30
ms against 15.65 with K/V repeated to 32 heads first (forward 5.80 /
6.00); window 2047 at 512 x 512 10.88 / 11.12; outputs and all three
gradients equal to the bit either way.

Windows as a model calls them (PR 34; `window` counts the keys BEFORE
self: position q attends to keys [q - window, q], window + 1 of them.
A checkpoint whose config says `sliding_window: W` in the HF sense, W
keys counting self, passes `window = W - 1`). A windowed call never
takes the head kernels; until PR 37 its forward, dq and dkv ran on the
resident loops where they fit, else on the narrowed streaming grid,
seven block matmuls a step. Its auto tiles stay SQUARE: at T
8192, d 128, window 2047 the budget shrink used to give 1024 x 512,
which put dq and dkv past the loops' budget and on the streaming grid,
where the dkv narrows only at block_q == block_k and so walked all 128
(k-block, q-block) steps a head, 80 of them fetching 512 KB to compute
nothing: 13.79 ms (forward 3.76, dq 4.42, dkv 5.60) for 44% of the
pairs of a full-causal call that takes 15.30. Tiles tried, same call:
**512 x 512 10.88** (3.09 / 3.68 / 4.10; all three on the loops, 70 of
256 blocks), 1024 x 1024 12.01 (streaming, all narrowed, 24 of 64), 512
x 256 13.02, 1024 x 256 13.73, 256 x 256 16.09, 2048 x 512 17.55. For
scale, the full-causal call with the dq + dkv pair forced: 20.79 (dq
6.80, dkv 8.20) where the fused kernel's whole backward is 9.50: what
a fused backward for windows could be sized against.

What the chip said of a fused backward for windows (one TPU v5e,
2026-10-15, PR 37; the same call, 32 query heads on 4, fwd + bwd of
the isolated call, the forward 3.09 ms on the loops at 512 x 512 in
every line; each line measured twice, in opposite orders, to 0.005
ms): the dq + dkv pair 10.87 (backward 7.78). ONE kernel in the
resident-loop form that landed (grid (B*H, nk), Q/dO held whole, a
loop over `_q_span`): **512 x 512 8.68** (backward 5.58, the pair's
0.72; 70 loop trips a head), 1024 x 1024 9.14, 256 x 256 11.62. The
same step on the narrowed streaming grid, (B*H, nk, span) with the
q/dO index map naming q-block jk + kk clamped to nq - 1 (no padding
of Q and dO), steps past nq skipped: 8.85 at 512 x 512 (70 of 80
steps compute), 9.04 at 1024 x 1024 (21 of 24), 13.00 at 256 x 256;
so the loops, whose Q and dO cross HBM once a head, took it at every
tile but 1024. Both forms (each q-block sums its k-blocks in
ascending order in f32) read the distances below to every digit,
repeat themselves to the bit, give the pair's dv to the bit and differ
from its dq and dk in 0.008%
and 0.004% of elements, by one bf16 rounding of ds (delta's summation
order): an L2 distance of 1.0e-5 and 1.4e-5 where either stands 1.9e-3
and 2.5e-3 from the pair on f32 inputs. The window-less calls, timed
beside the parent's in the same call, did not move (glm 16.607 /
16.607, `ouro` 2.378 / 2.377, the full layers 15.301 / 15.296, d 64 at
T 4096 3.400 / 3.397): their kernels are the parent's to the jaxpr.

Auto block sizes are budget-driven: the head kernels' chunk where they
apply, else the largest power-of-two tile <= 1024 that keeps the worst
kernel's VMEM estimate under budget (big head dims shrink blocks
instead of failing to compile). The fused backward takes 1024 x 1024
where T divides, whatever the forward's tiles, else the call's; under
a window the call's (512 x 512 at the sliding call: the sweep above).

Backward overhead trims (round 6): in the dq + dkv pairs the delta
precompute (`rowsum(dO * O)`, FlashAttention-2 eq. 4) is folded into
the dq kernel's first pass — dq already streams dO, so the separate XLA
reduction and its extra full read of dO/O are gone; dq emits the
per-row delta for the dkv kernel to consume (the fused streaming
backward measured the other way round, above). Residuals stay at the
input dtype end to end (bf16 in, bf16 residuals; only the [B*H, T]
lse/delta row vectors are f32). The output and the lse, which only
the forward kernel can produce, carry `checkpoint_name`s (`FLASH_OUT`,
`FLASH_LSE`): a `jax.checkpoint` whose policy saves them runs no forward
kernel twice; without such a policy a name is an identity.

`flash_attention` falls back to the plain jnp implementation when
shapes don't tile (T % block != 0) or on backends without Mosaic
(interpret mode covers CPU tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)

# `checkpoint_name`s of the forward's output and lse as the backward's
# residuals (`_flash_fwd`), for a recomputing caller's policy to keep
FLASH_OUT = "kf.flash_out"
FLASH_LSE = "kf.flash_lse"

# Mosaic's scoped-vmem stack limit is 16 MB; 15 MB leaves scheduling
# headroom (same calibration rationale as ops/fused_ce.py). The
# estimates below are tuned so that 1024x1024 blocks at d=64 (what
# T > 2048 and non-causal calls run) still fit — the budget bites only
# where the real limit would (large T residency, large head dims).
_VMEM_BUDGET = 15 * 1024 * 1024

# the tests' hook: they monkeypatch this to "stream" or "resident" to
# put a scheme the budget decision would not pick under test (None =
# auto). Read at trace time; nothing but a test sets it.
_FORCE_SCHEME = None


def _operand_dtype(*dtypes):
    """The dtype the block matmuls feed the MXU: bf16 when every input
    is bf16 (its native operand type; `preferred_element_type` keeps
    the accumulation f32), f32 otherwise — so f32 callers, and any
    mixed or exotic input, run the contraction they always ran. Shared
    with `flash_plan`, which reports it."""
    bf16 = jnp.dtype(jnp.bfloat16)
    return (jnp.bfloat16 if all(jnp.dtype(d) == bf16 for d in dtypes)
            else jnp.float32)


def _mxu(a, b, contract, op):
    """One block matmul: operands in `op`, f32 accumulation."""
    return lax.dot_general(a.astype(op), b.astype(op),
                           (contract, ((), ())),
                           preferred_element_type=jnp.float32)


_NT = ((1,), (1,))   # a @ b.T
_NN = ((1,), (0,))   # a @ b
_TN = ((0,), (0,))   # a.T @ b


def _scores(q_blk, k_blk, iq, jk, *, scale, causal, block_q, block_k,
            window=None, transpose=False, op=jnp.float32):
    """Scaled (and causal/window-masked) f32 score block — shared by
    the forward and both backward kernels so the masking and scaling
    semantics cannot drift apart. q and k enter the MXU in `op`
    (`_operand_dtype`); `scale` multiplies the f32 scores AFTER the
    dot, so a head size whose scale is no power of two rounds nothing
    in a bf16 operand.

    `transpose=False`: [block_q, block_k] (q on sublanes) — the
    forward and dq-kernel layout (dq caches the per-q lse/delta
    columns in VMEM scratch once per q-block). `transpose=True`:
    [block_k, block_q] (q on LANES) — the dkv kernel works in this
    transposed score space so the compactly-stored lane-major
    lse/delta rows (see `_flash_bwd_impl`) broadcast against scores
    with no lane<->sublane relayout, and its two accumulations become
    Mosaic-native NN contractions (the untransposed dkv pays two TN
    forms). Measured on v5e at T=16k (round 5): this split is the
    fastest of the four layout/orientation combinations tried (see git
    history of this file), 7% faster end-to-end fwd+bwd than the
    round-3 [B*H, T, 128] lane-broadcast scheme it replaces. The head
    kernel and the fused streaming backward run wholly in the
    transposed space and take dq from the same dsT by a transposed-lhs
    contraction: on this JAX it measured level with an NN form against
    a cached k^T (module docstring, PR 31), where a fully transposed
    dq kernel once lost 36% (`_bwd_dq_kernel`).

    `window` (sliding-window attention, causal only): position q
    attends to keys [q - window, q]. Self is always visible, so no row
    is ever fully masked. `causal=False, window=None` builds no mask
    at all: the head kernels call it so for the part of a causal head
    that lies wholly under the diagonal.
    """
    if transpose:
        shape = (block_k, block_q)
        q_dim, k_dim = 1, 0
        s = _mxu(k_blk, q_blk, _NT, op) * scale
    else:
        shape = (block_q, block_k)
        q_dim, k_dim = 0, 1
        s = _mxu(q_blk, k_blk, _NT, op) * scale
    if causal or window is not None:
        # q_pos - k_pos = diff - off: `diff` is the same for every
        # block of a loop, only the scalar `off` moves with (iq, jk)
        diff = (lax.broadcasted_iota(jnp.int32, shape, q_dim)
                - lax.broadcasted_iota(jnp.int32, shape, k_dim))
        off = jk * block_k - iq * block_q
        keep = diff >= off
        if window is not None:
            keep &= diff <= off + window
        s = jnp.where(keep, s, NEG_INF)
    return s


def _fwd_step(q_blk, k_blk, v_blk, iq, jk, acc, m, l, *, scale, causal,
              block_q, block_k, window=None):
    """One K/V block's online-softmax update — the SINGLE definition of
    the forward recurrence, shared by the resident kernel (fori carry)
    and the streaming kernel (VMEM-scratch state) so the two schemes
    cannot drift numerically (the `_scores` discipline, applied to the
    whole block update). State shapes: acc [bq, d] f32, m/l [bq] f32.
    Only the two matmuls' operands take `_operand_dtype`: the scores,
    max, exp and denominator are f32 whatever the input.
    Returns the updated (acc, m, l)."""
    op = _operand_dtype(q_blk.dtype, k_blk.dtype, v_blk.dtype)
    s = _scores(q_blk, k_blk, iq, jk, scale=scale, causal=causal,
                block_q=block_q, block_k=block_k, window=window, op=op)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[:, None])
    l = l * alpha + jnp.sum(p, axis=-1)
    acc = acc * alpha[:, None] + _mxu(p, v_blk, _NN, op)
    return acc, m_new, l


def _fwd_init(block_q, d):
    """The online-softmax state before the first block: (acc, m, l)."""
    return (jnp.zeros((block_q, d), jnp.float32),
            jnp.full((block_q,), NEG_INF, jnp.float32),
            jnp.zeros((block_q,), jnp.float32))


def _fwd_finish(acc, m, l, dtype, save_lse):
    """(o_block, lse_row | None) from the final online-softmax state —
    l == 0 (a fully-masked row, only reachable on the streaming grid's
    padded steps) divides by 1 instead."""
    l = jnp.where(l == 0.0, 1.0, l)
    o = (acc / l[:, None]).astype(dtype)
    return o, ((m + jnp.log(l)) if save_lse else None)


def _dq_step(q_blk, k_blk, v_blk, do, lse_col, delta_col, iq, jk, *,
             scale, causal, block_q, block_k, window=None):
    """One K/V block's dq contribution (FlashAttention-2: p rebuilt
    from lse; ds = p * (dp - delta); returns ds @ k, which the caller
    multiplies by `scale` once, after its last block) — shared by both
    backward-dq schemes. q, k, v, do and ds are MXU operands
    (`_operand_dtype`); p, dp, ds themselves are f32."""
    op = _operand_dtype(q_blk.dtype, k_blk.dtype, v_blk.dtype, do.dtype)
    s = _scores(q_blk, k_blk, iq, jk, scale=scale, causal=causal,
                block_q=block_q, block_k=block_k, window=window, op=op)
    p = jnp.exp(s - lse_col)
    dp = _mxu(do, v_blk, _NT, op)
    ds = p * (dp - delta_col)
    return _mxu(ds, k_blk, _NN, op)


def _dkv_step(q_blk, k_blk, v_blk, do, lse_row, delta_row, iq, jk, *,
              scale, causal, block_q, block_k, window=None):
    """One Q/dO block's (dk, dv) contribution in TRANSPOSED score
    space (q on lanes — see `_scores`): dv = pT @ do, dk = dsT @ q
    (the caller multiplies dk by `scale` once, after its last block) —
    shared by every backward scheme. Also returns dsT as the MXU
    operand it was cast to, for the head kernel, which derives dq from
    the same pass."""
    op = _operand_dtype(q_blk.dtype, k_blk.dtype, v_blk.dtype, do.dtype)
    s_t = _scores(q_blk, k_blk, iq, jk, scale=scale, causal=causal,
                  block_q=block_q, block_k=block_k, window=window,
                  transpose=True, op=op)              # [bk, bq]
    p_t = jnp.exp(s_t - lse_row)
    dv = _mxu(p_t, do, _NN, op)                       # p^T @ do
    dp_t = _mxu(v_blk, do, _NT, op)                   # (do @ v^T)^T
    ds_t = (p_t * (dp_t - delta_row)).astype(op)
    dk = _mxu(ds_t, q_blk, _NN, op)                   # ds^T @ q
    return dk, dv, ds_t


def _diag_ok(iq, jk, causal, block_q, block_k, window=None):
    """False for blocks with no visible entries: causal K blocks
    entirely above the diagonal, and (with a sliding window) K blocks
    entirely below the window — those are SKIPPED, which is what makes
    windowed attention O(T * window) compute instead of O(T^2)."""
    ok = (jk * block_k <= (iq + 1) * block_q - 1) if causal else True
    if window is not None:
        # newest key of this block still within the OLDEST query's reach
        win_ok = jk * block_k + block_k - 1 >= iq * block_q - window
        ok = win_ok if ok is True else jnp.logical_and(ok, win_ok)
    return ok


def _span_step(iq, kk, *, span, causal, block_q, block_k, window):
    """Streaming-scheme inner-step gate, shared by `_kernel` and
    `_bwd_dq_kernel` (the single definition of which narrowed steps
    are real, so forward and dq cannot diverge on the visible set):
    recovers the real k-block index from the window-relative grid
    index over the front-padded K/V — affine, `jk = iq*m + kk -
    (span - m)`; a max() in the index map instead was measured to
    defeat Mosaic's DMA prefetch pipelining (~28% slower) — and
    returns (jk, ok) where ok is False for steps with no visible
    entries (above the causal diagonal, past the window, or in the
    jk < 0 pad)."""
    if span is None:
        jk = kk
    else:
        m_ratio = block_q // block_k
        jk = iq * m_ratio + kk - (span - m_ratio)
    ok = _diag_ok(iq, jk, causal, block_q, block_k, window)
    if span is not None:
        ok = jnp.logical_and(jk >= 0, ok)
    return jk, ok


def _window_span(window, block_q, block_k, n_blocks):
    """K blocks a q-block can see under a causal sliding window, in
    k-block units, for block_q = m * block_k (the causal tiling
    invariant): first visible k-block of q-block i is
    i*m - ceil(window/block_k) and the last is i*m + m - 1, both
    AFFINE in i, so span = m + ceil(window/block_k) and the padded
    index map stays affine (see _flash_fwd_impl). m > 1 trades masked
    score area inside the band for fewer per-q-block prologues;
    measured at T=16k/window=512 the masked area wins (m=2 forward
    1.445 ms vs m=1's 0.969) so auto never picks m > 1 — the
    generality exists for window/block mixes where the trade flips.
    None = no narrowing (window absent, or it would not shrink the
    grid)."""
    if window is None:
        return None
    m = block_q // block_k
    span = m + (window + block_k - 1) // block_k
    return span if span < n_blocks else None


# ---------------------------------------------------------------------------
# block-skip loop bounds (resident scheme)
#
# Shared by the resident kernels AND the structural trip-count tests
# (`tests/test_flash_skip.py`): the fori_loop trip count of every
# program IS `hi - lo`, so pinning these functions pins the work-skip
# behaviour of all five loop nests (fwd/dq over k-blocks, dkv over
# q-blocks, causal and windowed).
# ---------------------------------------------------------------------------


def _k_span(iq, nk, *, causal, window, block_q, block_k):
    """Half-open range [lo, hi) of k-blocks with >= 1 visible entry for
    q-block `iq` — the fwd/dq resident loop bounds. Works on python
    ints (tests, planning) and traced values (inside kernels) alike.
    Causal: hi stops at the diagonal block (~halves the total visited
    blocks); a sliding window additionally lifts lo to the oldest
    in-window block, making the visit count O(window / block_k)."""
    if not causal:
        return 0, nk
    hi = jnp.minimum(((iq + 1) * block_q - 1) // block_k + 1, nk)
    if window is None:
        return 0, hi
    lo = jnp.maximum((iq * block_q - window) // block_k, 0)
    return lo, hi


def _q_span(jk, nq, *, causal, window, block_q, block_k):
    """Half-open range [lo, hi) of q-blocks that can see k-block `jk` —
    the dkv resident loop bounds (mirror image of `_k_span`). Causal:
    lo starts at the diagonal block; a window caps hi at the newest
    q-block still within `window` of this block's NEWEST key
    (jk*block_k + block_k - 1) — the newest key reaches furthest, so
    it defines the last visible q-block."""
    if not causal:
        return 0, nq
    lo = (jk * block_k) // block_q
    if window is None:
        return lo, nq
    hi = jnp.minimum((jk * block_k + block_k - 1 + window) // block_q + 1,
                     nq)
    return lo, hi


# ---------------------------------------------------------------------------
# VMEM-budget estimates (style of ops/fused_ce.py:_pick_blocks)
#
# Per-kernel resident-VMEM models: double-buffered pipeline blocks +
# f32 accumulator state + the [bq, bk] f32 score/probability
# temporaries (2 for the forward's s/p, 3 for the backwards' s/p +
# dp/ds). `t` terms are the full-length arrays the resident scheme
# holds per head; the budget is what flips a shape back to streaming.
# ---------------------------------------------------------------------------


def _fwd_stream_vmem(bq, bk, d, isz):
    inputs = 2 * (bq * d * isz + 2 * bk * d * isz)
    outputs = 2 * (bq * d * isz + bq * 4)
    scratch = bq * d * 4 + 2 * bq * 4
    return inputs + outputs + scratch + 2 * bq * bk * 4


def _dq_stream_vmem(bq, bk, d, isz):
    inputs = 2 * (3 * bq * d * isz + 2 * bk * d * isz + 2 * bq * 4)
    outputs = 2 * (bq * d * isz + bq * 4)
    scratch = bq * d * 4 + 2 * bq * 4
    return inputs + outputs + scratch + 3 * bq * bk * 4


def _dkv_stream_vmem(bq, bk, d, isz, t):
    inputs = 2 * (2 * bk * d * isz + 2 * bq * d * isz + 2 * t * 4)
    outputs = 2 * (2 * bk * d * isz)
    scratch = 2 * bk * d * 4
    return inputs + outputs + scratch + 3 * bq * bk * 4


# The fused streaming backward holds a head's dq at full length (an f32
# accumulator in scratch and its output block), so it states its own
# scoped-VMEM limit, as ops/fused_ce.py does, in place of Mosaic's
# 16 MB default (the v5e has 128 MiB). Mosaic took every shape tried
# whose estimate is under it (the largest 63.0 MiB: T 32768, d 128,
# f32); a call whose estimate passes it runs the dq + dkv pair.
_BWD_STREAM_VMEM_LIMIT = 64 * 1024 * 1024
# its auto tile, by the sweep in the module docstring: 1024 x 1024
_BWD_STREAM_BLOCK = 1024


def _bwd_stream_vmem(bq, bk, d, isz, t):
    d = -(-d // 128) * 128   # a [rows, d] buffer fills whole lane tiles
    inputs = 2 * (2 * bk * d * isz + 2 * bq * d * isz + 2 * t * 4)
    outputs = 2 * (2 * bk * d * isz + t * d * isz)
    scratch = 2 * bk * d * 4 + t * d * 4
    # s/p, dp and ds in f32, and the MXU-operand casts of p and ds
    return inputs + outputs + scratch + bq * bk * (3 * 4 + 2 * isz)


def _bwd_res_vmem(bq, bk, d, isz, t):
    """`_bwd_res_kernel`'s: Q and dO whole as the resident dkv holds
    them, dq whole as `_bwd_stream_vmem` counts it, dk and dv a carry."""
    d = -(-d // 128) * 128
    inputs = 2 * (2 * bk * d * isz + 2 * t * d * isz + 2 * t * 4)
    outputs = 2 * (2 * bk * d * isz + t * d * isz)
    carry = 2 * bk * d * 4
    return inputs + outputs + carry + t * d * 4 + bq * bk * (3 * 4 + 2 * isz)


def _bwd_fused_vmem(window):
    """The estimate of the one-kernel backward a call past the head
    kernels runs: `_bwd_stream_kernel` window-less, `_bwd_res_kernel`
    under a window. Both hold to `_BWD_STREAM_VMEM_LIMIT`."""
    return _bwd_stream_vmem if window is None else _bwd_res_vmem


# the "bwd" schemes of `flash_plan` that run ONE backward kernel
_ONE_KERNEL_BWD = ("head", "stream_fused", "resident_fused")


def _fwd_res_vmem(bq, bk, d, isz, t):
    inputs = 2 * (bq * d * isz + 2 * t * d * isz)
    outputs = 2 * (bq * d * isz + bq * 4)
    carry = bq * d * 4 + 2 * bq * 4
    return inputs + outputs + carry + 2 * bq * bk * 4


def _dq_res_vmem(bq, bk, d, isz, t):
    inputs = 2 * (3 * bq * d * isz + 2 * t * d * isz + bq * 4)
    outputs = 2 * (bq * d * isz + bq * 4)
    carry = bq * d * 4
    return inputs + outputs + carry + 3 * bq * bk * 4


def _dkv_res_vmem(bq, bk, d, isz, t):
    inputs = 2 * (2 * bk * d * isz + 2 * t * d * isz + 2 * t * 4)
    outputs = 2 * (2 * bk * d * isz)
    carry = 2 * bk * d * 4
    return inputs + outputs + carry + 3 * bq * bk * 4


_RES_VMEM = {"fwd": _fwd_res_vmem, "dq": _dq_res_vmem,
             "dkv": _dkv_res_vmem}

# full-length [t, d] arrays a head kernel's pipeline holds, in and out
# (the backward is one kernel: q, k, v, dO, O in, dq, dk, dv out)
_HEAD_ARRAYS = {"fwd": 4, "bwd": 8}

# the head kernels unroll 2 * nb - 1 block steps at trace time; past
# this many chunks the dynamic loops keep the program small
_HEAD_MAX_CHUNKS = 8
# rows a chunk: the auto pick for causal T <= 2048, by the PR 25 sweep
# on a v5e (d = 64, fwd + bwd, the backward still a dq + dkv pair):
# 256 beat 128 and 512 at T = 1024 (bf16 1.278 / 1.311 / 1.362 ms,
# f32 1.653 at 256 / 1.720 at 512) and at T = 2048 (1.880 at 256,
# 2.380 at 512); with the one-kernel backward 256 and 512 tie at
# T = 1024 (1.207 / 1.194)
_HEAD_CHUNK = 256


def _head_vmem(which, block, d, isz, t):
    """`which`: "fwd", or "bwd" for the one backward kernel."""
    arrays = 2 * (_HEAD_ARRAYS[which] * t * d * isz + t * 4)
    # the widest step's [block, t - block] f32 score temporaries, as
    # the other schemes count theirs, plus the MXU-operand casts
    n = 2 if which == "fwd" else 3
    wide = block * (t - block) * (n * 4 + (n - 1) * isz)
    scratch = 0 if which == "fwd" else t * d * 4 + t * 4
    return arrays + wide + scratch


def _head_tiles(t, d, isz):
    """Auto tiles of a causal, window-less call that the head kernels
    can take whole — `_HEAD_CHUNK`-row chunks, at most
    `_HEAD_MAX_CHUNKS` of them, every kernel inside the VMEM budget
    (`d` None: not known yet, assume it fits) — else None."""
    c = _HEAD_CHUNK
    if t % c or not c < t <= c * _HEAD_MAX_CHUNKS:
        return None
    if d is not None and any(_head_vmem(which, c, d, isz, t) > _VMEM_BUDGET
                             for which in _HEAD_ARRAYS):
        return None
    return c, c


def _choose_scheme(which, t, d, isz, bq, bk, causal=False, window=None):
    """'head' for a causal head that unrolls into a few chunks (square
    tiles, no window) and fits the VMEM budget whole: the statically
    scheduled kernels do the least work a block step. Else 'resident'
    when the full-length-per-head scheme fits (it both skips masked
    blocks AND fetches the streamed side once per head), else
    'stream'. `_FORCE_SCHEME` overrides for tests."""
    if _FORCE_SCHEME in ("stream", "resident"):
        return _FORCE_SCHEME
    if (causal and window is None and bq == bk
            and 1 < t // bq <= _HEAD_MAX_CHUNKS
            and _kernel_vmem(which, "head", bq, bk, d, isz, t)
            <= _VMEM_BUDGET):
        return "head"
    est = _kernel_vmem(which, "resident", bq, bk, d, isz, t)
    return "resident" if est <= _VMEM_BUDGET else "stream"


def _kernel_vmem(which, scheme, bq, bk, d, isz, t):
    """The VMEM estimate of kernel `which` ("fwd", "dq", "dkv") under
    `scheme` — what `_choose_scheme` and `_tiles` hold to the budget."""
    if scheme == "head":
        return _head_vmem("fwd" if which == "fwd" else "bwd", bq, d, isz, t)
    if scheme == "resident":
        return _RES_VMEM[which](bq, bk, d, isz, t)
    if which == "dkv":
        return _dkv_stream_vmem(bq, bk, d, isz, t)
    return {"fwd": _fwd_stream_vmem,
            "dq": _dq_stream_vmem}[which](bq, bk, d, isz)


def _bwd_stream_tiles(t, d, isz, bq, bk, causal, window, auto):
    """(block_q, block_k) of the fused backward where a call takes it,
    else None: `_bwd_stream_kernel` window-less, `_bwd_res_kernel` under
    a window (PR 37), wherever a pair on the loops or on the streaming
    grid would run (the head scheme has its own one-kernel backward), a
    window's tiles are square (its auto tiles are; a k-block's q-blocks
    are then `_q_span`'s whole ones) and a head's dq fits the limit the
    kernels state. Window-less its tiles are its own:
    `_BWD_STREAM_BLOCK` square where the caller left them to `_tiles`
    and T divides, else the call's; a window keeps the call's (the
    sweep in the module docstring). A function of (t, d, dtype, causal,
    window) and the tiles alone; `flash_plan` shows it under "bwd"."""
    pair = {_choose_scheme(which, t, d, isz, bq, bk, causal, window)
            for which in ("dq", "dkv")}
    if "head" in pair or (window is not None and bq != bk):
        return None
    tiles = [(bq, bk)]
    if auto and window is None and t % _BWD_STREAM_BLOCK == 0:
        tiles.insert(0, (_BWD_STREAM_BLOCK, _BWD_STREAM_BLOCK))
    vmem = _bwd_fused_vmem(window)
    tile = next((tile for tile in tiles if vmem(*tile, d, isz, t)
                 <= _BWD_STREAM_VMEM_LIMIT), None)
    if tile and auto and "resident" in pair and tile[1] < 512 and t > tile[1]:
        # a T over 1024 that 512 does not divide gets 128- or 256-row
        # blocks from `_tiles`: a grid step of the fused kernel then
        # costs more than a trip of the resident loops (T 1152, d 64, 96
        # heads, 128 x 128: the pair 5.06 ms, fused 5.32; 256 rows were
        # not measured), so such a call keeps the loops. ONE block of
        # any size gains (module docstring).
        return None
    return tile


def _dim_semantics(n):
    """Pipelining hint: every grid dim is embarrassingly parallel
    except a streaming kernel's innermost (scratch-carried online
    state ⇒ sequential)."""
    sem = ("parallel",) * n if n <= 2 else (
        ("parallel",) * (n - 1) + ("arbitrary",))
    return pltpu.CompilerParams(dimension_semantics=sem)


# ---------------------------------------------------------------------------
# streaming kernels (grid (B*H, outer, inner), VMEM-scratch state)
# ---------------------------------------------------------------------------


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
            scale, causal, block_q, block_k, window=None, span=None):
    """Grid (B*H, nq, nk), nk innermost: the VMEM scratch (accumulator +
    running max/denominator) carries the online-softmax state across the
    sequential K-block steps; K/V blocks stream through VMEM one at a
    time, so resident VMEM stays O(block) regardless of T.

    `span` (sliding window): the grid's inner dim is narrowed to the
    `span` K blocks a q-block can actually see, and the K/V index maps
    shift by the q-block (see _flash_fwd_impl) — out-of-window K/V
    blocks never even stream their DMA. The kernel recovers the REAL
    k-block index from the window-relative grid index here."""
    iq = pl.program_id(1)
    kk = pl.program_id(2)            # window-relative when narrowed
    nk = pl.num_programs(2)
    jk, ok = _span_step(iq, kk, span=span, causal=causal,
                        block_q=block_q, block_k=block_k, window=window)

    @pl.when(kk == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(ok)
    def _():
        acc, m, l = _fwd_step(
            q_ref[0], k_ref[0], v_ref[0], iq, jk, acc_ref[:],
            m_ref[:, 0], l_ref[:, 0], scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, window=window)
        acc_ref[:] = acc
        m_ref[:, 0] = m
        l_ref[:, 0] = l

    @pl.when(kk == nk - 1)
    def _():
        o, lse = _fwd_finish(acc_ref[:], m_ref[:, 0], l_ref[:, 0],
                             o_ref.dtype, lse_ref is not None)
        o_ref[0] = o
        if lse_ref is not None:
            # per-row logsumexp of the scaled scores — the backward
            # kernels reconstruct p = exp(s - lse) from it instead of
            # saving [T, T]. Stored lane-major at true [B*H, T] size;
            # the one sublane->lane relayout here runs once per
            # q-block, not per inner step. Skipped entirely on the
            # no-grad forward (save_lse=False).
            lse_ref[0, 0] = lse.reshape(1, block_q)


def _kernel_nolse(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale, causal, block_q, block_k, window=None,
                  span=None):
    _kernel(q_ref, k_ref, v_ref, o_ref, None, acc_ref, m_ref, l_ref,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            window=window, span=span)


# ---------------------------------------------------------------------------
# resident kernels (grid (B*H, outer), dynamic-trip-count inner fori)
# ---------------------------------------------------------------------------


def _fwd_res_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale,
                    causal, block_q, block_k, window=None, nk=None):
    """Grid (B*H, nq): K/V live in VMEM at full length per head (one
    O(T)-per-head DMA, vs the streaming grid re-fetching each K/V
    block nq times); the online-softmax state is a fori_loop carry (no
    cross-step scratch), and the loop runs ONLY over `_k_span`'s
    visible k-blocks — causal programs stop at the diagonal, windowed
    programs start at the window edge, so fully-masked blocks spend no
    compute (their bytes still ride the full-length fetch)."""
    iq = pl.program_id(1)
    q_blk = q_ref[0]
    d = q_blk.shape[-1]
    lo, hi = _k_span(iq, nk, causal=causal, window=window,
                     block_q=block_q, block_k=block_k)

    def body(jk, carry):
        off = pl.multiple_of(jk * block_k, block_k)
        return _fwd_step(
            q_blk, k_ref[0, pl.ds(off, block_k), :],
            v_ref[0, pl.ds(off, block_k), :], iq, jk, *carry,
            scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, window=window)

    acc, m, l = lax.fori_loop(lo, hi, body, _fwd_init(block_q, d))
    o, lse = _fwd_finish(acc, m, l, o_ref.dtype, lse_ref is not None)
    o_ref[0] = o
    if lse_ref is not None:
        lse_ref[0, 0] = lse.reshape(1, block_q)


def _fwd_res_kernel_nolse(q_ref, k_ref, v_ref, o_ref, *, scale, causal,
                          block_q, block_k, window=None, nk=None):
    _fwd_res_kernel(q_ref, k_ref, v_ref, o_ref, None, scale=scale,
                    causal=causal, block_q=block_q, block_k=block_k,
                    window=window, nk=nk)


def _dq_res_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref,
                   delta_ref, *, scale, causal, block_q, block_k,
                   window=None, nk=None):
    """Grid (B*H, nq): dq for one Q block against VMEM-resident K/V,
    visiting only `_k_span`'s visible k-blocks. The delta precompute
    (rowsum(dO * O), FlashAttention-2 eq. 4) is folded into this
    kernel's prologue — dO and O are already here as q-blocks, so the
    standalone XLA reduction (and its extra HBM pass over both) is
    gone; the lane-major delta row is emitted for the dkv kernel."""
    iq = pl.program_id(1)
    q_blk = q_ref[0]
    d = q_blk.shape[-1]
    do = do_ref[0]
    delta_col = jnp.sum(
        do.astype(jnp.float32) * o_ref[0].astype(jnp.float32), axis=-1,
        keepdims=True)                                    # [bq, 1]
    delta_ref[0, 0] = delta_col.reshape(1, block_q)
    lse_col = lse_ref[0, 0].reshape(block_q, 1)
    lo, hi = _k_span(iq, nk, causal=causal, window=window,
                     block_q=block_q, block_k=block_k)

    def body(jk, acc):
        off = pl.multiple_of(jk * block_k, block_k)
        return acc + _dq_step(
            q_blk, k_ref[0, pl.ds(off, block_k), :],
            v_ref[0, pl.ds(off, block_k), :], do, lse_col, delta_col,
            iq, jk, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, window=window)

    acc = lax.fori_loop(lo, hi, body,
                        jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = (acc * scale).astype(dq_ref.dtype)


def _dkv_res_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, causal, block_q, block_k,
                    window=None, nq=None):
    """Grid (B*H, nk): dk/dv for one K/V block against VMEM-resident
    Q/dO, in TRANSPOSED score space (q on lanes — see `_scores`),
    visiting only `_q_span`'s visible q-blocks: causal programs start
    at the diagonal, windowed programs stop at the window edge.
    lse/delta arrive as the head's full lane-major row set, DMA'd once
    per head; the per-q-block row is a cheap non-tiled-dim select."""
    jk = pl.program_id(1)
    k_blk = k_ref[0]
    d = k_blk.shape[-1]
    lo, hi = _q_span(jk, nq, causal=causal, window=window,
                     block_q=block_q, block_k=block_k)

    def body(iq, carry):
        dk_acc, dv_acc = carry
        off = pl.multiple_of(iq * block_q, block_q)
        dk, dv, _ = _dkv_step(
            q_ref[0, pl.ds(off, block_q), :], k_blk, v_ref[0],
            do_ref[0, pl.ds(off, block_q), :],
            lse_ref[0, iq, 0, :][None, :],    # [1, bq] lane rows
            delta_ref[0, iq, 0, :][None, :],
            iq, jk, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, window=window)
        return dk_acc + dk, dv_acc + dv

    dk_acc, dv_acc = lax.fori_loop(lo, hi, body, (
        jnp.zeros((block_k, d), jnp.float32),
        jnp.zeros((block_k, d), jnp.float32)))
    dk_ref[0] = (dk_acc * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_acc.astype(dv_ref.dtype)


def _bwd_res_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                    dq_ref, dk_ref, dv_ref, dq_acc, *, scale, causal,
                    block_q, block_k, window=None, nq=None):
    """Grid (B*H, nk): dq, dk and dv of one head from ONE pass over its
    visible score blocks — the fused backward of a windowed call (PR
    37). `_dkv_res_kernel`'s loop (Q/dO held at full length per head,
    a fori over `_q_span`'s q-blocks, dk/dv its carry) whose step's
    dsT also gives the q-block's dq, dsT^T @ k, summed into a whole
    head's f32 accumulator as `_bwd_stream_kernel` sums it: zeroed at
    the head's first k-block, scaled and written at its last, so the
    k-block dim is "arbitrary". Five block matmuls and one exp pass a
    step where the pair runs seven and two; each q-block still sums its
    k-blocks in ascending order in f32."""
    jk = pl.program_id(1)

    @pl.when(jk == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    k_blk = k_ref[0]
    v_blk = v_ref[0]
    d = k_blk.shape[-1]
    lo, hi = _q_span(jk, nq, causal=causal, window=window,
                     block_q=block_q, block_k=block_k)

    def body(iq, carry):
        dk_acc, dv_acc = carry
        rows = pl.ds(pl.multiple_of(iq * block_q, block_q), block_q)
        dk, dv, ds_t = _dkv_step(
            q_ref[0, rows, :], k_blk, v_blk, do_ref[0, rows, :],
            lse_ref[0, iq, 0, :][None, :],            # [1, bq] lanes
            delta_ref[0, iq, 0, :][None, :],
            iq, jk, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, window=window)
        dq_acc[rows, :] += _mxu(ds_t, k_blk, _TN, ds_t.dtype)
        return dk_acc + dk, dv_acc + dv

    dk, dv = lax.fori_loop(lo, hi, body, (
        jnp.zeros((block_k, d), jnp.float32),
        jnp.zeros((block_k, d), jnp.float32)))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(jk == pl.num_programs(1) - 1)
    def _():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# head kernels (grid (B*H,), the causal schedule unrolled at trace time)
#
# One program holds a whole head — q, k, v (and dO, O) at full length in
# VMEM — and walks it in chunks of `block` rows with PYTHON loops, so
# every extent is static: a chunk's visible part under the diagonal is
# ONE wide unmasked block step ([block, lo] scores, whatever lo is), and
# only the [block, block] square the diagonal crosses builds the mask.
# nb chunks cost 2*nb - 1 block steps a kernel where the resident loops
# take nb*(nb+1)/2 — the per-step cost (online-softmax rescale, MXU
# fill and drain, a [block, d] accumulate) is what kept smaller tiles
# from paying on the chip (PERF.md §6, PR 25) — and the steps are the
# `_fwd_step` / `_dkv_step` every scheme shares.
# ---------------------------------------------------------------------------


def _fwd_head_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale,
                     block):
    """o (and lse) of one head: a chunk of queries meets all earlier
    keys, [0, lo), in one wide unmasked step, then its own under the
    mask."""
    t, d = q_ref.shape[1:]
    for lo in range(0, t, block):
        rows = slice(lo, lo + block)
        q_blk = q_ref[0, rows, :]
        step = functools.partial(_fwd_step, q_blk, scale=scale,
                                 block_q=block)
        carry = _fwd_init(block, d)
        if lo:   # keys [0, lo): every pair visible
            carry = step(k_ref[0, :lo, :], v_ref[0, :lo, :], 0, 0,
                         *carry, causal=False, block_k=lo)
        acc, m, l = step(k_ref[0, rows, :], v_ref[0, rows, :], 0, 0,
                         *carry, causal=True, block_k=block)
        o, lse = _fwd_finish(acc, m, l, o_ref.dtype, lse_ref is not None)
        o_ref[0, rows, :] = o
        if lse_ref is not None:
            lse_ref[0, 0, :, rows] = lse.reshape(1, block)


def _fwd_head_kernel_nolse(q_ref, k_ref, v_ref, o_ref, *, scale, block):
    _fwd_head_kernel(q_ref, k_ref, v_ref, o_ref, None, scale=scale,
                     block=block)


def _bwd_head_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                     dq_ref, dk_ref, dv_ref, dq_acc, delta_row, *,
                     scale, block):
    """dq, dk and dv of one head from ONE pass over its visible
    scores, in transposed score space (see `_scores`): a chunk of keys
    meets its own queries under the mask and all later queries,
    [hi, t), in one wide unmasked step. With queries and keys both
    here, dsT serves dk (dsT @ q) and dq (ds @ k, a transposed-lhs
    contraction) alike, so the backward is five matmuls a step where
    the dq + dkv kernel pair of the other schemes, each rebuilding p
    and dp, runs seven (fwd + bwd 1.28 -> 1.21 ms at T 1024, 1.88 ->
    1.66 at 2048: PERF.md §6, PR 25). delta (rowsum(dO * O), FlashAttention-2
    eq. 4) is a lane-major row in scratch, dq an f32 accumulator."""
    t = q_ref.shape[1]
    for lo in range(0, t, block):
        rows = slice(lo, lo + block)
        delta_row[:, rows] = jnp.sum(
            do_ref[0, rows, :].astype(jnp.float32)
            * o_ref[0, rows, :].astype(jnp.float32),
            axis=-1, keepdims=True).reshape(1, block)
    dq_acc[...] = jnp.zeros_like(dq_acc)
    for lo in range(0, t, block):
        hi = lo + block
        k_blk = k_ref[0, lo:hi, :]
        step = functools.partial(_dkv_step, k_blk=k_blk, iq=0, jk=0,
                                 v_blk=v_ref[0, lo:hi, :], scale=scale,
                                 block_k=block)
        dk = dv = 0.0
        for rows, causal in ((slice(lo, hi), True), (slice(hi, t), False)):
            if rows.start == rows.stop:
                continue
            dk_r, dv_r, ds_t = step(
                q_blk=q_ref[0, rows, :], do=do_ref[0, rows, :],
                lse_row=lse_ref[0, 0, :, rows],
                delta_row=delta_row[:, rows], causal=causal,
                block_q=rows.stop - rows.start)
            dk, dv = dk + dk_r, dv + dv_r
            dq_acc[rows, :] += _mxu(ds_t, k_blk, _TN, ds_t.dtype)
        dk_ref[0, lo:hi, :] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0, lo:hi, :] = dv.astype(dv_ref.dtype)
    dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


# The head kernels' bodies are long to trace (2 * nb - 1 unrolled block
# steps) and a model calls them once a layer with the same shapes: an
# inlined jit keeps the traced call per (shapes, statics), so layers
# 2..L replay it — no name of its own lands between the caller's scope
# and `pallas_call` in the op's name.


@functools.partial(jax.jit, inline=True, static_argnames=(
    "scale", "block", "save_lse", "interpret"))
def _head_fwd(qb, kb, vb, *, scale, block, save_lse, interpret):
    """(o, lse) or o of [B*H, T, D] inputs; lse comes back [B*H,1,1,T]."""
    bh, t, d = qb.shape
    kv_of = _kv_row(bh // kb.shape[0])
    full = pl.BlockSpec((1, t, d), lambda i: (i, 0, 0))
    full_kv = pl.BlockSpec((1, t, d), lambda i: (kv_of(i), 0, 0))
    o_shape = jax.ShapeDtypeStruct(qb.shape, qb.dtype)
    lse_spec = pl.BlockSpec((1, 1, 1, t), lambda i: (i, 0, 0, 0))
    lse_shape = jax.ShapeDtypeStruct((bh, 1, 1, t), jnp.float32)
    return pl.pallas_call(
        functools.partial(
            _fwd_head_kernel if save_lse else _fwd_head_kernel_nolse,
            scale=scale, block=block),
        grid=(bh,),
        in_specs=[full, full_kv, full_kv],
        out_specs=[full, lse_spec] if save_lse else full,
        out_shape=[o_shape, lse_shape] if save_lse else o_shape,
        compiler_params=_dim_semantics(1),
        interpret=interpret,
    )(qb, kb, vb)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "scale", "block", "interpret"))
def _head_bwd(qb, kb, vb, dob, ob, lse, *, scale, block, interpret):
    """(dq, dk, dv) of [B*H, T, D] queries, [B*H_kv, T, D] keys and
    values and the [B*H, T] lse; dk and dv a QUERY head (`_unbh_kv`)."""
    bh, t, d = qb.shape
    kv_of = _kv_row(bh // kb.shape[0])
    full = pl.BlockSpec((1, t, d), lambda i: (i, 0, 0))
    full_kv = pl.BlockSpec((1, t, d), lambda i: (kv_of(i), 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_head_kernel, scale=scale, block=block),
        grid=(bh,),
        in_specs=[full, full_kv, full_kv, full, full,
                  pl.BlockSpec((1, 1, 1, t), lambda i: (i, 0, 0, 0))],
        out_specs=[full] * 3,
        out_shape=[jax.ShapeDtypeStruct(qb.shape, x.dtype)
                   for x in (qb, kb, vb)],
        scratch_shapes=[pltpu.VMEM((t, d), jnp.float32),   # dq
                        pltpu.VMEM((1, t), jnp.float32)],  # delta
        compiler_params=_dim_semantics(1),
        interpret=interpret,
    )(qb, kb, vb, dob, ob, lse.reshape(bh, 1, 1, t))


def _plain_attention(q, k, v, causal, scale, window=None):
    # single reference implementation, shared with the sequence-parallel
    # mixers (sequence.py has no pallas dependency; this module does)
    from ..parallel.sequence import _local_attention

    group = q.shape[2] // k.shape[2]
    if group > 1:   # grouped K/V heads: query head h reads h // group
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    return _local_attention(q, k, v, causal=causal, scale=scale,
                            window=window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    window: int | None = None,
) -> jnp.ndarray:
    """Attention over q [B, T, H, D] and k, v [B, T, H_kv, D] without
    materializing [T, T] scores. H_kv divides H (grouped K/V heads;
    H_kv == H is plain multi-head attention): query head h reads K/V
    head h // (H / H_kv), callers do NOT repeat K/V, and dk, dv come
    back at k's and v's shape, summed over each group in f32. The plain
    fallback takes the same shapes.

    Tiling requires T % block == 0 (and causal additionally
    block_q % block_k == 0); other shapes use the plain implementation.
    `block_q`/`block_k` default to auto: a causal, window-less T that
    is 2 to 8 chunks of 256 rows (512 ... 2048) runs the head kernels
    at that chunk; any other T <= 1024 runs as ONE block (any length —
    full-dim blocks always satisfy Mosaic's tiling rule; odd lengths
    verified on real v5e), longer T picks the largest of
    1024/512/256/128 dividing it that also keeps every kernel's VMEM
    estimate under `_VMEM_BUDGET` (large head dims shrink blocks
    instead of compile-OOMing), and longer non-dividing T takes the
    plain fallback. `interpret=None` auto-selects interpreter mode
    off-TPU so tests run on the CPU mesh.

    Each kernel then runs the head scheme where it applies, else the
    VMEM-resident block-skipping loops when they fit the budget, else
    the streaming grid — see the module docstring and `flash_plan` for
    the decision and the per-shape visited-block counts.

    Backward pass: fused flash backward kernels — the forward saves only
    (q, k, v, o, lse), dq/dk/dv are computed blockwise with the
    FlashAttention-2 recurrence (p re-materialized per block from the
    saved logsumexp): one kernel for all three in the head scheme and,
    behind the loops and the streaming grid alike, window-less or under
    a window at square tiles (`_bwd_stream_tiles`), else a dq + dkv
    pair with the delta precompute inside the dq kernel. Both
    directions are O(T) in HBM. Non-tiling shapes fall back to the
    plain VJP.

    `window` (requires causal=True): sliding-window attention — position
    q attends to keys [q - window, q] (Mistral-style local attention):
    `window` counts the keys BEFORE self. HF configs' `sliding_window`
    counts self too (key j visible iff 0 <= i - j < sliding_window), so
    a model with `sliding_window: 2048` passes `window=2047`
    (`models/afmoe.py`; `tests/test_afmoe.py` pins the edge). A
    windowed call's auto tiles are square (512 x 512 at d = 128 and
    256, T 8192), its backward ONE kernel (`_bwd_res_kernel`) where it
    fits: module docstring.
    Out-of-window blocks stream no DMA and spend no FLOPs — O(T *
    window) compute AND data movement — via the resident loop bounds
    (`_k_span`/`_q_span`), or, on the streaming fallback, via the
    narrowed inner grid (`_window_span`; the streaming dkv narrows only
    at block_q == block_k and keeps compute-skip otherwise). Measured
    at T=16k, window=512 on v5e with the round-5 slope harness:
    training fwd+bwd 5.48x, forward 4.54x vs the full-causal
    auto-block baseline.
    """
    out, _ = _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k,
                             interpret, save_lse=False, window=window)
    return out


def _tiles(t, causal, block_q, block_k, window=None, *, d=None,
           itemsize=4):
    """The (block_q, block_k) actually usable for length t, or None.

    `None` block sizes auto-select the head kernels' chunk for a
    causal, window-less t they can take (`_head_tiles`), else the
    largest power-of-two <= 1024 that divides t. PR 25's v5e sweep of
    the loops (fwd+bwd, 8192 tokens x 12 heads, d=64, bf16): at
    t = 1024 one 1024 block (2.06 ms) beats 512 x 256 and 256 x 256
    by 6% and 21% and loses 6% to 512 x 512; at t = 2048 and 4096
    (2.87 and 4.29 ms) 512 x 512 ties 1024 x 1024 within 2% and
    256 x 256 loses 36% and 52% — a loop's block step costs too much
    for small tiles (module docstring), so the loops' pick stays, and
    t = 4096 stays with them. With a sliding `window`, the cap is
    the largest power-of-two <= window instead: past-window score area
    inside a block is masked waste, and at t=16k/window=512 the 1024
    block measured 40% SLOWER (7.04 vs 5.02 ms) than 512. When the
    head dim `d` is known, auto blocks additionally shrink (bk first,
    then bq, powers of two, floor 128; a WINDOWED call both at once, so
    that it stays square: 512 x 512 at d = 128 and d = 256) until the
    WORST streaming kernel's VMEM estimate fits `_VMEM_BUDGET` — the
    fused_ce
    `_pick_blocks` discipline, so big-D shapes trade tile size for
    compilability instead of OOMing in Mosaic. Explicit sizes are
    respected as given (no budget shrink); mixing one explicit size
    with auto fills the other with the SAME value so the causal
    divisibility invariant can't silently demote the call to plain
    attention. Tiles below 128 starve the MXU, so auto only goes
    smaller when one block covers the whole (short) sequence;
    otherwise non-tiling lengths take the plain fallback as before.
    """
    auto = block_q is None and block_k is None
    if auto and causal and window is None:
        head = _head_tiles(t, d, itemsize)
        if head is not None:
            return head
    if auto:
        cap = 1024
        if window is not None:
            cap = max(128, 1 << max(7, (window).bit_length() - 1))
            cap = min(cap, 1024)
        if t <= cap:
            block_q = block_k = t  # one block: any length tiles
        else:
            pick = next((b for b in (1024, 512, 256, 128)
                         if b <= cap and t % b == 0), None)
            if pick is None:
                return None
            block_q = block_k = pick
    elif block_q is None:
        block_q = block_k
    elif block_k is None:
        block_k = block_q
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    if (t % block_q or t % block_k
            or (causal and block_q % block_k)):
        return None
    if auto and d is not None:
        # budget shrink — auto pow2 blocks only (halving a pow2 divisor
        # of t keeps dividing t and preserves bq % bk == 0)
        def _pow2(x):
            return x & (x - 1) == 0

        def _worst(bq, bk):
            return max(_fwd_stream_vmem(bq, bk, d, itemsize),
                       _dq_stream_vmem(bq, bk, d, itemsize),
                       _dkv_stream_vmem(bq, bk, d, itemsize, t))

        while _worst(block_q, block_k) > _VMEM_BUDGET:
            if (window is not None and block_q == block_k
                    and block_k > 128 and _pow2(block_k)):
                # a windowed call stays square: at block_q = 2 block_k
                # the streaming dkv cannot narrow its grid and walks
                # every q-block of every k-block (module docstring, PR
                # 34), and the resident loops fit less often
                block_q = block_k = block_k // 2
            elif block_k > 128 and _pow2(block_k):
                block_k //= 2
            elif block_q > 128 and _pow2(block_q):
                block_q //= 2
            else:
                # cannot shrink further (non-pow2 single-block tile, or
                # already at the 128 floor) and STILL over budget:
                # plain attention beats handing Mosaic an OOMing tile
                return None
    return block_q, block_k


def _narrowed_kv(causal, window, block_q, block_k, nk, kb, vb, kv_of):
    """Streaming-scheme sliding-window narrowing, shared by the
    forward and dq paths (which MUST agree on which blocks stream):
    returns (span, kv index map, K/V inputs). With a window, the inner
    grid dim narrows to the `span` K blocks a q-block can see and the
    K/V index maps shift by the q-block — out-of-window K/V never
    streams (round 3 skipped only the COMPUTE via pl.when, leaving the
    full-causal DMA schedule, and measured 2.3x where FLOP
    proportionality allows ~8x). K/V are front-padded by span-m blocks
    (m = bq//bk, affine for any m — see `_window_span`) so the map
    stays AFFINE — a max() in the map was measured to defeat Mosaic's
    DMA prefetch pipelining (~28% slower; see `_kernel`). `kv_of` maps
    a query head's grid row to its K/V head's (`_kv_row`)."""
    span = (_window_span(window, block_q, block_k, nk)
            if causal else None)
    if span is None:
        return None, (lambda i, j, kk: (kv_of(i), kk, 0)), kb, vb
    m_ratio = block_q // block_k
    kv_pad = (span - m_ratio) * block_k
    return (span,
            lambda i, j, kk: (kv_of(i), j * m_ratio + kk, 0),
            jnp.pad(kb, ((0, 0), (kv_pad, 0), (0, 0))),
            jnp.pad(vb, ((0, 0), (kv_pad, 0), (0, 0))))


def _bh(x):
    """[B, T, H, D] -> [B*H, T, D]: one grid row per (batch, head)."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _unbh(x, b, h):
    bh_, t, d = x.shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _kv_row(group):
    """Grid row i of the queries' [B*H, T, D] -> the row of its K/V head
    in [B*H_kv, T, D]: with H = H_kv * group, query head h reads K/V
    head h // group, and rows fold as b * H + h, so the row is i //
    group. The K/V block specs take it in their index maps: nothing
    repeats K/V in HBM, and the resident schemes' full-length K/V block
    keeps its index over a group's `group` consecutive rows, so the
    pipeline fetches it once a group. Ungrouped, the identity."""
    if group == 1:
        return lambda i: i
    return lambda i: lax.div(i, jnp.int32(group))


def _unbh_kv(x, b, h_kv):
    """The kernels' dk or dv, one a QUERY head [B*H, T, D], ->
    [B, T, H_kv, D]: a K/V head's gradient is the sum over its group's
    query heads, taken here in f32 by one XLA reduction (the backward
    kernels' grids run over query heads; a kernel that summed a group
    itself would hold `group` heads' dq in VMEM: module docstring)."""
    bh_, t, d = x.shape
    group = bh_ // (b * h_kv)
    if group > 1:
        x = x.reshape(b * h_kv, group, t, d).astype(jnp.float32).sum(
            axis=1).astype(x.dtype)
    return _unbh(x, b, h_kv)


def _kv_group(q, k, v):
    """Query heads a K/V head (1 ungrouped), after checking the
    contract: k and v alike, [B, T, H_kv, D] with H_kv dividing H."""
    if k.shape != v.shape or k.shape[:2] != q.shape[:2] or (
            k.shape[3] != q.shape[3] or q.shape[2] % k.shape[2]):
        raise ValueError(
            f"flash_attention takes q [B, T, H, D] and k, v [B, T, H_kv, "
            f"D] with H_kv dividing H; got {q.shape}, {k.shape}, {v.shape}")
    return q.shape[2] // k.shape[2]


def _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret,
                    save_lse, window=None):
    """Returns (out, lse) — lse is None on the plain-attention fallback
    or when `save_lse` is False (the no-grad forward skips the extra
    [B*H, T] output entirely: no HBM allocation, no writes)."""
    # validated HERE, not in the custom_vjp primal: under jax.grad the
    # primal body never runs (custom_vjp routes straight to _flash_fwd,
    # which also lands here), so a primal-only check would let autodiff
    # silently compute semantics the caller never asked for
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 0:
            raise ValueError(f"window must be >= 0, got {window}")
    b, t, h, d = q.shape
    kv_of = _kv_row(_kv_group(q, k, v))
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    isz = jnp.dtype(q.dtype).itemsize
    tiles = _tiles(t, causal, block_q, block_k, window, d=d,
                   itemsize=isz)
    if tiles is None:
        return _plain_attention(q, k, v, causal, scale,
                                window=window), None
    block_q, block_k = tiles
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    nq, nk = t // block_q, t // block_k
    o_shape = jax.ShapeDtypeStruct((b * h, t, d), q.dtype)
    lse_shape = jax.ShapeDtypeStruct((b * h, nq, 1, block_q),
                                     jnp.float32)

    scheme = _choose_scheme("fwd", t, d, isz, block_q, block_k, causal,
                            window)
    if scheme == "head":
        result = _head_fwd(_bh(q), _bh(k), _bh(v), scale=scale,
                           block=block_q, save_lse=save_lse,
                           interpret=interpret)
    elif scheme == "resident":
        kernel = functools.partial(
            _fwd_res_kernel if save_lse else _fwd_res_kernel_nolse,
            scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, window=window, nk=nk)
        o_spec = pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0))
        lse_spec = pl.BlockSpec((1, 1, 1, block_q),
                                lambda i, j: (i, j, 0, 0))
        result = pl.pallas_call(
            kernel,
            grid=(b * h, nq),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((1, t, d), lambda i, j: (kv_of(i), 0, 0)),
                pl.BlockSpec((1, t, d), lambda i, j: (kv_of(i), 0, 0)),
            ],
            out_specs=[o_spec, lse_spec] if save_lse else o_spec,
            out_shape=[o_shape, lse_shape] if save_lse else o_shape,
            compiler_params=_dim_semantics(2),
            interpret=interpret,
        )(_bh(q), _bh(k), _bh(v))
    else:
        span, kv_j, kb_in, vb_in = _narrowed_kv(
            causal, window, block_q, block_k, nk, _bh(k), _bh(v), kv_of)
        kernel = functools.partial(
            _kernel if save_lse else _kernel_nolse, scale=scale,
            causal=causal, block_q=block_q, block_k=block_k,
            window=window, span=span)
        o_spec = pl.BlockSpec((1, block_q, d),
                              lambda i, j, kk: (i, j, 0))
        lse_spec = pl.BlockSpec((1, 1, 1, block_q),
                                lambda i, j, kk: (i, j, 0, 0))
        result = pl.pallas_call(
            kernel,
            grid=(b * h, nq, span if span is not None else nk),
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda i, j, kk: (i, j, 0)),
                pl.BlockSpec((1, block_k, d), kv_j),
                pl.BlockSpec((1, block_k, d), kv_j),
            ],
            out_specs=[o_spec, lse_spec] if save_lse else o_spec,
            out_shape=[o_shape, lse_shape] if save_lse else o_shape,
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),  # out accumulator
                pltpu.VMEM((block_q, 1), jnp.float32),  # running max
                pltpu.VMEM((block_q, 1), jnp.float32),  # running denom
            ],
            compiler_params=_dim_semantics(3),
            interpret=interpret,
        )(_bh(q), kb_in, vb_in)
    if not save_lse:
        return _unbh(result, b, h), None
    out, lse = result
    return _unbh(out, b, h), lse.reshape(b * h, t)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                   dq_ref, delta_out_ref, acc_ref, lse_col, delta_col,
                   *, scale, causal, block_q, block_k, window=None,
                   span=None):
    """Grid (B*H, nq, nk), nk innermost: accumulate dq for one Q block
    while K/V blocks stream by. p is rebuilt from the saved lse, never
    stored: ds = p * (dp - delta); dq += scale * ds @ k. The q-row lse
    arrives lane-major (compact [B*H, T] storage) and is relayouted to
    a column ONCE per q-block into VMEM scratch; delta is COMPUTED here
    in the kk == 0 prologue (rowsum(dO * O) — dO/O are this program's
    q-blocks already) and emitted lane-major for the dkv kernel, so no
    standalone XLA delta pass touches HBM. This kernel's blocks change
    only with (i, q-block), so the inner k-sweep reuses the cached
    columns; its matmuls stay in Mosaic-native NN/NT forms (a fully
    transposed-space dq variant turns ds @ k into a TN contraction and
    measured 36% slower end-to-end)."""
    iq = pl.program_id(1)
    kk = pl.program_id(2)            # window-relative when narrowed
    nk = pl.num_programs(2)
    jk, ok = _span_step(iq, kk, span=span, causal=causal,
                        block_q=block_q, block_k=block_k, window=window)

    @pl.when(kk == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        lse_col[:] = lse_ref[0, 0].reshape(block_q, 1)
        delta_col[:] = jnp.sum(
            do_ref[0].astype(jnp.float32)
            * o_ref[0].astype(jnp.float32), axis=-1, keepdims=True)
        delta_out_ref[0, 0] = delta_col[:].reshape(1, block_q)

    @pl.when(ok)
    def _():
        acc_ref[:] += _dq_step(
            q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_col[:],
            delta_col[:], iq, jk, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, window=window)

    @pl.when(kk == nk - 1)
    def _():
        dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, block_k, window=None, span=None,
                    nq_total=None):
    """Grid (B*H, nk, nq), nq innermost: accumulate dk/dv for one K/V
    block while Q/dO blocks stream by, in TRANSPOSED score space (q on
    lanes — see _scores): dv += pT @ do; dk += scale * dsT @ q.

    lse/delta arrive as the head's FULL row set ([1, nq, 1, block_q],
    index_map constant over both inner grid dims), so their DMA runs
    once per head instead of once per inner step — per-step 2 KB
    fetches left ~30% on the table at T=16k — and the per-q-block row
    is a cheap non-tiled-dim select. In transposed space the row is
    already a lane vector (no relayout) and both accumulations are
    Mosaic-native NN contractions."""
    jk = pl.program_id(1)
    kk = pl.program_id(2)            # window-relative when narrowed
    nq = pl.num_programs(2)
    if span is None:
        iq = kk
        iq_c = kk
        valid = True
    else:
        # a K block's in-window q-blocks are [jk, jk + span); Q/dO are
        # END-padded by span-1 blocks so the index map stays affine,
        # and the pad tail must not contribute
        iq = jk + kk
        iq_c = jnp.minimum(iq, nq_total - 1)
        valid = iq <= nq_total - 1

    @pl.when(kk == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    ok = _diag_ok(iq, jk, causal, block_q, block_k, window)
    if valid is not True:
        ok = jnp.logical_and(ok, valid)

    @pl.when(ok)
    def _():
        dk, dv, _ = _dkv_step(
            q_ref[0], k_ref[0], v_ref[0], do_ref[0],
            lse_ref[0, iq_c, 0, :][None, :],          # [1, bq] lanes
            delta_ref[0, iq_c, 0, :][None, :],
            iq, jk, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, window=window)
        dk_acc[:] += dk
        dv_acc[:] += dv

    @pl.when(kk == nq - 1)
    def _():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_stream_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                       scale, causal, block_q, block_k):
    """Grid (B*H, nk, nq), nq innermost: dq, dk and dv of one head from
    ONE pass over its score blocks, in transposed score space as
    `_bwd_dkv_kernel`. dk/dv accumulate over a k-block's q-sweep as
    there; the step's dsT (`_dkv_step`) also gives the q-block's dq
    contribution, dsT^T @ k — a transposed-lhs contraction, as in the
    head kernel — added into an f32 accumulator that holds the WHOLE
    head's dq in VMEM scratch, since a q-block's dq is complete only
    after the last k-sweep: zeroed at the head's first step, scaled and
    written to the full-head output block at its last. Both inner grid
    dims carry state, so both are "arbitrary". Five block matmuls and
    one exp pass a step where the dq + dkv pair runs seven and two;
    each q-block still sums its k-blocks in ascending order in f32.
    lse/delta arrive as the head's full lane-major row set."""
    jk = pl.program_id(1)
    iq = pl.program_id(2)
    nk = pl.num_programs(1)
    nq = pl.num_programs(2)

    @pl.when(jnp.logical_and(jk == 0, iq == 0))
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(iq == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_diag_ok(iq, jk, causal, block_q, block_k))
    def _():
        k_blk = k_ref[0]
        dk, dv, ds_t = _dkv_step(
            q_ref[0], k_blk, v_ref[0], do_ref[0],
            lse_ref[0, iq, 0, :][None, :],            # [1, bq] lanes
            delta_ref[0, iq, 0, :][None, :],
            iq, jk, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k)
        dk_acc[...] += dk
        dv_acc[...] += dv
        rows = pl.ds(pl.multiple_of(iq * block_q, block_q), block_q)
        dq_acc[rows, :] += _mxu(ds_t, k_blk, _TN, ds_t.dtype)

    @pl.when(iq == nq - 1)
    def _():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(jk == nk - 1, iq == nq - 1))
    def _():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _stream_bwd(qb, kb, vb, dob, lse, delta, *, scale, causal, block_q,
                block_k, interpret):
    """(dq, dk, dv) of [B*H, T, D] queries, [B*H_kv, T, D] keys and
    values and the [B*H, T] lse and delta, by `_bwd_stream_kernel`; dk
    and dv a QUERY head (`_unbh_kv`)."""
    bh, t, d = qb.shape
    nq, nk = t // block_q, t // block_k
    kv_of = _kv_row(bh // kb.shape[0])
    kv_in = pl.BlockSpec((1, block_k, d),
                         lambda i, j, kk: (kv_of(i), j, 0))
    kv = pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, j, 0))
    if causal:
        # the q-blocks before a k-block's first visible one compute
        # nothing: name that first one for them, and the pipeline
        # fetches it once in place of a block a step (1.6 ms of 18.6 at
        # the glm cell's call; no pipelining lost, unlike `_span_step`'s
        # record of a max() in an index map)
        def qdo_j(i, j, kk):
            lo, _ = _q_span(j, nq, causal=True, window=None,
                            block_q=block_q, block_k=block_k)
            return i, jnp.maximum(kk, lo), 0
    else:
        def qdo_j(i, j, kk):
            return i, kk, 0
    qdo = pl.BlockSpec((1, block_q, d), qdo_j)
    rows = pl.BlockSpec((1, nq, 1, block_q),
                        lambda i, j, kk: (i, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_stream_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(bh, nk, nq),
        in_specs=[kv_in, kv_in, qdo, qdo, rows, rows],
        out_specs=[pl.BlockSpec((1, t, d), lambda i, j, kk: (i, 0, 0)),
                   kv, kv],
        out_shape=[jax.ShapeDtypeStruct(qb.shape, x.dtype)
                   for x in (qb, kb, vb)],
        scratch_shapes=[pltpu.VMEM((t, d), jnp.float32),        # dq
                        pltpu.VMEM((block_k, d), jnp.float32),  # dk
                        pltpu.VMEM((block_k, d), jnp.float32)],  # dv
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_BWD_STREAM_VMEM_LIMIT),
        interpret=interpret,
    )(kb, vb, qb, dob, lse.reshape(bh, nq, 1, block_q),
      delta.reshape(bh, nq, 1, block_q))


def _res_bwd(qb, kb, vb, dob, lse, delta, *, scale, causal, block_q,
             block_k, window, interpret):
    """`_stream_bwd`'s contract under a window, by `_bwd_res_kernel`:
    one K/V block and the head's whole Q, dO, lse and delta rows a
    program."""
    bh, t, d = qb.shape
    nq, nk = t // block_q, t // block_k
    kv_of = _kv_row(bh // kb.shape[0])
    kv_in = pl.BlockSpec((1, block_k, d), lambda i, j: (kv_of(i), j, 0))
    kv = pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0))
    full = pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0))
    rows = pl.BlockSpec((1, nq, 1, block_q), lambda i, j: (i, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_res_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, window=window,
                          nq=nq),
        grid=(bh, nk),
        in_specs=[kv_in, kv_in, full, full, rows, rows],
        out_specs=[full, kv, kv],
        out_shape=[jax.ShapeDtypeStruct(qb.shape, x.dtype)
                   for x in (qb, kb, vb)],
        scratch_shapes=[pltpu.VMEM((t, d), jnp.float32)],   # dq
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_BWD_STREAM_VMEM_LIMIT),
        interpret=interpret,
    )(kb, vb, qb, dob, lse.reshape(bh, nq, 1, block_q),
      delta.reshape(bh, nq, 1, block_q))


def _flash_bwd_impl(q, k, v, o, lse, g, causal, scale, block_q, block_k,
                    interpret, window=None):
    b, t, h, d = q.shape
    h_kv = k.shape[2]
    kv_of = _kv_row(h // h_kv)
    isz = jnp.dtype(q.dtype).itemsize
    auto = block_q is None and block_k is None
    plan = _tiles(t, causal, block_q, block_k, window, d=d, itemsize=isz)
    assert plan is not None, (
        "no flash tile fits the VMEM budget for this shape — the forward "
        "pass takes the plain-attention fallback for identical arguments, "
        "so this backward must be unreachable")
    block_q, block_k = plan
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    qb, kb, vb, dob = _bh(q), _bh(k), _bh(v), _bh(g)
    fused = _bwd_stream_tiles(t, d, isz, block_q, block_k, causal, window,
                              auto)
    if fused is not None:
        # one kernel for dq, dk and dv. delta (rowsum(dO * O),
        # FlashAttention-2 eq. 4) is one XLA reduction here: computed
        # in the kernel's first k-sweep instead it cost 0.3 ms more at
        # the glm cell's call (O's blocks ride the pipeline)
        delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1).transpose(0, 2, 1).reshape(b * h, t)
        kw = dict(scale=scale, causal=causal, block_q=fused[0],
                  block_k=fused[1], interpret=interpret)
        dq, dk, dv = (_stream_bwd(qb, kb, vb, dob, lse, delta, **kw)
                      if window is None else
                      _res_bwd(qb, kb, vb, dob, lse, delta, window=window,
                               **kw))
        return (_unbh(dq, b, h), _unbh_kv(dk, b, h_kv),
                _unbh_kv(dv, b, h_kv))
    ob = _bh(o)
    # lse enters the kernels at TRUE [B*H, T] size, reshaped to
    # [B*H, nq, 1, block_q] so Mosaic's tiling rule (trailing block
    # dims equal the array dims) accepts a one-row block; the dq kernel
    # relayouts the row into VMEM column scratch once per q-block, the
    # dkv kernel works in transposed score space where the row is
    # already lane-shaped (see _scores). For the dq + dkv pair delta is
    # not precomputed by XLA at all:
    # the dq kernel folds it into its kk == 0 / loop prologue (dO and O
    # stream there anyway) and emits it in the same compact lane-major
    # layout for the dkv kernel. This closes the round-2 ADVICE item
    # (the old layout broadcast both vectors to [B*H, T, 128] f32 in
    # HBM) AND the round-5 one (the separate delta reduction paid one
    # extra full HBM pass over dO and O per backward).
    nq, nk = t // block_q, t // block_k
    lse4 = lse.reshape(b * h, nq, 1, block_q)
    delta_shape = jax.ShapeDtypeStruct((b * h, nq, 1, block_q),
                                       jnp.float32)
    dq_shape = jax.ShapeDtypeStruct((b * h, t, d), q.dtype)

    scheme = _choose_scheme("dq", t, d, isz, block_q, block_k, causal,
                            window)
    if scheme == "head":   # one kernel for dq, dk and dv
        dq, dk, dv = _head_bwd(
            qb, kb, vb, dob, ob, lse, scale=scale, block=block_q,
            interpret=interpret)
        return (_unbh(dq, b, h), _unbh_kv(dk, b, h_kv),
                _unbh_kv(dv, b, h_kv))
    if scheme == "resident":
        dq_kernel = functools.partial(
            _dq_res_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, window=window, nk=nk)
        dq, delta4 = pl.pallas_call(
            dq_kernel,
            grid=(b * h, nq),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((1, t, d), lambda i, j: (kv_of(i), 0, 0)),
                pl.BlockSpec((1, t, d), lambda i, j: (kv_of(i), 0, 0)),
                pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((1, 1, 1, block_q),
                             lambda i, j: (i, j, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((1, 1, 1, block_q),
                             lambda i, j: (i, j, 0, 0)),
            ],
            out_shape=[dq_shape, delta_shape],
            compiler_params=_dim_semantics(2),
            interpret=interpret,
        )(qb, kb, vb, dob, ob, lse4)
    else:
        # same grid narrowing as the streaming forward — _narrowed_kv
        # is the single definition, so fwd and dq cannot disagree on
        # which blocks stream; narrows for any m = bq//bk (affine)
        span, kv_j, kb_in, vb_in = _narrowed_kv(
            causal, window, block_q, block_k, nk, kb, vb, kv_of)
        dq_kernel = functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, window=window, span=span)
        dq, delta4 = pl.pallas_call(
            dq_kernel,
            grid=(b * h, nq, span if span is not None else nk),
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda i, j, kk: (i, j, 0)),
                pl.BlockSpec((1, block_k, d), kv_j),
                pl.BlockSpec((1, block_k, d), kv_j),
                pl.BlockSpec((1, block_q, d),
                             lambda i, j, kk: (i, j, 0)),
                pl.BlockSpec((1, block_q, d),
                             lambda i, j, kk: (i, j, 0)),
                pl.BlockSpec((1, 1, 1, block_q),
                             lambda i, j, kk: (i, j, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda i, j, kk: (i, j, 0)),
                pl.BlockSpec((1, 1, 1, block_q),
                             lambda i, j, kk: (i, j, 0, 0)),
            ],
            out_shape=[dq_shape, delta_shape],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),  # lse column
                pltpu.VMEM((block_q, 1), jnp.float32),  # delta column
            ],
            compiler_params=_dim_semantics(3),
            interpret=interpret,
        )(qb, kb_in, vb_in, dob, ob, lse4)

    # one dk and dv a QUERY head: `_unbh_kv` sums each group's
    dkv_shapes = [
        jax.ShapeDtypeStruct((b * h, t, d), k.dtype),
        jax.ShapeDtypeStruct((b * h, t, d), v.dtype),
    ]
    if _choose_scheme("dkv", t, d, isz, block_q, block_k, causal,
                      window) == "resident":
        dkv_kernel = functools.partial(
            _dkv_res_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, window=window, nq=nq)
        dk, dv = pl.pallas_call(
            dkv_kernel,
            grid=(b * h, nk),
            in_specs=[
                pl.BlockSpec((1, block_k, d),
                             lambda i, j: (kv_of(i), j, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda i, j: (kv_of(i), j, 0)),
                pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((1, nq, 1, block_q),
                             lambda i, j: (i, 0, 0, 0)),
                pl.BlockSpec((1, nq, 1, block_q),
                             lambda i, j: (i, 0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            ],
            out_shape=dkv_shapes,
            compiler_params=_dim_semantics(2),
            interpret=interpret,
        )(kb, vb, qb, dob, lse4, delta4)
    else:
        # the streaming dkv kernel's q-start index jk // m is NOT
        # affine for m > 1, so it narrows only at m == 1 and otherwise
        # keeps the full grid with compute-skip. m == 1: q-blocks
        # [jk, jk+span) mirror the dq kernel's k-blocks [iq-span+1, iq]
        # over END-padded Q/dO arrays.
        m_ratio = block_q // block_k
        span = (_window_span(window, block_q, block_k, nk)
                if causal else None)
        span_dkv = span if m_ratio == 1 else None
        qb_in, dob_in = qb, dob
        if span_dkv is not None:
            q_pad = (span_dkv - 1) * block_q
            qb_in = jnp.pad(qb, ((0, 0), (0, q_pad), (0, 0)))
            dob_in = jnp.pad(dob, ((0, 0), (0, q_pad), (0, 0)))
        qdo_j = (lambda i, j, kk: (i, kk, 0)) if span_dkv is None else (
            lambda i, j, kk: (i, j + kk, 0))
        dkv_kernel = functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, window=window,
            span=span_dkv, nq_total=nq)
        dk, dv = pl.pallas_call(
            dkv_kernel,
            grid=(b * h, nk,
                  span_dkv if span_dkv is not None else nq),
            in_specs=[
                pl.BlockSpec((1, block_k, d),
                             lambda i, j, kk: (kv_of(i), j, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda i, j, kk: (kv_of(i), j, 0)),
                pl.BlockSpec((1, block_q, d), qdo_j),
                pl.BlockSpec((1, block_q, d), qdo_j),
                pl.BlockSpec((1, nq, 1, block_q),
                             lambda i, j, kk: (i, 0, 0, 0)),
                pl.BlockSpec((1, nq, 1, block_q),
                             lambda i, j, kk: (i, 0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d),
                             lambda i, j, kk: (i, j, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda i, j, kk: (i, j, 0)),
            ],
            out_shape=dkv_shapes,
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
            compiler_params=_dim_semantics(3),
            interpret=interpret,
        )(kb, vb, qb_in, dob_in, lse4, delta4)
    return (_unbh(dq, b, h), _unbh_kv(dk, b, h_kv), _unbh_kv(dv, b, h_kv))


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               window):
    out, lse = _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k,
                               interpret, save_lse=True, window=window)
    if lse is None:  # fallback path (statically decidable from shapes)
        return out, (q, k, v)
    # The residual is carried as [B, T, H, 1] — the same
    # batch/sequence/head layout as q/k/v/o — NOT the kernel's [B*H, T],
    # and the residual tuple carries NO None sentinels: both confuse
    # `shard_map(..., check_vma=False)` grad residual handling (the
    # hoisted residual gets mis-wired and downstream reshapes see the
    # lse where the output should be — see test_ulysses_flash_grads).
    b, t, h, d = q.shape
    lse4 = _unbh(lse[..., None], b, h)  # [B*H, T, 1] -> [B, T, H, 1]
    # the named `out` is the primal too: residual and value are one
    out = checkpoint_name(out, FLASH_OUT)
    lse4 = checkpoint_name(lse4, FLASH_LSE)
    return out, (q, k, v, out, lse4)


def _flash_bwd(causal, scale, block_q, block_k, interpret, window,
               res, g):
    q, k, v = res[0], res[1], res[2]
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if len(res) == 3:  # shapes didn't tile: mirror the fallback forward
        _, vjp = jax.vjp(
            lambda q, k, v: _plain_attention(q, k, v, causal, scale,
                                             window=window), q, k, v)
        return vjp(g)
    o, lse4 = res[3], res[4]
    lse = _bh(lse4)[..., 0]  # [B, T, H, 1] -> [B*H, T]
    return _flash_bwd_impl(q, k, v, o, lse, g, causal, scale, block_q,
                           block_k, interpret, window=window)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# planning / accounting introspection (benchmarks + structural tests)
# ---------------------------------------------------------------------------


def flash_plan(t, d, *, dtype=jnp.float32, causal=False, window=None,
               block_q=None, block_k=None, q_per_kv=1):
    """Static execution plan for `flash_attention` at this shape: block
    sizes, the MXU operand dtype of the block matmuls
    (`_operand_dtype`), per-kernel scheme, and per-kernel VISITED K/V
    (or Q/dO) block counts — the exact fori/grid trip totals, derived
    from the same `_k_span`/`_q_span`/`_window_span` the kernels use,
    so the structural block-skip tests and published benchmark
    metadata cannot drift from the implementation. `masked_blocks` of
    the visited ones build the causal/window mask (every block the
    resident and streaming loops visit; the diagonal's alone in the
    head kernels); `grid_blocks` is the unskipped outer*inner product
    for comparison.

    "bwd" says what the backward as a whole is: `scheme`
    "stream_fused" (window-less) or "resident_fused" (a window: PR 37)
    where `_bwd_stream_tiles` takes the call (one kernel, ITS tiles,
    `block_matmuls` 5 a block step), else the dq + dkv pair's (7: each
    rebuilds s and dp; the head scheme's one kernel 5). Its
    `visited_blocks` are the (q-block, k-block) pairs whose block step
    RUNS — for the streaming grids fewer than the grid steps "dq" and
    "dkv" count, since `pl.when` skips the rest; for "resident_fused"
    its loops' trips — and `vmem_bytes` the largest estimate among its
    kernels. "dq" / "dkv" say what the pair would run: which of the two
    narrowed its grid or its loops.

    `q_per_kv` > 1 (grouped K/V heads: that many query heads read one
    K/V head) changes no tile, scheme or count, all of them a query
    head's, and adds one entry, "kv_group": how K/V are read (`_kv_row`
    in the block specs' index maps), where dk and dv are summed over a
    group (`_unbh_kv`), and the bytes of the per-query-head dk and dv
    that sum reads, a K/V head. Ungrouped plans carry no such key."""
    isz = jnp.dtype(dtype).itemsize
    tiles = _tiles(t, causal, block_q, block_k, window, d=d,
                   itemsize=isz)
    if tiles is None:
        return {"scheme": "plain"}
    bq, bk = tiles
    nq, nk = t // bq, t // bk
    plan = {"block_q": bq, "block_k": bk, "nq": nq, "nk": nk,
            "operand_dtype": jnp.dtype(_operand_dtype(dtype)).name}
    span = _window_span(window, bq, bk, nk) if causal else None
    kw = dict(causal=causal, window=window, block_q=bq, block_k=bk)
    for which in ("fwd", "dq", "dkv"):
        scheme = _choose_scheme(which, t, d, isz, bq, bk, causal, window)
        masked = None
        if scheme == "head":
            # a chunk's wide step under the diagonal covers as much as
            # the square blocks it spans: the lower triangle, of which
            # only the diagonal's nq squares build the mask
            visited, masked = nq * (nq + 1) // 2, nq
        elif scheme == "resident":
            spans = ([_q_span(jk, nq, **kw) for jk in range(nk)]
                     if which == "dkv" else
                     [_k_span(iq, nk, **kw) for iq in range(nq)])
            visited = sum(int(hi) - int(lo) for lo, hi in spans)
        elif which == "dkv":
            visited = nk * (span if span is not None and bq == bk else nq)
        else:
            visited = nq * (span if span is not None else nk)
        if masked is None:   # the loops mask every block they visit
            masked = visited if causal else 0
        plan[which] = {"scheme": scheme, "visited_blocks": visited,
                       "masked_blocks": masked,
                       "grid_blocks": nq * nk}
    pair = [plan[which]["scheme"] for which in ("dq", "dkv")]
    fused = _bwd_stream_tiles(t, d, isz, bq, bk, causal, window,
                              block_q is None and block_k is None)
    if fused is not None:
        bq, bk = fused
        nq, nk = t // bq, t // bk
        scheme = "stream_fused" if window is None else "resident_fused"
        vmem = _bwd_fused_vmem(window)(bq, bk, d, isz, t)
    else:
        scheme = pair[0] if pair[0] == pair[1] else "+".join(pair)
        vmem = max(_kernel_vmem(which, plan[which]["scheme"], bq, bk, d,
                                isz, t) for which in ("dq", "dkv"))
    spans = [_q_span(jk, nq, causal=causal, window=window, block_q=bq,
                     block_k=bk) for jk in range(nk)]
    visited = sum(int(hi) - int(lo) for lo, hi in spans)
    plan["bwd"] = {
        "scheme": scheme, "block_q": bq, "block_k": bk,
        "visited_blocks": visited,
        "masked_blocks": (plan["dq"]["masked_blocks"] if scheme == "head"
                          else visited if causal else 0),
        "grid_blocks": nq * nk,
        "block_matmuls": 5 if scheme in _ONE_KERNEL_BWD else 7,
        "vmem_bytes": vmem}
    if q_per_kv > 1:
        plan["kv_group"] = {
            "q_per_kv": q_per_kv,
            "kv_read": "index_map",   # row i of q reads K/V row i // g
            "dkv_sum": "xla_f32",     # over a group, outside the kernel
            "dkv_partial_bytes": 2 * q_per_kv * t * d * isz}
    return plan


def flash_attention_flops(b, t, h, d, causal=False, window=None,
                          backward=False):
    """Useful matmul FLOPs of one flash_attention call (per the
    standard 2-FLOPs/MAC convention), counting only VISIBLE (q, k)
    position pairs — causal halves the full t^2, a sliding window caps
    each row at window+1 — so achieved/peak from this numerator is the
    honest kernel efficiency (masked-but-computed score area inside
    partially visible blocks counts as overhead, not work). Forward:
    QK^T + PV = 4*pairs*d; `backward=True` returns the fwd+bwd total
    for a grad call (the four backward block matmuls add 8*pairs*d)."""
    if causal:
        if window is not None:
            w = min(window, t - 1)
            pairs = t * (w + 1) - w * (w + 1) // 2
        else:
            pairs = t * (t + 1) // 2
    else:
        pairs = t * t
    flops = 4 * b * h * pairs * d
    if backward:
        flops += 8 * b * h * pairs * d
    return flops
