"""Fused paged-attention decode as a Pallas TPU kernel.

The serving decode step (`serve/paged.py`) is shaped around a block-
table KV pool: each batch row owns an ordered list of fixed-size pool
blocks and a length. The stock-JAX path re-gathers every row's blocks
into a contiguous [T, h, d] view per layer per step
(``pool_k[layer][tables]``), which materializes
B * max_blocks * block_tokens * h * d bytes of HBM traffic per layer
even for rows that occupy two blocks. This kernel removes the
re-gather: per-row block tables and lengths ride in as SCALAR-PREFETCH
arguments (`pltpu.PrefetchScalarGridSpec`), the K/V BlockSpec index
maps chase the table (``tbl[b, j]`` picks the j-th pool block of row
b), and the grid's inner dimension is clamped to each row's own
visible block count — steps past ``lengths[b] // bt`` re-issue the
LAST visible block's index, which Pallas's block-revisiting rule turns
into zero new DMA traffic, so the bytes actually moved per row are
O(length), not O(max_len). vLLM's PagedAttention decode shape
(PAPERS.md), as a flash-style Pallas kernel.

Two execution schemes per shape, chosen by a VMEM-budget estimate in
the `flash_plan` style (``paged_plan`` shows the decision):

- **resident** (preferred while it fits): VMEM scratch holds the
  row's full score buffer ([max_blocks, h, bt] f32) and a copy of its
  visited V blocks; the final grid step runs ONE full-width softmax
  over the buffer — the exact shape and masking of the functional
  path's f32 softmax, which is what makes the functional path a
  bitwise oracle for this scheme (pinned by
  tests/test_serve.py::TestPagedKernel).
- **stream** (fallback past the budget — long max_len residency):
  online-softmax carried in O(h*d) scratch across the inner grid, the
  flash recurrence at block_tokens granularity. Token-equivalent, not
  bitwise (the usual online-softmax reassociation).

Past BOTH estimates, `paged_plan` says ``functional`` and
`serve.paged.decode_step` keeps its stock-JAX gather — the same
over-budget fallback discipline as ops/flash.py (`_tiles` returning
None), so an impossible shape degrades to slower, never to a Mosaic
compile OOM. The kflint ``vmem-budget`` pass evaluates `paged_plan`
over the serving shape grid for exactly that reason.

`interpret=None` auto-selects interpreter mode off-TPU, so the CPU
test mesh runs the real kernel logic (scalar prefetch included)
without Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)

#: same calibration as ops/flash.py — Mosaic's scoped-vmem stack limit
#: is 16 MB; 15 MB leaves scheduling headroom.
_VMEM_BUDGET = 15 * 1024 * 1024

#: the tests' hook, as in ops/flash.py: monkeypatch it to force one
#: scheme, regardless of the budget decision, through callers that
#: pass no `scheme=` (the engine). None = auto; read at trace time.
_FORCE_SCHEME = None


# ---------------------------------------------------------------------------
# VMEM-budget estimates (style of ops/flash.py)
# ---------------------------------------------------------------------------


def _tile(rows, cols, isz):
    """VMEM bytes of one [rows, cols] trailing tile as Mosaic lays it
    out: padded to 8 sublanes of 32 bits (16 rows of bf16) by 128
    lanes. A GPT-2 head tile [12, 64] bf16 occupies 4 KiB, not 1.5 —
    the unpadded count let `paged_plan` offer the resident scheme at
    max_len 2048, which the v5e compiler refuses (scoped VMEM)."""
    sub = 8 * max(4 // isz, 1)  # 8-byte types: 8 sublanes, as f32
    return -(-rows // sub) * sub * -(-cols // 128) * 128 * isz


def _res_vmem(max_blocks, bt, h, d, isz):
    """Resident scheme: double-buffered K/V pool blocks + q/o + the
    full-length score buffer (f32) and V copy (pool dtype) + the final
    step's temporaries (V in f32 for the weighted sum, and s, w and
    the exp intermediate, each [h, T] f32)."""
    t = max_blocks * bt
    inputs = 2 * (2 * bt * _tile(h, d, isz))
    io = 2 * (2 * _tile(h, d, isz))
    scratch = max_blocks * _tile(h, bt, 4) + t * _tile(h, d, isz)
    temps = t * _tile(h, d, 4) + 3 * _tile(h, t, 4)
    return inputs + io + scratch + temps


def _stream_vmem(bt, h, d, isz):
    """Stream scheme: double-buffered K/V blocks + q/o + the online
    state (acc [h, d] + m/l columns, f32) + per-block temporaries (the
    K and V block in f32, score and weight tiles). O(block) regardless
    of max_len."""
    inputs = 2 * (2 * bt * _tile(h, d, isz))
    io = 2 * (2 * _tile(h, d, isz))
    scratch = _tile(h, d, 4) + 2 * _tile(h, 1, 4)
    temps = 2 * bt * _tile(h, d, 4) + 2 * _tile(h, bt, 4)
    return inputs + io + scratch + temps


def paged_plan(max_blocks, block_tokens, num_heads, head_dim, *,
               dtype=jnp.float32):
    """Static execution plan for `paged_attention` at this pool shape:
    the chosen scheme and the per-scheme VMEM estimates — derived from
    the same models the kernel requests scratch with, so the kflint
    vmem-budget pass and the published benchmark metadata cannot drift
    from the implementation."""
    isz = jnp.dtype(dtype).itemsize
    res = _res_vmem(max_blocks, block_tokens, num_heads, head_dim, isz)
    strm = _stream_vmem(block_tokens, num_heads, head_dim, isz)
    if _FORCE_SCHEME in ("resident", "stream"):
        scheme = _FORCE_SCHEME
    elif res <= _VMEM_BUDGET:
        scheme = "resident"
    elif strm <= _VMEM_BUDGET:
        scheme = "stream"
    else:
        scheme = "functional"
    return {
        "scheme": scheme,
        "t": max_blocks * block_tokens,
        "max_blocks": max_blocks,
        "block_tokens": block_tokens,
        "resident_bytes": res,
        "stream_bytes": strm,
        "vmem_bytes": {"resident": res, "stream": strm,
                       "functional": 0}[scheme],
    }


def paged_traffic_bytes(lengths, block_tokens, num_heads, head_dim,
                        itemsize, layers=1):
    """Block-pool bytes a decode step actually VISITS under the
    table-chasing index maps: per row, the visible blocks only
    (length // bt + 1 of them), K and V, per layer. This is the
    traffic model `benchmarks/flash_eff.py` publishes achieved
    bandwidth against — the whole point of the kernel is that this,
    not B * max_blocks * bt, is what moves."""
    blocks = sum(int(n) // block_tokens + 1 for n in lengths)
    return 2 * layers * blocks * block_tokens * num_heads * head_dim \
        * itemsize


# ---------------------------------------------------------------------------
# kernels (grid (B, max_blocks), block tables + lengths scalar-prefetched)
# ---------------------------------------------------------------------------


def _qk(q, k):
    """Per-head scores ``q [h, d] . k [t, h, d] -> [h, t]`` as a VPU
    multiply and lane reduction. Mosaic refuses the direct
    ``"nd,tnd->nt"`` einsum (batch dimension in the middle, no free
    lhs dimension), and with one query row per head the MXU would run
    one row deep anyway; the exact-f32 multiply-reduce is also the
    form XLA's CPU backend gives the functional path's einsum, which
    keeps the resident scheme its bitwise oracle in interpret mode."""
    return jnp.sum(k * q[None], axis=-1).T


def _pv(p, v):
    """Per-head weighted sum ``p [h, t] . v [t, h, d] -> [h, d]`` on
    the MXU, in the one form Mosaic lowers a batched `dot_general`
    in: batch (head) dimension leading on both sides and a free lhs
    dimension (a unit row axis here)."""
    o = jax.lax.dot_general(
        p[:, None, :], jnp.swapaxes(v, 0, 1),
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)     # [h, 1, d]
    return o[:, 0, :]


def _block_scores(q_ref, k_ref, length, j, *, bt, scale):
    """One pool block's masked f32 score tile [h, bt] — shared by both
    schemes so masking/scaling semantics cannot drift. Matches the
    functional path exactly: f32 einsum over d, scale applied AFTER
    the contraction, invisible positions (> length) forced to
    f32-finfo.min."""
    q = q_ref[0].astype(jnp.float32)            # [h, d]
    k = k_ref[0].astype(jnp.float32)            # [bt, h, d]
    s = _qk(q, k) * scale                       # [h, bt]
    pos = j * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
    return jnp.where(pos <= length, s, NEG_INF)


def _res_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                s_buf, v_buf, *, bt, max_blocks, scale):
    """Resident scheme: accumulate per-block score tiles and V copies
    into full-length VMEM scratch; the LAST grid step runs one
    full-width softmax + weighted sum — the functional path's exact
    reduction shapes, hence bitwise logits parity (pool dtype V is
    cast to f32 at the same point the functional einsum casts it)."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    length = len_ref[b]
    nvis = length // bt + 1          # the incoming token sits at `length`

    @pl.when(j == 0)
    def _():
        # NEG_INF scores == the functional path's masked fill for
        # never-visited positions; zero V so 0-weight rows contribute
        # exact zeros instead of NaN-poisoning uninitialized VMEM
        s_buf[...] = jnp.full_like(s_buf, NEG_INF)
        v_buf[...] = jnp.zeros_like(v_buf)

    @pl.when(j < nvis)
    def _():
        s_buf[j] = _block_scores(q_ref, k_ref, length, j, bt=bt,
                                 scale=scale)
        v_buf[j] = v_ref[0]

    @pl.when(j == max_blocks - 1)
    def _():
        t = max_blocks * bt
        h = q_ref.shape[1]
        s = s_buf[...].transpose(1, 0, 2).reshape(h, t)   # [h, T]
        w = jax.nn.softmax(s, axis=-1)
        v = v_buf[...].reshape(t, h, -1).astype(jnp.float32)
        o_ref[0] = _pv(w, v).astype(o_ref.dtype)


def _stream_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, bt, scale):
    """Stream scheme: the flash online-softmax recurrence carried in
    O(h*d) VMEM scratch across the inner grid — resident VMEM stays
    constant in max_len, for pools whose full-length buffer would not
    fit the budget."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)
    length = len_ref[b]
    nvis = length // bt + 1

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j < nvis)
    def _():
        s = _block_scores(q_ref, k_ref, length, j, bt=bt, scale=scale)
        m = m_ref[:, 0]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=-1)
        v = v_ref[0].astype(jnp.float32)          # [bt, h, d]
        acc_ref[...] = acc_ref[...] * alpha[:, None] + _pv(p, v)
        m_ref[:, 0] = m_new

    @pl.when(j == nb - 1)
    def _():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _kv_index_map(base, bt):
    """Table-chasing K/V index map: grid step (b, j) fetches pool
    block ``base + tbl[b, j]``, with j CLAMPED to the row's last
    visible block — past-length steps re-issue the same block index,
    which Pallas's revisiting rule resolves to no new DMA. `base`
    offsets into a [layers * (num_blocks + 1), bt, h, d] pool view so
    the per-layer call needs no layer-slice copy of the pool."""

    def index_map(b, j, tbl_ref, len_ref):
        jj = jnp.minimum(j, len_ref[b] // bt)
        return (base + tbl_ref[b, jj], 0, 0, 0)

    return index_map


def paged_attention(q, k_pool, v_pool, tables, lengths, *,
                    block_base=0, scheme=None, interpret=None):
    """Paged decode attention for one layer.

    - ``q`` [B, h, d] — the current token's query per row (its k/v
      must already be scattered at position ``lengths[b]``);
    - ``k_pool``/``v_pool`` [num_pool_blocks, bt, h, d] — the pool
      tensors (any leading layer structure flattened away; `block_base`
      offsets table entries into it);
    - ``tables`` [B, max_blocks] int32, ``lengths`` [B] int32 — the
      allocator's batch views; visibility is positions 0..length
      INCLUSIVE, matching `serve.paged.decode_step`.

    Returns ``o`` [B, h, d] in q's dtype (the attention output before
    the out-projection). `scheme=None` consults `paged_plan`; a
    "functional" plan raises — the CALLER owns the fallback (it has
    the stock-JAX path; this module has no second implementation to
    silently diverge)."""
    b, h, d = q.shape
    bt = k_pool.shape[1]
    max_blocks = tables.shape[1]
    if scheme is None:
        scheme = paged_plan(max_blocks, bt, h, d, dtype=q.dtype)["scheme"]
    if scheme == "functional":
        raise ValueError(
            "paged_plan chose the functional fallback for this shape — "
            "call serve.paged.decode_step with kernel='functional'")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    scale = d ** -0.5
    tables = tables.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)

    if scheme == "resident":
        kernel = functools.partial(_res_kernel, bt=bt,
                                   max_blocks=max_blocks, scale=scale)
        scratch = [
            pltpu.VMEM((max_blocks, h, bt), jnp.float32),
            pltpu.VMEM((max_blocks, bt, h, d), v_pool.dtype),
        ]
    elif scheme == "stream":
        kernel = functools.partial(_stream_kernel, bt=bt, scale=scale)
        scratch = [
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ]
    else:
        raise ValueError(f"unknown paged scheme {scheme!r}")

    kv_map = _kv_index_map(block_base, bt)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_blocks),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda b_, j, tbl, ln: (b_, 0, 0)),
            pl.BlockSpec((1, bt, h, d), kv_map),
            pl.BlockSpec((1, bt, h, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, h, d),
                               lambda b_, j, tbl, ln: (b_, 0, 0)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tables, lengths, q, k_pool, v_pool)
