"""Vocab-sharded fused cross-entropy: the Pallas head on a real mesh.

Until this module, the fused head+CE kernel (`ops/fused_ce.py`) ran
only where the mesh degenerated to one device — every multi-chip
configuration (`--tp`, multi-chip MoE) silently fell back to the
unfused f32-logits head because `pallas_call` has no GSPMD
partitioning rule, so the partitioner would all-gather the kernel's
operands instead of splitting them. This is the Megatron-LM
vocab-parallel-loss move, built on the same shard_map-wraps-Pallas
pattern `build_dp_replicated_train_step` proved for dp:

- the lm_head weight is **column-sharded over the model axis**: each
  device owns a vocab shard [H, V/tp] and runs the unmodified fused
  forward kernel on its shard, producing the *local* online row-max /
  sum-exp (as a local logsumexp) and the local target-logit partial;
- a **psum-based logsumexp combine** recovers the exact global loss:
  ``lse = m + log(psum(exp(lse_local - m)))`` with ``m = pmax(
  lse_local)``, and ``tl = psum(tl_local)`` (each row's target lives
  in exactly one shard; the others contribute 0 by the sentinel
  targets below);
- the backward reuses the unmodified per-shard kernels with the
  *global* lse: dW/db stay local to the owning shard (a column of W
  only touches its own logits), dx partials are psum'd over the model
  axis, and dW/db/dx row-partials are psum'd over the data axis.

Target sentinels make this work without kernel changes: each shard
rewrites the global target ids so that -1 still marks a padded row
(zero gradient), an in-shard target becomes its local column, and an
out-of-shard target becomes ``v_loc_pad`` — a value >= the padded
local vocab that can never match a column (no onehot hit) but is >= 0
(the row keeps its pure-softmax gradient and stays in the loss mean).

Autodiff never transposes the shard_map: the whole sharded fwd/bwd
pair is ONE `jax.custom_vjp` whose fwd and bwd each invoke shard_map
as opaque SPMD programs with explicit in/out specs, so the collectives
(and their replication) are stated, not inferred.

No reference counterpart: the reference's loss is framework-fused and
data-parallel only; this is the TPU-native tensor-parallel extension.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from .rules import cols, replicated, rows, spec, stacked
from ..ops.fused_ce import (_PAD_BIAS, _dw_pallas, _dx_pallas,
                            _fwd_pallas, _fwd_vmem_bytes, _pick_blocks,
                            _recompute_vmem_bytes, _residual_d_pallas,
                            _round_up, reference_cross_entropy)
from ..trace.scopes import FUSED_CE


class _VSConfig(NamedTuple):
    """Static plan for one (shapes, mesh) instance — hashable so it can
    ride custom_vjp's nondiff_argnums."""
    mesh: Mesh
    data_axis: str
    model_axis: str
    residual: bool
    interpret: bool
    bn: int
    bv: int
    n: int            # global rows
    h: int
    v: int            # true vocab
    v_padg: int       # vocab padded to a multiple of tp
    d_data: int
    tp: int
    n_loc: int        # rows per data shard
    n_loc_pad: int    # row-padded to a multiple of bn (per shard)
    v_loc: int        # vocab columns per model shard
    v_loc_pad: int    # column-padded to a multiple of bv (per shard)


def _localize_targets(t, cfg: _VSConfig):
    """Global target ids -> this shard's sentinel form (see module
    docstring): row-pad to n_loc_pad with -1, then map out-of-shard
    targets to v_loc_pad (valid row, no onehot hit)."""
    voff = lax.axis_index(cfg.model_axis) * cfg.v_loc
    t_pad = jnp.pad(t.astype(jnp.int32), (0, cfg.n_loc_pad - cfg.n_loc),
                    constant_values=-1)
    in_shard = (t_pad >= voff) & (t_pad < voff + cfg.v_loc)
    t_loc = jnp.where(t_pad < 0, -1,
                      jnp.where(in_shard, t_pad - voff, cfg.v_loc_pad))
    return t_loc[:, None]


def _local_pads(x, w, b, cfg: _VSConfig):
    x_p = jnp.pad(x, ((0, cfg.n_loc_pad - cfg.n_loc), (0, 0)))
    w_p = jnp.pad(w, ((0, 0), (0, cfg.v_loc_pad - cfg.v_loc)))
    b_p = jnp.pad(b, (0, cfg.v_loc_pad - cfg.v_loc),
                  constant_values=_PAD_BIAS)[None, :]
    return x_p, w_p, b_p


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _vs_ce(cfg: _VSConfig, x, w, b, t):
    loss, _ = _vs_fwd(cfg, x, w, b, t)
    return loss


@jax.named_scope(FUSED_CE)
def _vs_fwd(cfg: _VSConfig, x, w, b, t):
    da, ma = cfg.data_axis, cfg.model_axis

    def shard_fwd(x, w, b, t):
        x_p, w_p, b_p = _local_pads(x, w, b, cfg)
        t_loc = _localize_targets(t, cfg)
        logits, lse, tl = _fwd_pallas(x_p, w_p, b_p, t_loc, cfg.bn,
                                      cfg.bv, cfg.interpret,
                                      residual=cfg.residual)
        # exact logsumexp combine over the vocab shards: each shard's
        # lse is a valid partial logsumexp of its own columns
        m = lax.pmax(lse, ma)
        lse_g = m + jnp.log(lax.psum(jnp.exp(lse - m), ma))
        tl_g = lax.psum(tl, ma)
        valid = (t_loc >= 0).astype(jnp.float32)
        num_valid = jnp.maximum(
            lax.psum(jnp.sum(valid), da), 1.0)
        loss = lax.psum(jnp.sum((lse_g - tl_g) * valid), da) / num_valid
        if cfg.residual:
            return loss, lse_g, num_valid, logits
        return loss, lse_g, num_valid

    out_specs = (replicated(), rows(da), replicated())
    if cfg.residual:
        out_specs = out_specs + (spec(da, ma),)
    out = jax.shard_map(
        shard_fwd, mesh=cfg.mesh,
        in_specs=(rows(da), cols(ma), stacked(ma), stacked(da)),
        out_specs=out_specs, check_vma=False)(x, w, b, t)
    loss, lse_g, num_valid = out[:3]
    logits = out[3] if cfg.residual else None
    return loss, (x, w, b, t, lse_g, num_valid, logits)


@jax.named_scope(FUSED_CE)
def _vs_bwd(cfg: _VSConfig, res, g):
    import numpy as np

    x, w, b, t, lse_g, num_valid, logits = res
    da, ma = cfg.data_axis, cfg.model_axis

    def shard_bwd(g, num_valid, x, w, b, t, lse, *maybe_logits):
        x_p, w_p, b_p = _local_pads(x, w, b, cfg)
        t_loc = _localize_targets(t, cfg)
        scale = (g / num_valid).astype(jnp.float32)[None, None]
        if cfg.residual:
            d, db = _residual_d_pallas(scale, maybe_logits[0], lse,
                                       t_loc, cfg.bn, cfg.bv,
                                       cfg.interpret)
            dw = lax.dot_general(x_p, d, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            dx = lax.dot_general(d, w_p, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        else:
            dw, db = _dw_pallas(scale, x_p, w_p, b_p, t_loc, lse,
                                cfg.bn, cfg.bv, cfg.interpret)
            dx = _dx_pallas(scale, x_p, w_p, b_p, t_loc, lse, cfg.bn,
                            cfg.bv, cfg.interpret)
        # dW/db: sum the row partials over data shards, stay local in
        # vocab; dx: sum the vocab partials over model shards, stay
        # local in rows. Per-shard pads are sliced off inside the
        # region (row/column pads are shard-local). Partials are
        # psum'd in f32 and cast AFTER — summing bf16 partials would
        # accrue one rounding per shard on near-cancelling terms,
        # where the single-device kernel rounds once.
        dw = lax.psum(dw.astype(jnp.float32), da)[:, :cfg.v_loc]
        db = lax.psum(db.astype(jnp.float32), da)[0, :cfg.v_loc]
        dx = lax.psum(dx.astype(jnp.float32), ma)[:cfg.n_loc]
        return dx.astype(x.dtype), dw.astype(w.dtype), db

    args = (g, num_valid, x, w, b, t, lse_g)
    in_specs = (replicated(), replicated(), rows(da), cols(ma),
                stacked(ma), stacked(da), rows(da))
    if cfg.residual:
        args = args + (logits,)
        in_specs = in_specs + (spec(da, ma),)
    dx, dw, db = jax.shard_map(
        shard_bwd, mesh=cfg.mesh, in_specs=in_specs,
        out_specs=(rows(da), cols(ma), stacked(ma)),
        check_vma=False)(*args)
    return dx, dw, db, np.zeros(t.shape, jax.dtypes.float0)


_vs_ce.defvjp(_vs_fwd, _vs_bwd)


def vocab_sharded_fused_ce(hidden, kernel, bias, targets, *,
                           mesh: Mesh,
                           data_axis: str = "data",
                           model_axis: str = "model",
                           residual: bool = True,
                           interpret: Optional[bool] = None):
    """Mean softmax cross-entropy of ``hidden @ kernel + bias`` against
    integer `targets` through the fused Pallas head, vocab-sharded over
    `model_axis` and row-sharded over `data_axis` of `mesh`.

    Same semantics and dtypes as `ops.fused_ce.fused_cross_entropy`
    (bf16 matmuls, f32 accumulation, differentiable in hidden/kernel/
    bias); exact — not approximate — on any mesh: the per-shard online
    logsumexp partials are combined with a psum-based logsumexp, so
    loss and gradients match the single-device kernel up to reduction
    order. Non-divisible vocabularies are padded to a multiple of the
    model-axis size with `_PAD_BIAS` columns that contribute exactly 0
    to loss and gradients, then sliced off.

    Falls back to `reference_cross_entropy` (GSPMD partitions the
    plain-XLA path natively) when H doesn't tile (not a multiple of
    128), rows don't divide the data axis, or no block size fits VMEM.

    `interpret=None` keys Pallas interpreter mode off the MESH devices
    (not the default backend — the driver host may own a broken TPU
    while the mesh is virtual CPU).
    """
    n, h = hidden.shape
    v = kernel.shape[1]
    d_data = mesh.shape[data_axis]
    tp = mesh.shape[model_axis]
    v_padg = _round_up(v, tp)
    v_loc = v_padg // tp
    vmem = _fwd_vmem_bytes if residual else _recompute_vmem_bytes
    blocks = None
    if h % 128 == 0 and n % d_data == 0:
        blocks = _pick_blocks(n // d_data, h, v_loc, vmem)
    if blocks is None:
        with jax.named_scope(FUSED_CE):
            return reference_cross_entropy(hidden, kernel, bias, targets)
    if interpret is None:
        interpret = mesh.devices.flat[0].platform != "tpu"
    bn, bv = blocks
    n_loc = n // d_data
    cfg = _VSConfig(
        mesh=mesh, data_axis=data_axis, model_axis=model_axis,
        residual=residual, interpret=interpret, bn=bn, bv=bv,
        n=n, h=h, v=v, v_padg=v_padg, d_data=d_data, tp=tp,
        n_loc=n_loc, n_loc_pad=_round_up(n_loc, bn),
        v_loc=v_loc, v_loc_pad=_round_up(v_loc, bv))
    # differentiable pads/casts OUTSIDE the custom_vjp: JAX transposes
    # them to slice/cast-back, so callers see unpadded gradients in
    # their own dtypes (same convention as fused_cross_entropy)
    with jax.named_scope(FUSED_CE):  # the halves open it themselves
        x = hidden.astype(jnp.bfloat16)
        w = jnp.pad(kernel.astype(jnp.bfloat16),
                    ((0, 0), (0, v_padg - v)))
        b = jnp.pad(bias.astype(jnp.float32), (0, v_padg - v),
                    constant_values=_PAD_BIAS)
        t = lax.stop_gradient(targets).astype(jnp.int32)
    return _vs_ce(cfg, x, w, b, t)
