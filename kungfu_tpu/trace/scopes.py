"""The names this program puts into a profiler trace: one table.

Device side: `jax.named_scope`s the compiled step opens round a layer
boundary, so a trace reader selects operations by a name the program
owns and not by whatever scope path JAX and flax happen to give them.
Host side: the prefix under which a kftrace span appears in a running
`jax.profiler` session. docs/observability.md "Device scopes and the
profiler" holds the same table and the rule for adding a name.

Plain constants: importing this module imports nothing.
"""

#: `tx.update` + `optax.apply_updates` in every builder of
#: `parallel/train.py`: the optimizer's arithmetic, whatever `tx` is.
OPT_UPDATE = "kf.opt_update"

#: everything a data-parallel step does to agree across workers:
#: `ops.collective.all_reduce_mean`, `bucketed_all_reduce_mean`
#: (concatenate, pmean, slice), the model-state and loss pmeans. Under
#: `sync_sgd` it nests inside OPT_UPDATE: a reader of the optimizer's
#: time takes OPT_UPDATE without GRAD_SYNC.
GRAD_SYNC = "kf.grad_sync"

#: head matmul + cross-entropy of `ops/fused_ce.py`, forward and
#: backward: the kernels, the pads and casts round them, and the
#: residual scheme's backward head matmuls.
FUSED_CE = "kf.fused_ce"

#: latent attention of `models/glm_moe.py`: the low-rank projections,
#: their norms, rotary positions, the flash (or plain) call and the
#: output projection. The kernels stay `pallas_call`s directly under
#: `MLAttention_<n>` inside it.
MLA = "kf.mla"

#: an expert layer's routing: router matmul, sigmoid, top-k, the sort
#: into the row buffer, dispatch's gather and combine's sums of each
#: token's held rows.
MOE_ROUTE = "kf.moe_route"

#: an expert layer's matmuls: the grouped ones over the held experts'
#: rows and the shared expert's.
MOE_EXPERTS = "kf.moe_experts"

#: the multi-token-prediction module whole: its two norms, `eh_proj`,
#: its one expert block (whose MLA / MOE scopes nest inside it).
MTP = "kf.mtp"

#: one pass of `models/ouro.py`'s stack: every block once and the final
#: norm. Opened inside the loop over passes, so it stands once in the
#: HLO of a rolled loop and once a pass in the trace; the forward, the
#: recomputed forward and the backward of every pass carry it.
LOOP_STACK = "kf.loop_stack"

#: what the looped model does with the passes: the exit gate after each,
#: sigmoid, the exit distribution, its entropy and the weighting of the
#: passes' cross-entropies (the head + CE calls stay under FUSED_CE).
LOOP_EXIT = "kf.loop_exit"

#: the attention sublayer of a SLIDING-window layer of
#: `models/afmoe.py`: both sandwich norms, the q / k / v / gate
#: projections, QK-norm, rotary, the windowed flash (or plain) call, the
#: output gate and `o`. The kernels stay `pallas_call`s directly under
#: `LocalAttention_<n>` inside it.
ATTN_LOCAL = "kf.attn_local"

#: the same of a FULL-attention layer (no positions, no window); the
#: kernels directly under `GlobalAttention_<n>`. Two names, so that a
#: trace prices the two kinds of layer of one stack apart.
ATTN_GLOBAL = "kf.attn_global"

#: a Mamba-2 mixer's sublayer of `models/granite_hybrid.py`: its input
#: norm, `in_proj`, the causal depthwise conv, the step size, the SSD
#: scan (SSD below, nested), the gated norm and `out_proj`; forward,
#: recomputed forward and backward.
SSM = "kf.ssm"

#: the chunked state-space-duality scan of `ops/ssd.py` alone, forward
#: and backward: the decays and their sums, the in-chunk masked
#: products, the chunk states, the pass between chunks and the output
#: from the states.
SSD = "kf.ssd"

#: a kftrace span `name` shows in a profiler session as
#: HOST_SPAN_PREFIX + name, on the calling thread of `/host:CPU`.
HOST_SPAN_PREFIX = "kf."
