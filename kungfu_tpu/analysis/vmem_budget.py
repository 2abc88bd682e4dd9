"""vmem-budget: block plans must fit VMEM over the benchmark grid.

Mosaic's scoped-vmem limit is a compile-time cliff: a block plan that
estimates past it OOMs with a compiler error at a shape nobody tried
until a user did (the round-6 calibration found h=1024 at 1024/1024
fused-CE blocks compiling 18.9 MB real against a 14.7 MB estimate).
``ops/flash.py`` and ``ops/fused_ce.py`` defend with budget-driven
auto-shrink (``flash_plan`` / ``_pick_blocks``); this pass evaluates
those exact plan functions over the declared benchmark shape grid and
fails the lint when any chosen plan's own VMEM estimate exceeds the
budget — so a drift between the block defaults, the estimate models
and the budget becomes a lint failure instead of a 3 a.m. Mosaic
crash at a new shape.

Unlike the AST passes this one imports the real modules (the plan
functions are pure host-side Python over ints): the single source of
truth for the estimate IS the implementation, so the lint can never
disagree with what the kernels will actually request.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

from .core import Finding

NAME = "vmem-budget"

#: The shape grid the flash benchmarks sweep (benchmarks/flash_eff.py
#: defaults + the published BASELINE long-context points), extended to
#: the head dims that historically broke estimates (d=256 at long T).
FLASH_GRID = [
    # (t, d, dtype_name, causal, window)
    (1024, 64, "float32", False, None),
    (1024, 64, "bfloat16", True, None),    # the GPT cells: head kernels
    (1024, 256, "bfloat16", True, None),   # head kernels at a big head dim
    (2048, 64, "bfloat16", True, None),    # head kernels' widest step
    (2048, 64, "float32", True, None),     # past their budget: the loops
    (2048, 128, "bfloat16", True, None),
    (4096, 64, "bfloat16", True, None),
    (4096, 128, "bfloat16", True, None),   # the ouro cell: loops + fused bwd
    (4096, 256, "float32", True, None),
    (8192, 128, "bfloat16", True, None),   # the trinity-mini cell's full layers
    (8192, 128, "bfloat16", True, 2047),   # its sliding layers: 512 x 512 loops
    (8192, 256, "bfloat16", False, None),
    (8192, 256, "bfloat16", True, None),   # the glm cell: fused backward
    (16384, 64, "bfloat16", True, None),
    (16384, 64, "bfloat16", True, 512),
    (16384, 128, "bfloat16", True, 512),
    (16384, 256, "float32", True, None),
]

#: Fused-CE grid: GPT-2-small benchmark shapes (lm.py defaults), the
#: h=1024 OOM calibration point, the n=16384 full-model-graph shrink
#: point, and a non-divisible vocab.
FUSED_CE_GRID = [
    # (n, h, v)
    (1024, 256, 32000),
    (8184, 768, 50257),
    (8192, 1024, 50257),
    (16384, 768, 50257),
    (16384, 1024, 50304),
    (32768, 4096, 128256),
]


#: Paged-decode grid: the serving shapes `ops/paged_attn.paged_plan`
#: must cover — (max_len, block_tokens, num_heads, head_dim, dtype).
#: GPT-2 small/medium serving tiers at growing context plus the
#: big-pool long-context point that pushes the resident scheme past
#: any plausible budget (the plan must DEGRADE there, not OOM).
PAGED_GRID = [
    (1024, 16, 12, 64, "bfloat16"),
    (2048, 16, 16, 64, "bfloat16"),
    (4096, 32, 16, 64, "bfloat16"),
    (4096, 16, 32, 128, "bfloat16"),
    (8192, 32, 16, 64, "bfloat16"),
    (2048, 16, 12, 64, "float32"),
]


def check_paged(grid: Sequence = PAGED_GRID,
                budget: Optional[int] = None) -> List[Finding]:
    import jax.numpy as jnp

    from ..ops import paged_attn

    budget = paged_attn._VMEM_BUDGET if budget is None else budget
    findings = []
    for max_len, bt, heads, d, dtype_name in grid:
        dtype = jnp.dtype(dtype_name)
        max_blocks = -(-max_len // bt)
        plan = paged_attn.paged_plan(max_blocks, bt, heads, d,
                                     dtype=dtype)
        if plan["scheme"] == "functional":
            continue  # stock-JAX fallback: nothing to compile
        est = plan["vmem_bytes"]
        if est > budget:
            findings.append(Finding(
                "kungfu_tpu/ops/paged_attn.py", 1, NAME,
                f"paged decode plan at max_len={max_len} "
                f"block_tokens={bt} heads={heads} d={d} "
                f"dtype={dtype_name} picks scheme={plan['scheme']} "
                f"with VMEM estimate {est / 2**20:.1f} MB > budget "
                f"{budget / 2**20:.1f} MB — Mosaic would OOM at "
                "compile time"))
    return findings


def check_flash(grid: Sequence = FLASH_GRID,
                budget: Optional[int] = None) -> List[Finding]:
    import jax.numpy as jnp

    from ..ops import flash

    fused_limit = (flash._BWD_STREAM_VMEM_LIMIT if budget is None
                   else budget)
    fwd_limit = flash._FWD_STREAM_VMEM_LIMIT if budget is None else budget
    budget = flash._VMEM_BUDGET if budget is None else budget
    findings = []
    for t, d, dtype_name, causal, window in grid:
        dtype = jnp.dtype(dtype_name)
        plan = flash.flash_plan(t, d, dtype=dtype, causal=causal,
                                window=window)
        if plan.get("scheme") == "plain":
            continue  # fallback path: nothing to compile, nothing to OOM
        bq, bk = plan["block_q"], plan["block_k"]
        bwd = plan["bwd"]
        # the fused backward replaces dq + dkv behind the loops and
        # the streaming grid alike, at its own tiles and under the
        # limit it states
        fused = bwd["scheme"] in ("stream_fused", "resident_fused")
        kernels = [
            (which, plan[which]["scheme"], (bq, bk), flash._kernel_vmem(
                which, plan[which]["scheme"], bq, bk, d, dtype.itemsize,
                t), budget)
            for which in (() if fused else ("dq", "dkv"))]
        # a window-less forward names its own tiles; on the streaming
        # grid it states its own limit too
        fwd = plan["fwd"]
        fq, fk = fwd.get("block_q", bq), fwd.get("block_k", bk)
        kernels.insert(0, (
            "fwd", fwd["scheme"], (fq, fk),
            flash._kernel_vmem("fwd", fwd["scheme"], fq, fk, d,
                               dtype.itemsize, t),
            fwd_limit if window is None and fwd["scheme"] == "stream"
            else budget))
        if fused:
            kernels.append(("bwd", bwd["scheme"],
                            (bwd["block_q"], bwd["block_k"]),
                            bwd["vmem_bytes"], fused_limit))
        for which, scheme, blocks, est, limit in kernels:
            if est > limit:
                findings.append(Finding(
                    "kungfu_tpu/ops/flash.py", 1, NAME,
                    f"flash {which} plan at t={t} d={d} "
                    f"dtype={dtype_name} causal={causal} "
                    f"window={window} picks blocks {blocks} "
                    f"scheme={scheme} with VMEM estimate "
                    f"{est / 2**20:.1f} MB > budget "
                    f"{limit / 2**20:.1f} MB — Mosaic would OOM at "
                    "compile time"))
    return findings


def check_fused_ce(grid: Sequence = FUSED_CE_GRID,
                   budget: Optional[int] = None) -> List[Finding]:
    from ..ops import fused_ce

    budget = fused_ce._VMEM_BUDGET if budget is None else budget
    findings = []
    models = {"fwd": fused_ce._fwd_vmem_bytes,
              "recompute": fused_ce._recompute_vmem_bytes}
    for n, h, v in grid:
        for label, model in models.items():
            blocks = fused_ce._pick_blocks(n, h, v, vmem_bytes=model)
            if blocks is None:
                continue  # callers take the reference path: safe
            bn, bv = blocks
            est = model(bn, h, bv)
            if est > budget:
                findings.append(Finding(
                    "kungfu_tpu/ops/fused_ce.py", 1, NAME,
                    f"fused_ce {label} plan at n={n} h={h} v={v} picks "
                    f"blocks ({bn}, {bv}) with VMEM estimate "
                    f"{est / 2**20:.1f} MB > budget "
                    f"{budget / 2**20:.1f} MB — Mosaic would OOM at "
                    "compile time"))
    return findings


class VmemBudgetPass:
    name = NAME
    doc = ("flash/fused_ce/paged-decode block plans evaluated over "
           "the benchmark shape grid must fit the VMEM budget")

    def run_global(self, paths: Sequence[str]) -> List[Finding]:
        # only meaningful when the analyzed tree contains the kernels
        import os

        covers = any(
            os.path.isdir(p) and any(
                os.path.exists(os.path.join(root, "flash.py"))
                for root, _, _ in os.walk(p))
            or os.path.basename(p) in ("flash.py", "fused_ce.py",
                                       "paged_attn.py")
            for p in paths)
        if not covers:
            return []
        return check_flash() + check_fused_ce() + check_paged()
