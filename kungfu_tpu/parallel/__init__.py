"""SPMD data-parallel runtime on a jax.sharding.Mesh.

This package is where the reference's "distributed optimizer wrapper + TF
session" pattern becomes TPU-native (SURVEY §7): a `Mesh` over the chips,
worker-local training state laid out with a leading mesh-axis dimension
(row i = worker i's model), and a jitted `shard_map` train step whose
collectives compile onto ICI. Elastic resize swaps the mesh at an epoch
boundary and re-broadcasts state (kungfu_tpu.elastic).
"""

from .mesh import (
    axis_size,
    broadcast_params,
    data_mesh,
    init_worker_state,
    replicate_to_workers,
    shard_batch,
    unstack_worker_state,
    worker_sharding,
)
from .pair_host import PairAveragingHost
from .sequence import (heads_to_seq, ring_attention, seq_to_heads,
                       ulysses_attention)
from .bootstrap import init_distributed, shutdown_distributed
from .expert import (MoEParams, dispatch_tensors, init_moe_params,
                     moe_capacity, moe_mlp)
from .pipeline import (pipeline_apply, pipeline_train_step_1f1b,
                       stack_stage_params)
from .rules import (PlanError, RuleTable, afmoe_rules, bert_tp_rules,
                    glm_moe_rules,
                    gpt_moe_rules, granite_hybrid_rules,
                    gpt_pp_rules, gpt_serve_rules, gpt_tp_rules,
                    match_partition_rules,
                    moe_ep_rules, ouro_rules, reshard, seq_sp_rules,
                    shard_params,
                    spec_diff, tree_specs)
from .vocab_ce import vocab_sharded_fused_ce
from .train import (build_dp_replicated_train_step, build_eval_step,
                    build_gspmd_train_step, build_train_step,
                    build_train_step_with_state)
from .zero import zero1_shard_opt_state

__all__ = [
    "data_mesh",
    "axis_size",
    "replicate_to_workers",
    "unstack_worker_state",
    "init_worker_state",
    "broadcast_params",
    "shard_batch",
    "worker_sharding",
    "build_train_step",
    "build_eval_step",
    "build_train_step_with_state",
    "build_gspmd_train_step",
    "build_dp_replicated_train_step",
    "init_distributed",
    "shutdown_distributed",
    "dispatch_tensors",
    "moe_capacity",
    "PairAveragingHost",
    "ring_attention",
    "ulysses_attention",
    "seq_to_heads",
    "heads_to_seq",
    "bert_tp_rules",
    "gpt_tp_rules",
    "gpt_moe_rules",
    "glm_moe_rules",
    "ouro_rules",
    "afmoe_rules",
    "granite_hybrid_rules",
    "gpt_pp_rules",
    "gpt_serve_rules",
    "moe_ep_rules",
    "seq_sp_rules",
    "match_partition_rules",
    "tree_specs",
    "spec_diff",
    "reshard",
    "PlanError",
    "RuleTable",
    "shard_params",
    "vocab_sharded_fused_ce",
    "zero1_shard_opt_state",
    "pipeline_train_step_1f1b",
    "moe_mlp",
    "init_moe_params",
    "MoEParams",
    "pipeline_apply",
    "stack_stage_params",
]
