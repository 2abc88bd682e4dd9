"""kfspec: the declarative sharding-rules engine.

Every ``parallel/`` module used to hand-build its PartitionSpecs — a
new dp x tp x pp x ep x sp composition meant a new special case that
only failed at runtime (the ``fused=(n == 1)`` silent-degradation
class PR 3 killed by hand). This module makes specs **data**: an
ordered table of ``(path regex, PartitionSpec)`` rules per model
family (the SNIPPETS.md [2] ``match_partition_rules`` pattern), one
engine that instantiates a table on any mesh, and plan-time
validation so a bad composition raises where the plan is derived —
not three layers deep inside a shard_map trace.

Because a table is data, it is **statically checkable**: the
``shard-rule-coverage`` / ``shard-rule-mesh`` kflint passes
(``analysis/shard_rules.py``) walk the :data:`REGISTRY` and prove
every leaf of every registered model tree matches exactly one rule,
every axis a rule names exists in every declared mesh shape, and the
sharded dims divide — and the ``shard-rules`` pass flags literal
``PartitionSpec(...)`` construction anywhere else in the package, so
specs cannot silently regrow as code. kfverify's ``schedule-purity``
pass holds the table constructors (``*_rules`` functions and
``match_partition_rules``) to the same shape-only discipline as
chunk/bucket/shard_schedule: no tensor-value or env reads, so every
rank statically derives the identical plan.

Match semantics (pinned by tests/test_shard_rules.py):

- **first match wins** over the ordered rules (``re.fullmatch`` on
  the ``/``-joined leaf path);
- a rule whose spec has more entries than the leaf has dims is
  **skipped** (rank guard — the one-rule-serves-kernel-and-bias idiom
  the legacy ``tensor.spec_for`` established);
- scalars are never partitioned (``P()``);
- a :class:`RuleTable` is **total**: an unmatched leaf raises
  :class:`PlanError` at plan time (tables end with an explicit
  catch-all), while a legacy plain sequence of ``(pattern, spec)``
  pairs keeps the historical lenient behavior (unmatched leaves
  replicate) so existing call sites migrate without a flag day.

The same table serves params, optimizer state and activations:
optax state paths embed the param path as a suffix (``0/mu/<param
path>``), so ``.*``-anchored rules match both trees; batch/activation
placement comes from the table's ``batch_axes``.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from functools import lru_cache
from typing import (Callable, Dict, Iterator, Mapping, Optional,
                    Sequence, Tuple)

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# rule: (path regex, PartitionSpec). First match wins.
Rules = Sequence[Tuple[str, PartitionSpec]]


class PlanError(ValueError):
    """A sharding plan cannot be derived: unmatched leaf, unknown mesh
    axis, or a non-divisible dimension — raised when the plan is built,
    never from inside a shard_map trace."""


# -- spec constructors --------------------------------------------------------
#
# The ONLY place in the package that constructs PartitionSpec (the
# `shard-rules` lint pass enforces this): call sites say what a layout
# MEANS, and the construction stays here where the mesh-validity pass
# can see every axis name.


def spec(*axes) -> PartitionSpec:
    """``PartitionSpec(*axes)`` — the generic constructor."""
    return PartitionSpec(*axes)


def replicated() -> PartitionSpec:
    """Fully replicated (the empty spec)."""
    return PartitionSpec()


def stacked(axis: str) -> PartitionSpec:
    """Leading dim split over ``axis`` — worker-stacked state rows and
    batch leading dims alike."""
    return PartitionSpec(axis)


def rows(axis: str) -> PartitionSpec:
    """A 2-D operand split along dim 0 (row-parallel kernels, row
    shards of activations)."""
    return PartitionSpec(axis, None)


def cols(axis: str) -> PartitionSpec:
    """A 2-D operand split along dim 1 (column-parallel kernels,
    vocab-sharded heads)."""
    return PartitionSpec(None, axis)


#: Spec-helper names the axis-consistency pass resolves axis names
#: from (specs-as-data): a string argument to any of these IS a mesh
#: axis declaration at the call site.
SPEC_HELPERS = ("spec", "replicated", "stacked", "rows", "cols")


# -- the rule table -----------------------------------------------------------


@dataclass(frozen=True)
class RuleTable:
    """An ordered, named, *total* rules table for one model family.

    Iterates as legacy ``(pattern, spec)`` pairs so every pre-engine
    call site (``shard_params(params, mesh, gpt_tp_rules())``) keeps
    working unchanged.

    ``axes`` is the table's declared axis universe (derived from the
    rules unless given); ``batch_axes`` names the mesh axes a batch's
    leading dim shards over — the activation half of the plan.
    """

    name: str
    rules: Tuple[Tuple[str, PartitionSpec], ...]
    batch_axes: Tuple[str, ...] = ()
    axes: Tuple[str, ...] = field(default=())

    def __post_init__(self):
        if not self.axes:
            object.__setattr__(self, "axes", _rule_axes(self.rules))

    def __iter__(self) -> Iterator[Tuple[str, PartitionSpec]]:
        return iter(self.rules)

    def __len__(self) -> int:
        return len(self.rules)

    def __getitem__(self, i) -> Tuple[str, PartitionSpec]:
        return self.rules[i]

    def batch_spec(self) -> PartitionSpec:
        """Leading-dim placement for a global batch on this table's
        meshes (the activation spec)."""
        if not self.batch_axes:
            return replicated()
        if len(self.batch_axes) == 1:
            return stacked(self.batch_axes[0])
        return spec(tuple(self.batch_axes))


def _spec_axes(s: PartitionSpec) -> Tuple[str, ...]:
    out = []
    for entry in tuple(s):
        if entry is None:
            continue
        for ax in (entry if isinstance(entry, tuple) else (entry,)):
            if ax not in out:
                out.append(ax)
    return tuple(out)


def _rule_axes(rules: Rules) -> Tuple[str, ...]:
    out: list = []
    for _, s in rules:
        for ax in _spec_axes(s):
            if ax not in out:
                out.append(ax)
    return tuple(out)


# -- matching -----------------------------------------------------------------


@lru_cache(maxsize=1024)
def _compiled(pattern: str):
    return re.compile(pattern)


def path_str(path) -> str:
    """The ``/``-joined leaf path rules match against (dict keys,
    sequence indices and NamedTuple fields all stringify)."""
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def match_index(rules: Rules, path: str, ndim: int) -> Optional[int]:
    """Index of the first rule matching ``path`` at rank ``ndim``
    (the rank guard skips rules written for larger ranks), or None."""
    for i, (pattern, s) in enumerate(rules):
        if _compiled(pattern).fullmatch(path) is None:
            continue
        if len(s) > ndim:  # rule written for a larger rank
            continue
        return i
    return None


def spec_for(path: str, ndim: int, rules: Rules) -> Optional[PartitionSpec]:
    """First-match-wins spec for one leaf path, or None (legacy
    lenient contract — unmatched leaves replicate downstream)."""
    i = match_index(rules, path, ndim)
    return None if i is None else rules[i][1]


def match_partition_rules(rules: Rules, tree):
    """Pytree of PartitionSpecs for ``tree`` per the ordered rules.

    Scalars never partition. With a :class:`RuleTable` an unmatched
    leaf raises :class:`PlanError` (tables are total — end them with a
    catch-all); a plain rules sequence keeps the legacy lenient
    behavior and maps unmatched leaves to the replicated spec.
    """
    strict = isinstance(rules, RuleTable)

    def get(path, leaf):
        nd = np.ndim(leaf)
        if nd == 0:
            return replicated()
        s = spec_for(path_str(path), nd, rules)
        if s is None:
            if strict:
                raise PlanError(
                    f"table {rules.name!r}: no rule matches leaf "
                    f"{path_str(path)!r} (rank {nd}) — rules tables "
                    "must be total; add a rule or a catch-all")
            return replicated()
        return s

    return jax.tree_util.tree_map_with_path(get, tree)


def tree_specs(params, rules: Rules) -> Dict[str, PartitionSpec]:
    """{leaf path: spec} for every *matched* leaf (debugging aid; the
    legacy contract — unmatched leaves are absent, scalars included
    only when a rule claims them)."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        s = spec_for(path_str(path), np.ndim(leaf), rules)
        if s is not None:
            out[path_str(path)] = s
    return out


# -- plan-time validation -----------------------------------------------------


def _axis_sizes(entry, mesh_shape: Mapping[str, int]) -> int:
    size = 1
    for ax in (entry if isinstance(entry, tuple) else (entry,)):
        size *= mesh_shape[ax]
    return size


def validate_specs(specs, tree, mesh_shape: Mapping[str, int],
                   table_name: str = "<specs>") -> None:
    """Prove a spec tree instantiates on ``mesh_shape``: every named
    axis exists and every sharded dim divides. Raises PlanError with
    the leaf path — at plan time, not at runtime inside shard_map."""
    flat_s = jax.tree_util.tree_flatten_with_path(specs)[0]
    leaves = jax.tree_util.tree_leaves(tree)
    for (path, s), leaf in zip(flat_s, leaves):
        shape = np.shape(leaf)
        for dim, entry in enumerate(tuple(s)):
            if entry is None:
                continue
            for ax in (entry if isinstance(entry, tuple) else (entry,)):
                if ax not in mesh_shape:
                    raise PlanError(
                        f"table {table_name!r}: leaf {path_str(path)!r} "
                        f"names axis {ax!r} absent from mesh "
                        f"{dict(mesh_shape)}")
            size = _axis_sizes(entry, mesh_shape)
            if shape[dim] % size:
                raise PlanError(
                    f"table {table_name!r}: leaf {path_str(path)!r} "
                    f"dim {dim} of size {shape[dim]} does not divide "
                    f"over {entry!r} (size {size}) in mesh "
                    f"{dict(mesh_shape)}")


def plan(rules: Rules, tree, mesh_shape: Mapping[str, int]):
    """Validated spec tree for ``tree`` on ``mesh_shape`` — the one
    entry point composing match + validation, so every consumer
    (shard_params, elastic reshard, checkpoint restore) fails the
    same way at the same time."""
    name = rules.name if isinstance(rules, RuleTable) else "<rules>"
    specs = match_partition_rules(rules, tree)
    validate_specs(specs, tree, mesh_shape, table_name=name)
    return specs


# -- placement / diff ---------------------------------------------------------


def placement_signature(s: PartitionSpec, ndim: int,
                        mesh_shape: Mapping[str, int]) -> Tuple:
    """Per-dim ``(axis names, split size)`` of a spec instantiated on
    one mesh shape. An axis absent from the mesh contributes a split
    of 1 (replication over an absent axis is no split) — that is what
    makes signatures comparable ACROSS mesh shapes: a dp x tp save and
    a tp x pp restore agree on a leaf exactly when its bytes land the
    same way."""
    sig = []
    entries = tuple(s) + (None,) * (ndim - len(tuple(s)))
    for entry in entries:
        if entry is None:
            sig.append(((), 1))
            continue
        axes = tuple(entry if isinstance(entry, tuple) else (entry,))
        size = 1
        for ax in axes:
            size *= int(mesh_shape.get(ax, 1))
        sig.append((axes, size))
    return tuple(sig)


def spec_diff(specs, tree, axes_a: Mapping[str, int],
              axes_b: Mapping[str, int]) -> Dict[str, Tuple[Tuple, Tuple]]:
    """{leaf path: (signature under axes_a, signature under axes_b)}
    for every leaf whose placement CHANGES between the two mesh
    shapes — the diff that drives joiner resharding and
    mesh-shape-change restore (unchanged leaves need no data
    movement beyond the device map)."""
    flat_s = jax.tree_util.tree_flatten_with_path(specs)[0]
    leaves = jax.tree_util.tree_leaves(tree)
    out: Dict[str, Tuple[Tuple, Tuple]] = {}
    for (path, s), leaf in zip(flat_s, leaves):
        nd = np.ndim(leaf)
        a = placement_signature(s, nd, axes_a)
        b = placement_signature(s, nd, axes_b)
        if a != b:
            out[path_str(path)] = (a, b)
    return out


def place(tree, mesh: Mesh, specs):
    """`jax.device_put` every leaf per its spec (same-sharding leaves
    are no-ops inside device_put, so calling this after a spec_diff
    moves only what changed)."""
    return jax.tree_util.tree_map(
        lambda leaf, s: jax.device_put(leaf, NamedSharding(mesh, s)),
        tree, specs)


def reshard(tree, mesh: Mesh, rules: Rules,
            prev_axes: Optional[Mapping[str, int]] = None):
    """Plan + place ``tree`` on ``mesh`` per the rules table.

    Returns ``(placed_tree, diff)`` where ``diff`` is the
    :func:`spec_diff` against ``prev_axes`` (the mesh shape the tree
    was last planned for) — empty when no leaf's byte layout moved.
    With ``prev_axes=None`` the diff is computed against a fully
    replicated prior (every sharded leaf reports as changed)."""
    mesh_shape = dict(mesh.shape)
    specs = plan(rules, tree, mesh_shape)
    diff = spec_diff(specs, tree, dict(prev_axes or {}), mesh_shape)
    return place(tree, mesh, specs), diff


# -- the model-family tables --------------------------------------------------


def _attention_rules(scope: str, axis: str) -> Tuple:
    """Megatron attention split: QKV projections column-parallel
    (heads shard), output projection row-parallel, column-parallel
    biases shard with the features."""
    return (
        (r".*(query|key|value).*kernel", spec(None, axis, None)),
        (rf".*{scope}.*out.*kernel", spec(axis, None, None)),
        (r".*(query|key|value).*bias", rows(axis)),
    )


def _mlp_rules(scope: str, axis: str) -> Tuple:
    """Megatron dense-MLP split: up-projection column-parallel,
    down-projection row-parallel."""
    return (
        (rf".*{scope}.*Dense_0.*kernel", cols(axis)),
        (rf".*{scope}.*Dense_1.*kernel", rows(axis)),
        (rf".*{scope}.*Dense_0.*bias", stacked(axis)),
    )


def _megatron_rules(scope: str, axis: str) -> Tuple:
    """The Megatron split, anchored to a transformer-block scope name.

    Anchoring matters: the models' top-level vocab logits heads are
    also auto-named `Dense_0`, and vocab sizes (30522/50257) rarely
    divide a model axis — heads and embeddings stay replicated by
    falling through to the catch-all.
    """
    return _attention_rules(scope, axis) + _mlp_rules(scope, axis)


#: every table is total: the catch-all replicates what no earlier rule
#: claims (embeddings, layernorms, heads, optimizer scalars)
_CATCH_ALL = (r".*", replicated())


def bert_tp_rules(axis: str = "model") -> RuleTable:
    """Megatron split for models/bert.py parameter paths."""
    return RuleTable(
        name=f"bert_tp[{axis}]",
        rules=_megatron_rules("TransformerLayer", axis) + (_CATCH_ALL,),
        batch_axes=("data",))


def gpt_tp_rules(axis: str = "model") -> RuleTable:
    """Megatron split for models/gpt.py parameter paths (Block
    scope)."""
    return RuleTable(
        name=f"gpt_tp[{axis}]",
        rules=_megatron_rules("Block", axis) + (_CATCH_ALL,),
        batch_axes=("data",))


def gpt_moe_rules(axis: str = "model") -> RuleTable:
    """Expert sharding for `models.gpt.MoEMLP`'s global stacks,
    composed with the Megatron split: expert stacks [E, H, F] shard
    their expert dim over `axis`, the router stays replicated, and the
    non-MoE rules apply to attention. GSPMD lowers the
    dispatch/combine einsums to all-to-alls across the expert
    shards."""
    return RuleTable(
        name=f"gpt_moe[{axis}]",
        rules=(
            (r".*moe.*w_(up|down)", spec(axis, None, None)),
            (r".*moe.*router", replicated()),
            # attention rules only: a MoE GPT's blocks have no dense
            # MLP, so the Dense_0/Dense_1 split would be dead rules
            # (the shard-rule-coverage pass holds tables to that)
        ) + _attention_rules("Block", axis) + (_CATCH_ALL,),
        batch_axes=("data",))


def glm_moe_rules(axis: str = "model") -> RuleTable:
    """The Megatron split for `models/glm_moe.py`'s parameter paths:
    the per-head up-projections of latent attention (`q_b`, `kv_b`)
    column-parallel over heads and `o` row-parallel; every SwiGLU
    (dense, shared expert) and the held experts' stacks split over
    their width. The low-rank down-projections, norms, routers and
    their biases, `eh_proj`, the embedding and the head replicate (a
    sliced vocabulary rarely divides a model axis). The stacks'
    leading dim is the held experts': an `expert` mesh axis would
    split it, and no table here names one yet."""
    return RuleTable(
        name=f"glm_moe[{axis}]",
        rules=(
            (r".*MLAttention.*(q_b|kv_b).*kernel", spec(None, axis, None)),
            (r".*MLAttention.*/o/kernel", spec(axis, None, None)),
            (r".*(mlp|shared)/(gate|up)/kernel", cols(axis)),
            (r".*(mlp|shared)/down/kernel", rows(axis)),
            (r".*moe/w_(gate|up)", spec(None, None, axis)),
            (r".*moe/w_down", spec(None, axis, None)),
            _CATCH_ALL,
        ),
        batch_axes=("data",))


def ouro_rules(axis: str = "model") -> RuleTable:
    """The Megatron split for `models/ouro.py`'s parameter paths: q, k
    and v column-parallel over heads and `o` row-parallel, the SwiGLU
    split over its width. One table serves every pass: the passes share
    the one `stack/` subtree. Norms, the exit gate, the embedding and
    the head replicate."""
    return RuleTable(
        name=f"ouro[{axis}]",
        rules=(
            (r".*RotaryAttention.*/(q|k|v)/kernel", spec(None, axis, None)),
            (r".*RotaryAttention.*/o/kernel", spec(axis, None, None)),
            (r".*mlp/(gate|up)/kernel", cols(axis)),
            (r".*mlp/down/kernel", rows(axis)),
            _CATCH_ALL,
        ),
        batch_axes=("data",))


def afmoe_rules(axis: str = "model") -> RuleTable:
    """The Megatron split for `models/afmoe.py`'s parameter paths, both
    kinds of attention layer alike: q, k and v column-parallel over
    their heads (the K/V heads are fewer: the axis must divide THEIR
    count), the output gate over its columns, which lie head by head as
    `o`'s rows do, and `o` row-parallel; every SwiGLU (dense, shared
    expert) and the held experts' stacks split over their width. Norms
    (the QK-norms' [head_dim] scales too), routers and their biases,
    the embedding and the head replicate. The stacks' leading dim is
    the held experts', as in `glm_moe_rules`."""
    return RuleTable(
        name=f"afmoe[{axis}]",
        rules=(
            (r".*Attention_\d+/(q|k|v)/kernel", spec(None, axis, None)),
            (r".*Attention_\d+/gate/kernel", cols(axis)),
            (r".*Attention_\d+/o/kernel", rows(axis)),
            (r".*(mlp|shared)/(gate|up)/kernel", cols(axis)),
            (r".*(mlp|shared)/down/kernel", rows(axis)),
            (r".*moe/w_(gate|up)", spec(None, None, axis)),
            (r".*moe/w_down", spec(None, axis, None)),
            _CATCH_ALL,
        ),
        batch_axes=("data",))


def granite_hybrid_rules(axis: str = "model") -> RuleTable:
    """The Megatron split for `models/granite_hybrid.py`'s parameter
    paths: the attention layer's q, k and v column-parallel over their
    heads (the K/V heads are fewer: the axis must divide THEIR count)
    and `o_proj` row-parallel; every SwiGLU split over its width. The
    Mamba-2 mixer replicates whole: its `in_proj` lays z, x, B, C and
    dt side by side in one kernel, so a split of its columns would cut
    across them, and a split by heads needs the kernel cut by segment
    first. Norms, the embedding (the tied head) replicate."""
    return RuleTable(
        name=f"granite_hybrid[{axis}]",
        rules=(
            (r".*self_attn/(q|k|v)_proj/kernel", spec(None, axis, None)),
            (r".*self_attn/o_proj/kernel", rows(axis)),
            (r".*shared_mlp/(gate|up)/kernel", cols(axis)),
            (r".*shared_mlp/down/kernel", rows(axis)),
            _CATCH_ALL,
        ),
        batch_axes=("data",))


def gpt_pp_rules(axis: str = "pipe",
                 tp_axis: Optional[str] = None) -> RuleTable:
    """Stage-stacked pipeline placement for the STACKED half of
    `models.gpt.stack_gpt_blocks`: every leaf carries leading
    [num_stages, layers_per_stage] axes (the ``Block_k`` scope is
    stripped by the stacking), and the stage dim shards over the pipe
    axis — so the catch-all here is ``stacked(axis)``, not
    replication. With ``tp_axis`` the Megatron split composes in:
    each tp rule's spec gains the two leading stage entries (the
    dp x tp x pp family as ONE table; scope-free patterns are safe
    because the vocab head lives in the outer tree, never here)."""
    if tp_axis is None:
        body: Tuple = ()
    else:
        body = (
            (r".*(query|key|value).*kernel",
             spec(axis, None, None, tp_axis, None)),
            (r".*out.*kernel", spec(axis, None, tp_axis, None, None)),
            (r".*Dense_0.*kernel", spec(axis, None, None, tp_axis)),
            (r".*Dense_1.*kernel", spec(axis, None, tp_axis, None)),
            (r".*(query|key|value).*bias",
             spec(axis, None, tp_axis, None)),
            (r".*Dense_0.*bias", spec(axis, None, tp_axis)),
        )
    return RuleTable(
        name=(f"gpt_pp[{axis}]" if tp_axis is None
              else f"gpt_pp[{axis}x{tp_axis}]"),
        rules=body + (
            # every stacked block leaf: leading stage dim over the axis
            (r".*", stacked(axis)),
        ),
        batch_axes=())


def gpt_serve_rules(axis: str = "model") -> RuleTable:
    """The decode tier's placement (docs/serving.md): the Megatron
    block split — GSPMD propagates the head sharding into the KV
    tensors and inserts the ICI collectives, the standard TPU serving
    layout — with embeddings/logits head replicated via the
    catch-all (serving vocab sizes rarely divide a model axis, and
    decode reads the whole head every token anyway). A table of its
    own, not an alias of ``gpt_tp``: training and serving layouts
    evolve independently (serving has no optimizer tree, and a future
    KV-sharded layout lands HERE), and registering it keeps the
    shard-rule-coverage/mesh passes gating the serving plan like
    every other family's."""
    return RuleTable(
        name=f"gpt_serve[{axis}]",
        rules=_megatron_rules("Block", axis) + (_CATCH_ALL,),
        batch_axes=("data",))


def moe_ep_rules(axis: str = "expert") -> RuleTable:
    """Expert-parallel placement of `parallel.expert.MoEParams`
    global views: expert stacks split their leading expert dim over
    the axis, the router replicates everywhere (it must be identical
    for routing to agree)."""
    return RuleTable(
        name=f"moe_ep[{axis}]",
        rules=(
            # no catch-all: a MoEParams global view is EXACTLY these
            # three leaves — anything else reaching this table is a
            # wrong-tree bug that must raise, not silently replicate
            (r".*router", replicated()),
            (r".*w_(up|down)", spec(axis, None, None)),
        ),
        batch_axes=(axis,))


def seq_sp_rules(data_axis: str = "data",
                 seq_axis: str = "seq") -> RuleTable:
    """Sequence-parallel activation placement: params replicate (the
    mixers in `parallel/sequence.py` shard the SEQUENCE, not the
    weights); the batch spec carries the [B, T] token layout — rows
    over data, positions over seq."""
    return RuleTable(
        name=f"seq_sp[{data_axis}x{seq_axis}]",
        rules=(_CATCH_ALL,),
        batch_axes=(data_axis, seq_axis),
        axes=(data_axis, seq_axis))


def token_spec(table: RuleTable) -> PartitionSpec:
    """[B, T, ...] token placement from a table's batch axes: one mesh
    axis per leading dim (the seq-parallel layout); single-axis tables
    shard rows only."""
    return spec(*table.batch_axes)


# -- the registry: tables as statically checkable data ------------------------


@dataclass(frozen=True)
class RegisteredTable:
    """One table + the model trees and mesh shapes it is checked
    against. ``template()`` returns ``{leaf path: shape}`` for a
    representative tree of the family (the MULTICHIP dryrun shapes —
    abstract init only, no FLOPs); ``mesh_shapes`` are the mesh
    families the table may be instantiated on (the shard-rule-mesh
    pass proves axis existence + divisibility on every one)."""

    table: RuleTable
    template: Callable[[], Dict[str, Tuple[int, ...]]]
    mesh_shapes: Tuple[Mapping[str, int], ...]


REGISTRY: Dict[str, RegisteredTable] = {}


def register(name: str, table: RuleTable,
             template: Callable[[], Dict[str, Tuple[int, ...]]],
             mesh_shapes: Sequence[Mapping[str, int]]) -> None:
    """Register a table for static verification. Idempotent per name
    (re-registration replaces — tables are derived data)."""
    REGISTRY[name] = RegisteredTable(
        table=table, template=template,
        mesh_shapes=tuple(dict(m) for m in mesh_shapes))


def _tree_template(tree) -> Dict[str, Tuple[int, ...]]:
    return {path_str(p): tuple(np.shape(leaf)) for p, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


@lru_cache(maxsize=8)
def _template_bert() -> Dict[str, Tuple[int, ...]]:
    """The MULTICHIP tensor-parallel dryrun BERT (heads=4, inter=64:
    both divide the 2-way model axis)."""
    import jax.numpy as jnp

    from ..models import BertConfig, BertEncoder

    cfg = BertConfig(vocab_size=64, hidden_size=32, num_layers=1,
                     num_heads=4, intermediate_size=64, max_position=8,
                     dtype=jnp.float32)
    shapes = jax.eval_shape(BertEncoder(cfg).init,
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    return _tree_template(shapes["params"])


@lru_cache(maxsize=8)
def _template_gpt(num_experts: int = 0) -> Dict[str, Tuple[int, ...]]:
    """The MULTICHIP dp x tp dryrun GPT (vocab 251 — deliberately
    non-divisible, covered by the catch-all, never by a sharding
    rule)."""
    import jax.numpy as jnp

    from ..models import GPTConfig, GPTLM

    cfg = GPTConfig(vocab_size=251, hidden_size=128, num_layers=2,
                    num_heads=4, intermediate_size=256, max_position=32,
                    dtype=jnp.float32, num_experts=num_experts)
    shapes = jax.eval_shape(GPTLM(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 32), jnp.int32))
    return _tree_template(shapes["params"])


@lru_cache(maxsize=8)
def _template_moe_params() -> Dict[str, Tuple[int, ...]]:
    """The expert-parallel dryrun global view (E=4, so any declared
    2-way expert axis divides). A dict, not `expert.MoEParams`:
    NamedTuples flatten to positional paths, and the table matches by
    NAME — the global-view trees the dryrun builds are dicts too."""
    hidden, ffn, experts = 16, 32, 4
    tree = {
        "router": np.zeros((hidden, experts), np.float32),
        "w_up": np.zeros((experts, hidden, ffn), np.float32),
        "w_down": np.zeros((experts, ffn, hidden), np.float32),
    }
    return _tree_template(tree)


@lru_cache(maxsize=8)
def _template_gpt_stacked(stages: int = 2) -> Dict[str, Tuple[int, ...]]:
    """The stacked half of `stack_gpt_blocks` at the dryrun GPT
    shapes — what `gpt_pp_rules` places (leading [stage, layer]
    axes, Block scope stripped)."""
    import jax.numpy as jnp

    from ..models import GPTConfig, GPTLM
    from ..models.gpt import stack_gpt_blocks

    cfg = GPTConfig(vocab_size=251, hidden_size=128, num_layers=stages,
                    num_heads=4, intermediate_size=256, max_position=32,
                    dtype=jnp.float32)
    params = jax.eval_shape(GPTLM(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 32), jnp.int32))["params"]
    stacked_half = jax.eval_shape(
        lambda p: stack_gpt_blocks(p, stages)[1], params)
    return _tree_template(stacked_half)


@lru_cache(maxsize=8)
def _template_glm_moe() -> Dict[str, Tuple[int, ...]]:
    """`models/glm_moe.py` at a size whose heads and widths a 2-way
    model axis divides: one dense block, one expert block holding 2 of
    8 experts, the MTP module. Imported here and not at the top: the
    registry is checked lazily, and no other model's import pays for
    this one."""
    import jax.numpy as jnp

    from ..models.glm_moe import GlmMoeConfig, GlmMoeLM

    cfg = GlmMoeConfig(
        vocab_size=251, hidden_size=64, num_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8,
        v_head_dim=32, intermediate_size=160, moe_intermediate_size=48,
        n_routed_experts=8, num_experts_per_tok=2, num_layers=2,
        held=(0, 2), dtype=jnp.float32)
    shapes = jax.eval_shape(GlmMoeLM(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 16), jnp.int32))
    return _tree_template(shapes["params"])


@lru_cache(maxsize=8)
def _template_ouro() -> Dict[str, Tuple[int, ...]]:
    """`models/ouro.py` at a size whose heads and width a 2-way model
    axis divides: two blocks, the exit gate, the untied head. Imported
    here and not at the top, as `_template_glm_moe`."""
    import jax.numpy as jnp

    from ..models.ouro import OuroConfig, OuroLM

    cfg = OuroConfig(vocab_size=251, hidden_size=64, num_heads=4,
                     head_dim=16, intermediate_size=160, num_layers=2,
                     dtype=jnp.float32)
    shapes = jax.eval_shape(OuroLM(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 16), jnp.int32))
    return _tree_template(shapes["params"])


@lru_cache(maxsize=8)
def _template_afmoe() -> Dict[str, Tuple[int, ...]]:
    """`models/afmoe.py` at a size whose heads (4 on 2 K/V heads) and
    widths a 2-way model axis divides: a dense sliding block and an
    expert full block holding 2 of 8 experts. Imported here and not at
    the top, as `_template_glm_moe`."""
    import jax.numpy as jnp

    from ..models.afmoe import FULL, SLIDING, AfmoeConfig, AfmoeLM

    cfg = AfmoeConfig(
        vocab_size=251, hidden_size=64, num_heads=4, num_kv_heads=2,
        head_dim=16, sliding_window=8, layer_types=(SLIDING, FULL),
        num_dense_layers=1, intermediate_size=160,
        moe_intermediate_size=48, n_routed_experts=8,
        num_experts_per_tok=2, held=(0, 2), dtype=jnp.float32)
    shapes = jax.eval_shape(AfmoeLM(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 16), jnp.int32))
    return _tree_template(shapes["params"])


@lru_cache(maxsize=8)
def _template_granite_hybrid() -> Dict[str, Tuple[int, ...]]:
    """`models/granite_hybrid.py` at a size whose heads (4 on 2 K/V
    heads) and widths a 2-way model axis divides: a Mamba-2 block and
    an attention block. Imported here and not at the top, as
    `_template_glm_moe`."""
    import jax.numpy as jnp

    from ..models.granite_hybrid import (ATTENTION, MAMBA,
                                         GraniteHybridConfig,
                                         GraniteHybridLM)

    cfg = GraniteHybridConfig(
        vocab_size=251, hidden_size=64, num_heads=4, num_kv_heads=2,
        layer_types=(MAMBA, ATTENTION), intermediate_size=160,
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
        mamba_chunk_size=16, dtype=jnp.float32)
    shapes = jax.eval_shape(GraniteHybridLM(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 16), jnp.int32))
    return _tree_template(shapes["params"])


def _register_builtin_tables() -> None:
    """The shipped model-family tables at the MULTICHIP dryrun shapes
    — what `python -m kungfu_tpu.analysis` statically verifies."""
    register("bert_tp", bert_tp_rules(),
             _template_bert,
             [{"data": 4, "model": 2}, {"data": 2, "model": 2},
              {"data": 1, "model": 2}])
    register("gpt_tp", gpt_tp_rules(),
             _template_gpt,
             [{"data": 4, "model": 2}, {"data": 2, "model": 2},
              # the restore-on-mesh target family: no data axis at all
              {"model": 2, "pipe": 2}])
    register("gpt_moe", gpt_moe_rules(),
             lambda: _template_gpt(4),
             [{"data": 4, "model": 2}, {"data": 2, "model": 2}])
    register("moe_ep", moe_ep_rules(),
             _template_moe_params,
             [{"expert": 2}, {"expert": 4}])
    register("seq_sp", seq_sp_rules(),
             _template_bert,
             [{"data": 2, "seq": 4}, {"data": 2, "seq": 2}])
    register("gpt_pp", gpt_pp_rules(),
             _template_gpt_stacked,
             [{"pipe": 2}, {"pipe": 2, "model": 2}])
    register("gpt_pp_tp", gpt_pp_rules(tp_axis="model"),
             _template_gpt_stacked,
             # the dp x tp x pp family ROADMAP item 3 names
             [{"data": 2, "model": 2, "pipe": 2},
              {"model": 2, "pipe": 2}])
    register("glm_moe", glm_moe_rules(),
             _template_glm_moe,
             [{"data": 4, "model": 2}, {"data": 1, "model": 2},
              {"data": 1, "model": 1}])
    register("ouro", ouro_rules(),
             _template_ouro,
             [{"data": 4, "model": 2}, {"data": 1, "model": 2},
              {"data": 1, "model": 1}])
    register("afmoe", afmoe_rules(),
             _template_afmoe,
             [{"data": 4, "model": 2}, {"data": 1, "model": 2},
              {"data": 1, "model": 1}])
    register("granite_hybrid", granite_hybrid_rules(),
             _template_granite_hybrid,
             [{"data": 4, "model": 2}, {"data": 1, "model": 2},
              {"data": 1, "model": 1}])
    register("gpt_serve", gpt_serve_rules(),
             _template_gpt,
             # decode's (1, tp) serving mesh and the dp-replicated
             # serving family (kungfu_tpu/serve, benchmarks/lm.py
             # --decode --tp)
             [{"data": 1, "model": 2}, {"data": 2, "model": 2}])


_register_builtin_tables()


def _table_universe(table: RuleTable) -> Tuple[str, ...]:
    """A table's full axis universe: rule axes + batch axes — ONE
    source of truth (the table itself), so a batch_axes change can
    never drift from what the axis-consistency pass declares."""
    return table.axes + tuple(a for a in table.batch_axes
                              if a not in table.axes)


#: table constructor -> its default axis universe, exported for the
#: axis-consistency pass: a module that builds its mesh specs from a
#: rules table declares the table's axes without re-stating them as
#: string literals (specs-as-data; the literal path stays as
#: fallback). Derived from the table objects, never hand-listed.
TABLE_AXES: Dict[str, Tuple[str, ...]] = {
    f.__name__: _table_universe(f())
    for f in (bert_tp_rules, gpt_tp_rules, gpt_moe_rules,
              glm_moe_rules, ouro_rules, afmoe_rules,
              granite_hybrid_rules, gpt_pp_rules,
              moe_ep_rules,
              seq_sp_rules,
              gpt_serve_rules)
}


# -- shard_params: the one placement entry point ------------------------------


def shard_params(params, mesh: Mesh, rules: Rules):
    """Place every parameter on `mesh` per the first matching rule.

    With a :class:`RuleTable` the plan is validated first (coverage +
    axis existence + divisibility raise :class:`PlanError` at plan
    time); a legacy pairs sequence keeps the lenient contract
    (unmatched leaves replicate, nothing validates) so pre-engine call
    sites behave bit-identically."""
    if isinstance(rules, RuleTable):
        specs = plan(rules, params, dict(mesh.shape))
    else:
        specs = match_partition_rules(rules, params)
    return place(params, mesh, specs)
