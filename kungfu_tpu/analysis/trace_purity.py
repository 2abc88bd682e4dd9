"""trace-purity: no host impurity inside jitted/shard_mapped steps.

Chaos replay (round 7) re-executes a recorded fault schedule against a
deterministic training step: same seeds, same trace, same compiled
program. That determinism dies quietly the day someone traces a wall
clock, host RNG, or host synchronization into a step function — the
program still runs, but the traced value is frozen at compile time (a
``time.time()`` constant baked into the graph) or forces a blocking
device round-trip per step (``.item()``), and replay diverges from the
recording.

The pass finds functions that are jit boundaries — decorated with
``@jax.jit`` / ``@partial(jax.jit, ...)``, wrapped as ``jax.jit(f)``,
or used as a ``shard_map`` body — and flags, anywhere inside:

- wall clocks: ``time.time/perf_counter/monotonic/process_time``
- host RNG: ``np.random.*``, ``random.*`` (use ``jax.random`` with an
  explicit key)
- host sync: ``jax.device_get``, ``.item()``, ``.tolist()``,
  ``.block_until_ready()``
- tracer leaks where derivable: ``float(x)`` / ``int(x)`` / ``bool(x)``
  over a traced parameter, and Python ``if``/``while`` branching on a
  traced parameter (static metadata — ``.ndim`` / ``.shape`` /
  ``.dtype`` / ``len()`` — and ``is None`` checks are exempt; params
  named by ``static_argnames``/``static_argnums`` literals are not
  tracers and are exempt too)
- kftrace recorder calls: ``trace.span`` / ``trace.event`` /
  ``trace.complete`` / ``trace.flight_dump`` /
  ``trace.set_context`` (any ``trace``/``kftrace`` module prefix).
  A recorder call inside a jitted body runs at TRACE time — it
  records one event at compile, then never again — and the wall
  clocks inside `span` would be frozen constants. Instrumentation
  wraps the CALL SITE of a compiled step, never its body
  (docs/observability.md).
"""

from __future__ import annotations

import ast
from typing import List, Optional, Sequence, Set

from .core import Finding, Source, call_name, scoped_calls

NAME = "trace-purity"

_CLOCK_CALLS = {
    "time.time", "time.perf_counter", "time.monotonic",
    "time.process_time", "time.time_ns", "time.perf_counter_ns",
}
_HOST_SYNC_ATTRS = {"item", "tolist", "block_until_ready"}
_JIT_NAMES = {"jit", "jax.jit", "pjit", "jax.pjit"}
_SHARD_MAP_NAMES = {"shard_map", "jax.shard_map"}
_STATIC_ATTRS = {"ndim", "shape", "dtype", "size", "sharding"}
_CASTS = {"float", "int", "bool"}
#: kftrace recorder entry points (kungfu_tpu/trace/__init__.py) — any
#: dotted call whose module segment is trace/kftrace and whose final
#: segment is one of these fires inside a jit/shard_map body
_RECORDER_FUNCS = {"span", "event", "complete", "flight_dump",
                   "set_context"}
_RECORDER_MODULES = {"trace", "kftrace"}


def _is_recorder_call(cn: Optional[str]) -> bool:
    if not cn or "." not in cn:
        return False
    parts = cn.split(".")
    return (parts[-1] in _RECORDER_FUNCS
            and parts[-2] in _RECORDER_MODULES)


def _is_jit_expr(node: ast.AST) -> bool:
    """True for `jax.jit`, `jit`, or `partial(jax.jit, ...)`."""
    name = call_name(node) if isinstance(node, ast.Call) else None
    if isinstance(node, (ast.Name, ast.Attribute)):
        from .core import dotted_name

        return dotted_name(node) in _JIT_NAMES
    if isinstance(node, ast.Call):
        if name in ("functools.partial", "partial") and node.args:
            return _is_jit_expr(node.args[0])
        return name in _JIT_NAMES
    return False


def _static_params(call: Optional[ast.Call]) -> Set[str]:
    """Literal static_argnames from a jit call expression (argnums are
    resolved by position later)."""
    names: Set[str] = set()
    if call is None:
        return names
    for k in call.keywords:
        if k.arg == "static_argnames":
            for n in ast.walk(k.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, str):
                    names.add(n.value)
    return names


def _static_argnums(call: Optional[ast.Call]) -> Set[int]:
    nums: Set[int] = set()
    if call is None:
        return nums
    for k in call.keywords:
        if k.arg == "static_argnums":
            for n in ast.walk(k.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, int):
                    nums.add(n.value)
    return nums


def _tracer_params(fn: ast.AST, jit_call: Optional[ast.Call]) -> Set[str]:
    a = fn.args
    params = [p.arg for p in a.posonlyargs + a.args]
    static_names = _static_params(jit_call)
    for i in _static_argnums(jit_call):
        if 0 <= i < len(params):
            static_names.add(params[i])
    return {p for p in params if p not in static_names}


def _collect_jit_bodies(tree: ast.AST):
    """(function node, jit-call-or-None) for every jit boundary in the
    module: decorated defs, `jax.jit(f)` / `shard_map(f, ...)` over a
    local def, and jitted/shard_mapped lambdas. Call-form body names
    resolve scope-aware (core.scoped_calls) — several builders in one
    module each define a local `device_step`, and a module-wide
    last-wins map would silently skip all but one of them."""
    out = []
    seen = set()

    def add(fn, jit_call):
        if id(fn) not in seen:
            seen.add(id(fn))
            out.append((fn, jit_call))

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _is_jit_expr(dec):
                    add(node, dec if isinstance(dec, ast.Call) else None)

    def wraps_body(call: ast.Call) -> bool:
        cn = call_name(call)
        return bool(call.args) and (
            _is_jit_expr(call.func) or cn in _JIT_NAMES
            or cn in _SHARD_MAP_NAMES)

    for call, visible in scoped_calls(tree, wraps_body):
        target = call.args[0]
        cn = call_name(call)
        is_jit = _is_jit_expr(call.func) or cn in _JIT_NAMES
        jc = None
        if is_jit:
            # partial(jax.jit, static_argnames=...)(fn): the static
            # markers live on the INNER partial call, not the outer
            # application whose keywords are empty
            jc = (call.func if isinstance(call.func, ast.Call)
                  else call)
        if isinstance(target, ast.Lambda):
            add(target, jc)
        elif isinstance(target, ast.Name) and target.id in visible:
            add(visible[target.id], jc)
    return out


def _references_tracer(node: ast.AST, tracers: Set[str]) -> Optional[str]:
    """The first traced parameter referenced in ``node`` other than
    through static metadata (x.ndim / x.shape / x.dtype / len(x)) or
    an `is None` check; None when the expression is trace-safe."""

    def scan(n: ast.AST, parent: Optional[ast.AST]) -> Optional[str]:
        if isinstance(n, ast.Name) and n.id in tracers:
            if (isinstance(parent, ast.Attribute)
                    and parent.attr in _STATIC_ATTRS):
                return None
            if (isinstance(parent, ast.Call)
                    and isinstance(parent.func, ast.Name)
                    and parent.func.id == "len"):
                return None
            return n.id
        if isinstance(n, ast.Compare):
            ops_none = all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in n.ops)
            comparators_none = all(
                isinstance(c, ast.Constant) and c.value is None
                for c in n.comparators)
            if ops_none and comparators_none:
                return None  # `x is None`: x is then NOT a tracer
        for child in ast.iter_child_nodes(n):
            hit = scan(child, n)
            if hit:
                return hit
        return None

    return scan(node, None)


class TracePurityPass:
    name = NAME
    doc = ("wall clocks, host RNG, host sync, and derivable tracer "
           "leaks inside jit/shard_map step functions")

    def run(self, src: Source) -> List[Finding]:
        findings: List[Finding] = []
        for fn, jit_call in _collect_jit_bodies(src.tree):
            tracers = _tracer_params(fn, jit_call)
            findings.extend(self._check_body(src, fn, tracers))
        return findings

    def _check_body(self, src: Source, fn: ast.AST,
                    tracers: Set[str]) -> List[Finding]:
        findings: List[Finding] = []

        def add(node, msg):
            f = src.finding(node, NAME, msg)
            if f:
                findings.append(f)

        body: Sequence[ast.AST] = (
            [fn.body] if isinstance(fn, ast.Lambda) else fn.body)
        for stmt in body:
            for node in ast.walk(stmt):
                if isinstance(node, ast.Call):
                    cn = call_name(node)
                    if _is_recorder_call(cn):
                        add(node, f"kftrace recorder call {cn}() "
                                  "inside a jitted step records at "
                                  "trace time, not per step — wrap "
                                  "the call site of the compiled "
                                  "step instead")
                    elif cn in _CLOCK_CALLS:
                        add(node, f"{cn}() is frozen into the trace at "
                                  "compile time — wall clocks cannot "
                                  "live inside a jitted step")
                    elif cn and (cn.startswith("np.random.")
                                 or cn.startswith("numpy.random.")
                                 or cn.startswith("random.")):
                        add(node, f"host RNG {cn}() inside a jitted step "
                                  "breaks chaos-replay determinism — "
                                  "use jax.random with an explicit key")
                    elif cn in ("jax.device_get", "device_get"):
                        add(node, "jax.device_get inside a jitted step "
                                  "forces a host round-trip per step")
                    elif (isinstance(node.func, ast.Attribute)
                          and node.func.attr in _HOST_SYNC_ATTRS
                          and not node.args):
                        add(node, f".{node.func.attr}() inside a jitted "
                                  "step synchronizes with the host — "
                                  "return the value instead")
                    elif (isinstance(node.func, ast.Name)
                          and node.func.id in _CASTS
                          and len(node.args) == 1):
                        hit = _references_tracer(node.args[0], tracers)
                        if hit:
                            add(node,
                                f"{node.func.id}() over traced value "
                                f"{hit!r} — concretizes a tracer (host "
                                "sync or trace error)")
                elif isinstance(node, (ast.If, ast.While)):
                    hit = _references_tracer(node.test, tracers)
                    if hit:
                        add(node,
                            f"Python branching on traced value {hit!r} "
                            "— use lax.cond/jnp.where, or mark the "
                            "argument static")
        return findings
