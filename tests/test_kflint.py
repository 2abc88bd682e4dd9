"""kflint fixture suite: every pass fires on its positive fixture,
stays quiet on its negative twin, and the tree itself lints clean.

Fixtures are inline source strings (not files under kungfu_tpu/, which
would trip the tree-wide assertion) run through `run_source`, the same
entry point the CLI uses per file — so a pass that regresses to
never-firing fails here before it silently waves hazards through.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from kungfu_tpu.analysis import (all_passes, run_paths,
                                 run_project_texts, run_source)
from kungfu_tpu.analysis.axis_consistency import AxisConsistencyPass
from kungfu_tpu.analysis.lock_discipline import LockDisciplinePass
from kungfu_tpu.analysis.retry_discipline import RetryDisciplinePass
from kungfu_tpu.analysis.trace_purity import TracePurityPass
from kungfu_tpu.analysis.unused_imports import UnusedImportsPass
from kungfu_tpu.analysis import vmem_budget
from kungfu_tpu.analysis.protocol import (CollectiveOrderPass,
                                          LockOrderPass,
                                          SchedulePurityPass,
                                          StrategyGraphPass,
                                          WireNameDeterminismPass)
from kungfu_tpu.analysis.protocol import explore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "kungfu_tpu")


def fire(pass_obj, src):
    return run_source(pass_obj, textwrap.dedent(src))


def fire_project(pass_obj, **texts):
    return run_project_texts(
        pass_obj, {path: textwrap.dedent(src)
                   for path, src in texts.items()})


# -- retry-discipline --------------------------------------------------------


def test_retry_fires_on_bare_and_broad_except():
    findings = fire(RetryDisciplinePass(), """
        def poll():
            try:
                step()
            except:
                pass

        def poll2():
            try:
                step()
            except Exception:
                return None
    """)
    assert len(findings) == 2
    assert all(f.pass_name == "retry-discipline" for f in findings)


def test_retry_fires_on_raw_urlopen():
    findings = fire(RetryDisciplinePass(), """
        import urllib.request

        def fetch(url):
            return urllib.request.urlopen(url).read()
    """)
    assert len(findings) == 1
    assert "urlopen" in findings[0].message


def test_retry_quiet_on_narrow_reraise_del_and_disable():
    findings = fire(RetryDisciplinePass(), """
        def narrow():
            try:
                step()
            except (OSError, ValueError):
                pass

        def cleanup_then_propagate():
            try:
                step()
            except Exception:
                undo()
                raise

        class C:
            def __del__(self):
                try:
                    self.close()
                except Exception:
                    pass

        def justified():
            try:
                step()
            # kflint: disable=retry-discipline
            except Exception:
                pass
    """)
    assert findings == []


def test_retry_fires_when_raise_is_only_in_a_nested_def():
    # a `raise` inside a function merely DEFINED by the handler runs
    # later (if ever) — the handler itself still swallows
    findings = fire(RetryDisciplinePass(), """
        def swallow_but_define(cbs):
            try:
                step()
            except Exception:
                def cb():
                    raise
                cbs.append(cb)
    """)
    assert len(findings) == 1


def test_trace_call_form_partial_static_argnames():
    # partial(jax.jit, static_argnames=...)(fn): the static markers
    # live on the inner partial call — `causal` is NOT a tracer
    findings = fire(TracePurityPass(), """
        import functools
        import jax

        def masked(x, causal):
            if causal:
                return x * 2
            return x

        step = functools.partial(
            jax.jit, static_argnames=("causal",))(masked)
    """)
    assert findings == []


def test_retry_quiet_on_wrap_and_propagate():
    findings = fire(RetryDisciplinePass(), """
        def translate():
            try:
                step()
            except Exception as e:
                raise RuntimeError("step failed") from e
    """)
    assert findings == []


def test_disable_marker_does_not_leak_to_next_line():
    findings = fire(RetryDisciplinePass(), """
        import urllib.request

        def two_fetches(url):
            a = urllib.request.urlopen(url)  # kflint: disable=retry-discipline
            b = urllib.request.urlopen(url)
            return a, b
    """)
    assert len(findings) == 1  # only the UNjustified second call


# -- axis-consistency --------------------------------------------------------


def test_axis_fires_on_undeclared_literal_axis():
    findings = fire(AxisConsistencyPass(), """
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def body(x):
            return lax.psum(x, "modle")  # typo

        def build(mesh):
            return jax.shard_map(body, mesh=mesh,
                                 in_specs=(P("model"),),
                                 out_specs=P("model"))
    """)
    assert len(findings) == 1
    assert "modle" in findings[0].message


def test_axis_fires_on_spec_arity_mismatch():
    findings = fire(AxisConsistencyPass(), """
        import jax
        from jax.sharding import PartitionSpec as P

        def body(x, y):
            return x + y

        def build(mesh):
            return jax.shard_map(body, mesh=mesh,
                                 in_specs=(P("data"), P("data"), P()),
                                 out_specs=P("data"))
    """)
    assert len(findings) == 1
    assert "3 spec(s)" in findings[0].message


def test_axis_quiet_on_matching_and_dynamic_names():
    findings = fire(AxisConsistencyPass(), """
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def body(x):
            return lax.psum(x, "data")

        def build(mesh):
            return jax.shard_map(body, mesh=mesh,
                                 in_specs=(P("data"),),
                                 out_specs=P("data"))

        def dyn_body(x, axis_name):
            return lax.psum(x, axis_name)  # dynamic: never guessed
    """)
    assert findings == []


def test_axis_fires_on_partial_wrapped_body():
    """shard_map(partial(body, ...), ...) must resolve THROUGH the
    partial: a bad literal axis inside the wrapped body, a bad literal
    bound to axis_name=, and the partial-adjusted arity all fire."""
    findings = fire(AxisConsistencyPass(), """
        import functools
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def body(x, bucket_bytes):
            return lax.psum(x, "modle")  # typo, behind the partial

        def build(mesh):
            return jax.shard_map(
                functools.partial(body, bucket_bytes=1024), mesh=mesh,
                in_specs=(P("model"),), out_specs=P("model"))

        def body2(x, axis_name):
            return lax.psum(x, axis_name)

        def build2(mesh):
            return jax.shard_map(
                functools.partial(body2, axis_name="modle"), mesh=mesh,
                in_specs=(P("model"),), out_specs=P("model"))

        def body3(x, y, bucket_bytes):
            return x + y

        def build3(mesh):
            return jax.shard_map(
                functools.partial(body3, bucket_bytes=4), mesh=mesh,
                in_specs=(P("data"),), out_specs=P("data"))
    """)
    assert len(findings) == 3
    assert "modle" in findings[0].message
    assert "axis_name" in findings[1].message
    assert "after partial binding" in findings[2].message


def test_axis_quiet_on_partial_wrapped_body():
    findings = fire(AxisConsistencyPass(), """
        import functools
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def body(x, axis_name, bucket_bytes):
            return lax.psum(x, axis_name)

        def build(mesh):
            return jax.shard_map(
                functools.partial(body, axis_name="data",
                                  bucket_bytes=1024),
                mesh=mesh, in_specs=(P("data"),), out_specs=P("data"))

        def splat(mesh, kw):
            # **kwargs splat: arity underivable, never guessed
            return jax.shard_map(functools.partial(body, **kw),
                                 mesh=mesh, in_specs=(P("data"),),
                                 out_specs=P("data"))

        def kwonly(x, *, bucket_bytes):
            return lax.psum(x, "data")

        def build_kwonly(mesh):
            # binding a KEYWORD-ONLY param must not shrink the
            # positional arity (x still matches the one spec)
            return jax.shard_map(
                functools.partial(kwonly, bucket_bytes=64), mesh=mesh,
                in_specs=(P("data"),), out_specs=P("data"))
    """)
    assert findings == []


# -- trace-purity ------------------------------------------------------------


def test_trace_fires_on_clock_rng_and_item():
    findings = fire(TracePurityPass(), """
        import time
        import numpy as np
        import jax

        @jax.jit
        def step(params, batch):
            t0 = time.time()
            noise = np.random.normal(size=3)
            loss = (params * batch).sum()
            return loss.item() + t0 + noise
    """)
    kinds = " ".join(f.message for f in findings)
    assert len(findings) == 3
    assert "time.time" in kinds and "np.random" in kinds \
        and ".item()" in kinds


def test_trace_fires_on_branching_on_tracer():
    findings = fire(TracePurityPass(), """
        import jax

        @jax.jit
        def step(x):
            if x > 0:
                return x
            return -x
    """)
    assert len(findings) == 1
    assert "branching" in findings[0].message


def test_trace_fires_on_recorder_call_in_jit_body():
    # PR 11's rule: kftrace recorder calls inside a compiled body
    # record at trace time (and would bake frozen wall clocks into the
    # program) — instrumentation wraps the call site only
    findings = fire(TracePurityPass(), """
        import jax
        from kungfu_tpu import trace

        @jax.jit
        def step(params, batch):
            with trace.span("step.compute", cat="step"):
                loss = (params * batch).sum()
            trace.event("step.done")
            return loss
    """)
    assert len(findings) == 2, findings
    assert all("kftrace recorder" in f.message for f in findings)
    msgs = " ".join(f.message for f in findings)
    assert "trace.span" in msgs and "trace.event" in msgs


def test_trace_quiet_on_recorder_at_call_site():
    findings = fire(TracePurityPass(), """
        import jax
        from kungfu_tpu import trace

        @jax.jit
        def step(params, batch):
            return (params * batch).sum()

        def train_loop(params, batch):
            with trace.span("step.compute", cat="step"):
                loss = step(params, batch)
            trace.event("step.done")
            return loss
    """)
    assert findings == []


def test_trace_quiet_on_static_metadata_and_statics():
    findings = fire(TracePurityPass(), """
        import functools
        import jax

        @jax.jit
        def shape_static(x):
            if x.ndim == 3:
                return x.sum(axis=0)
            return x

        @functools.partial(jax.jit, static_argnames=("causal",))
        def masked(x, causal):
            if causal:
                return x * 2
            return x

        def host_side(x):
            return float(x)  # not a jit boundary: host code may sync
    """)
    assert findings == []


def test_trace_resolves_duplicate_body_names_per_scope():
    # two builders each with a local `device_step` (the real pattern in
    # parallel/train.py): the impurity in the FIRST one must still fire
    # — a module-wide last-wins name map would silently skip it
    findings = fire(TracePurityPass(), """
        import time
        import jax

        def build_a(mesh):
            def device_step(x):
                return x * time.time()  # impure, in builder A's body
            return jax.shard_map(device_step, mesh=mesh)

        def build_b(mesh):
            def device_step(x):
                return x * 2  # clean twin in builder B
            return jax.shard_map(device_step, mesh=mesh)
    """)
    assert len(findings) == 1
    assert "time.time" in findings[0].message


# -- lock-discipline ---------------------------------------------------------


def test_lock_fires_on_unlocked_write():
    findings = fire(LockDisciplinePass(), """
        import threading

        class Server:
            def __init__(self):
                self._lock = threading.Lock()
                self._stage = None  # kf: guarded_by(_lock)

            def put(self, stage):
                self._stage = stage  # missing lock!
    """)
    assert len(findings) == 1
    assert "_stage" in findings[0].message


def test_lock_fires_on_unlocked_container_mutation_and_global():
    findings = fire(LockDisciplinePass(), """
        import threading

        _mu = threading.Lock()
        _subs = []  # kf: guarded_by(_mu)

        def subscribe(cb):
            _subs.append(cb)  # missing lock!

        class Pool:
            def __init__(self):
                self._mu = threading.Lock()
                self._free = []  # kf: guarded_by(_mu)

            def put(self, x):
                self._free.append(x)  # missing lock!
    """)
    assert len(findings) == 2


def test_lock_quiet_on_locked_writes_and_init():
    findings = fire(LockDisciplinePass(), """
        import threading

        _mu = threading.Lock()
        _active = None  # kf: guarded_by(_mu)

        def install(s):
            global _active
            with _mu:
                _active = s

        class Server:
            def __init__(self):
                self._lock = threading.Lock()
                self._stage = None  # kf: guarded_by(_lock)

            def put(self, stage):
                with self._lock:
                    self._stage = stage
    """)
    assert findings == []


def test_lock_fires_on_global_written_from_class_method():
    findings = fire(LockDisciplinePass(), """
        import threading

        _mu = threading.Lock()
        _subs = []  # kf: guarded_by(_mu)

        class Bus:
            def subscribe(self, cb):
                _subs.append(cb)  # missing lock!
    """)
    assert len(findings) == 1
    assert "_subs" in findings[0].message


def test_lock_fires_in_closure_defined_under_the_lock():
    # a callback defined INSIDE `with self._lock:` runs later, on
    # whatever thread invokes it — the definition-time lock holds
    # nothing at call time (the ffi trampoline / monitor tick pattern)
    findings = fire(LockDisciplinePass(), """
        import threading

        class Group:
            def __init__(self):
                self._mu = threading.Lock()
                self._errors = []  # kf: guarded_by(_mu)

            def register(self, fn):
                with self._mu:
                    def cb(e):
                        self._errors.append(e)  # unlocked at call time
                    self.cb = cb
    """)
    assert len(findings) == 1
    assert "_errors" in findings[0].message


def test_lock_instance_lock_cannot_satisfy_module_guard():
    findings = fire(LockDisciplinePass(), """
        import threading

        _mu = threading.Lock()
        _active = None  # kf: guarded_by(_mu)

        class Engine:
            def __init__(self):
                self._mu = threading.Lock()  # same NAME, different lock

            def disarm(self):
                global _active
                with self._mu:
                    _active = None  # module _mu NOT held!
    """)
    assert len(findings) == 1
    assert "_active" in findings[0].message


def test_lock_quiet_on_local_shadowing_a_guarded_global():
    findings = fire(LockDisciplinePass(), """
        import threading

        _mu = threading.Lock()
        _subs = []  # kf: guarded_by(_mu)

        def local_twin():
            _subs = []     # binds a LOCAL: not the guarded global
            _subs.append(1)
            return _subs

        def real_write():
            global _subs
            with _mu:
                _subs = []
    """)
    assert findings == []


# -- unused-imports ----------------------------------------------------------


def test_unused_imports_fires():
    findings = fire(UnusedImportsPass(), """
        import os
        import sys

        print(sys.argv)
    """)
    assert len(findings) == 1
    assert "'os'" in findings[0].message


def test_unused_imports_quiet_on_use_noqa_and_all():
    findings = fire(UnusedImportsPass(), """
        import os
        import compat  # noqa: F401
        from x import exported

        __all__ = ["exported"]
        print(os.sep)
    """)
    assert findings == []


# -- vmem-budget -------------------------------------------------------------


def test_vmem_fires_under_tiny_budget():
    # a 1 MB budget: the real plans cannot fit, so the pass must fire —
    # this is the "pass demonstrably fires" guard for the model pass
    findings = vmem_budget.check_flash(budget=1 * 2**20)
    findings += vmem_budget.check_fused_ce(budget=1 * 2**20)
    assert findings, "vmem pass silent even under an impossible budget"
    assert all("VMEM estimate" in f.message for f in findings)


def test_vmem_quiet_on_real_budget():
    assert vmem_budget.check_flash() == []
    assert vmem_budget.check_fused_ce() == []


@pytest.mark.parametrize("cell,pair,squeezed", [
    # the glm cell: 40 MiB, its pair on the streaming grid
    ((8192, 256, "bfloat16", True, None), "stream", 15 * 2**20),
    # the ouro cell (PR 33): 24 MiB, its pair on the resident loops; the
    # kernel at the call's 1024 x 512 would still fit 15 MiB
    ((4096, 128, "bfloat16", True, None), "resident", 8 * 2**20),
], ids=["glm-cell", "ouro-cell"])
def test_vmem_holds_the_fused_flash_backward_to_the_limit_it_states(
        monkeypatch, cell, pair, squeezed):
    """A cell's call is in the grid, and its ONE backward kernel (a
    whole head's f32 dq in VMEM, far over the 15 MiB budget of every
    other flash kernel) is held to `_BWD_STREAM_VMEM_LIMIT`, the
    `vmem_limit_bytes` it hands Mosaic: quiet as the tree stands, and
    named once that limit is under its estimate."""
    from kungfu_tpu.ops import flash

    t, d, dtype, causal, _ = cell
    assert cell in vmem_budget.FLASH_GRID
    assert vmem_budget.check_flash(grid=[cell]) == []
    bwd = flash.flash_plan(t, d, dtype=dtype, causal=causal)["bwd"]
    assert bwd["scheme"] == "stream_fused"
    assert (flash._VMEM_BUDGET < bwd["vmem_bytes"]
            <= flash._BWD_STREAM_VMEM_LIMIT)
    monkeypatch.setattr(flash, "_BWD_STREAM_VMEM_LIMIT", squeezed)
    # the engage rule reads the same limit: no tile of the fused kernel
    # fits now, the call falls to the pair, and the pass stays quiet ...
    assert flash.flash_plan(t, d, dtype=dtype, causal=causal)[
        "bwd"]["scheme"] == pair
    assert vmem_budget.check_flash(grid=[cell]) == []
    # ... and a kernel that engaged anyway is what it names
    monkeypatch.setattr(flash, "_bwd_stream_tiles",
                        lambda *a: (1024, 1024))
    (finding,) = vmem_budget.check_flash(grid=[cell])
    assert "flash bwd plan" in finding.message
    assert "stream_fused" in finding.message


def test_vmem_paged_decode_fires_under_tiny_budget():
    # the serving decode kernel's plan grid rides the same contract:
    # an impossible budget must surface as lint, not a Mosaic OOM
    findings = vmem_budget.check_paged(budget=1 * 2**20)
    assert findings, "paged vmem pass silent under an impossible budget"
    assert all("VMEM estimate" in f.message
               and "paged_attn.py" in f.path for f in findings)


def test_vmem_paged_decode_quiet_on_real_budget():
    # includes the 8k-context point where the RESIDENT scheme cannot
    # fit: the plan must have degraded (stream or functional), never
    # returned an over-budget pick
    assert vmem_budget.check_paged() == []


# -- kfverify: wire-name-determinism -----------------------------------------

#: the PR 5 joiner deadlock, regression-encoded: an instance counter
#: (`self._round`) flows into the bucket wire name THROUGH a closure
#: (`tag` -> `nm`) and a method parameter (`_make_slot(nm)`) — three
#: frames from the collective, invisible to any per-file pass
PR5_FIXTURE = """
    class Pipe:
        def __init__(self, peer):
            self.peer = peer
            self.name = "kf::grad"
            self._round = 0

        def all_reduce(self, grads, step=None):
            if step is None:
                step = self._round   # the bug: joiner counts from 0
                self._round += 1
            tag = f"{self.name}:{self.peer.version}:{step}"

            def pack(k):
                nm = f"{tag}:b{k}"
                slot = self._make_slot(k, nm)
                slot()

            for k in range(4):
                pack(k)

        def _make_slot(self, k, nm):
            peer = self.peer

            def slot():
                peer.all_reduce_inplace(grads_buf, op="sum", name=nm)

            return slot
"""


def test_wire_name_fires_on_pr5_joiner_counter():
    findings = fire_project(WireNameDeterminismPass(),
                            **{"grad.py": PR5_FIXTURE})
    assert findings, "the PR 5 deadlock fixture MUST fire"
    msgs = " ".join(f.message for f in findings)
    assert "local counter 'self._round'" in msgs
    assert "_make_slot" in msgs  # found through the parameter flow


def test_wire_name_fires_on_rank_and_clock():
    findings = fire_project(WireNameDeterminismPass(), **{"w.py": """
        import time

        def sync(peer, buf):
            peer.all_reduce(buf, name=f"g:{peer.rank}")

        def sync2(peer, buf):
            t = time.monotonic()
            peer.broadcast(buf, name=f"m:{t}")
    """})
    kinds = " ".join(f.message for f in findings)
    assert len(findings) == 2
    assert "rank" in kinds and "time.monotonic" in kinds


def test_wire_name_quiet_on_agreed_sources():
    findings = fire_project(WireNameDeterminismPass(), **{"w.py": """
        class State:
            def __init__(self):
                # kf: cluster-agreed — re-synced via the max all-reduce
                self.step = 0

            def advance(self):
                self.step += 1

        def sync(peer, state, bufs):
            for k, b in enumerate(bufs):
                peer.all_reduce(
                    b, name=f"g:{peer.version}:{state.step}:b{k}")
    """})
    assert findings == []


def test_wire_name_agreed_annotation_is_class_local():
    # an annotation on ONE class's counter must not whitelist another
    # class's same-named counter (found in review: bare-name matching
    # let an annotated ElasticState.step exempt every `step` tree-wide)
    findings = fire_project(WireNameDeterminismPass(), **{"state.py": """
        class State:
            def __init__(self):
                # kf: cluster-agreed — re-synced via max all-reduce
                self.step = 0

            def advance(self):
                self.step += 1
    """, "pipe.py": """
        class Pipe:
            def __init__(self):
                self.step = 0

            def all_reduce(self, peer, buf):
                self.step += 1
                peer.all_reduce(buf, name=f"g:{self.step}")
    """})
    assert len(findings) == 1
    assert findings[0].path == "pipe.py"
    assert "local counter 'self.step'" in findings[0].message


def test_wire_name_checks_call_sites_of_name_params():
    # the name itself is a clean parameter; ONE call site feeds it a
    # pid — the finding must land at that call site, not the wrapper
    findings = fire_project(WireNameDeterminismPass(), **{"a.py": """
        def wrapped(peer, buf, name):
            peer.all_reduce(buf, name=name)
    """, "b.py": """
        import os

        from a import wrapped

        def good(peer, buf):
            wrapped(peer, buf, "g:0")

        def bad(peer, buf):
            wrapped(peer, buf, f"g:{os.getpid()}")
    """})
    assert len(findings) == 1
    assert findings[0].path == "b.py"
    assert "os.getpid" in findings[0].message


def test_wire_name_fires_on_env_subscript_and_percent_format():
    # review regression: os.environ["X"] subscripts and %-formatted
    # names were left opaque and slipped the gate silently
    findings = fire_project(WireNameDeterminismPass(), **{"w.py": """
        import os

        class Pipe:
            def __init__(self):
                self._round = 0

            def sync(self, peer, buf):
                peer.all_reduce(buf, name=os.environ["KF_NAME"])
                self._round += 1
                peer.broadcast(buf, name="b%d" % self._round)
    """})
    kinds = " ".join(f.message for f in findings)
    assert len(findings) == 2
    assert "env read" in kinds
    assert "local counter 'self._round'" in kinds


def test_wire_name_fires_through_format_join_and_str():
    # review regression: .format() on a LITERAL receiver, and
    # join/str assembly, must be followed like an f-string
    findings = fire_project(WireNameDeterminismPass(), **{"w.py": """
        def a(peer, buf):
            peer.all_reduce(buf, name="g:{}".format(peer.rank))

        def b(peer, buf):
            peer.broadcast(buf, name=":".join(["g", str(peer.rank)]))
    """})
    assert len(findings) == 2
    assert all("rank" in f.message for f in findings)


def test_wire_name_fires_on_bare_imported_collective():
    # review regression: a from-imported collective with an explicit
    # name= must be judged like the method form
    findings = fire_project(WireNameDeterminismPass(), **{"w.py": """
        from peerlib import all_reduce

        def sync(peer, g):
            all_reduce(g, name=f"grad:{peer.rank}")
    """})
    assert len(findings) == 1
    assert "rank" in findings[0].message


def test_marker_in_string_literal_is_inert():
    # review regression: marker syntax inside a STRING must neither
    # create a phantom guard nor whitelist a counter
    findings = fire(LockDisciplinePass(), """
        import threading

        _mu = threading.Lock()
        HELP = "annotate with  # kf: guarded_by(_mu)  on the line"

        def set_help(s):
            global HELP
            HELP = s
    """)
    assert findings == []


def test_lock_global_guard_ignores_nonlocal_shadow():
    # review regression: `nonlocal` can never bind a module global —
    # a same-named closure variable shadows, not shares
    findings = fire(LockDisciplinePass(), """
        import threading

        _mu = threading.Lock()
        _active = []  # kf: guarded_by(_mu)

        def outer():
            _active = []

            def inner():
                nonlocal _active
                _active.append(1)  # outer's local, not the global

            inner()
            return _active
    """)
    assert findings == []


def test_wire_name_quiet_on_id_accessor_methods():
    # review regression: bare `id` in the inventory must match the
    # builtin exactly, not every accessor method named .id()
    findings = fire_project(WireNameDeterminismPass(), **{"w.py": """
        def sync(peer, job, buf):
            peer.all_reduce(buf, name=f"slot:{job.id()}")

        def bad(peer, buf):
            peer.all_reduce(buf, name=f"slot:{id(buf)}")
    """})
    assert len(findings) == 1
    assert "'id'" in findings[0].message or " id" in findings[0].message


def test_wire_name_ignores_one_sided_store_ops():
    # save/request legitimately key by rank (per-peer model slots)
    findings = fire_project(WireNameDeterminismPass(), **{"w.py": """
        def publish(peer, buf):
            peer.save(f"model:{peer.rank}", buf)
            peer.request((peer.rank + 1) % peer.size,
                         f"model:{peer.rank}", buf)
    """})
    assert findings == []


# -- kfverify: collective-order ----------------------------------------------


def _order_pass(path="w.py", qual=None):
    return CollectiveOrderPass(entries={"fixture": (path, qual)})


def test_collective_order_fires_on_rank_gated_collective():
    findings = fire_project(_order_pass(qual="step"), **{"w.py": """
        def step(peer, buf):
            if peer.rank == 0:
                peer.broadcast(buf, name="m")
            return buf
    """})
    assert len(findings) == 1
    assert "rank-dependent test" in findings[0].message


def test_collective_order_fires_through_call_chain():
    # the divergent branch calls a HELPER whose callee runs the
    # collective — the finding lands at the gated call site
    findings = fire_project(_order_pass(qual="step"), **{"w.py": """
        def _sync(peer, buf):
            peer.all_reduce(buf, name="g")

        def helper(peer, buf):
            _sync(peer, buf)

        def step(peer, buf):
            if peer.local_rank == 0:
                helper(peer, buf)
    """})
    assert findings and "rank-dependent test" in findings[0].message


def test_collective_order_fires_on_clock_bounded_loop():
    findings = fire_project(_order_pass(qual="recover"), **{"w.py": """
        import time

        def recover(peer, deadline):
            while time.monotonic() < deadline:
                peer.barrier()
    """})
    assert len(findings) == 1
    assert "clock-bounded loop" in findings[0].message


def test_collective_order_quiet_on_schedule_loops():
    findings = fire_project(_order_pass(qual="step"), **{"w.py": """
        def step(peer, chunks):
            for ci, spans in enumerate(chunks):
                peer.broadcast_inplace(spans, name=f"c{ci}")
            for k in range(8):
                peer.all_reduce(k, name=f"b{k}")
            peer.barrier()
    """})
    assert findings == []


def test_collective_order_fails_loudly_on_renamed_entry():
    # a present file missing the named entry function is a rename
    # regression — silently skipping it would un-gate the path
    findings = fire_project(_order_pass(qual="no_such_fn"), **{"w.py": """
        def step(peer, buf):
            peer.barrier()
    """})
    assert len(findings) == 1
    assert "no longer exists" in findings[0].message


def test_wire_name_fires_on_positional_name_argument():
    # review regression: a rank-derived name passed POSITIONALLY
    # through a resolvable signature must be judged like a name= kwarg
    findings = fire_project(WireNameDeterminismPass(), **{"p.py": """
        class Peer:
            def all_reduce(self, x, op="sum", name=""):
                return x
    """, "u.py": """
        def sync(peer, buf):
            peer.all_reduce(buf, "sum", f"g:{peer.rank}")
    """})
    assert len(findings) == 1
    assert findings[0].path == "u.py"
    assert "rank" in findings[0].message


def test_stale_suppression_flags_dead_half_of_multi_pass_disable(
        tmp_path):
    p = tmp_path / "half.py"
    p.write_text(textwrap.dedent("""
        def f():
            try:
                g()
            # kflint: disable=retry-discipline,trace-purity
            except Exception:
                pass
    """))
    findings = run_paths([str(tmp_path)])
    stale = [f for f in findings if f.pass_name == "stale-suppression"]
    assert len(stale) == 1
    # only the dead half is flagged; the live retry half still vouches
    assert "trace-purity" in stale[0].message
    assert "retry-discipline" not in stale[0].message


def test_collective_order_extracts_sequences():
    p = _order_pass(qual="step")
    fire_project(p, **{"w.py": """
        def _inner(peer, buf):
            peer.all_reduce(buf, name="g")

        def step(peer, buf):
            peer.consensus(buf, name="kf::resize")
            _inner(peer, buf)
            peer.barrier()
    """})
    ops = [s.op for s in p.sequences["fixture"]]
    assert ops == ["consensus", "all_reduce", "barrier"]


# -- kfverify: schedule-purity -----------------------------------------------


def test_schedule_purity_fires_on_env_and_value_reads():
    findings = fire_project(SchedulePurityPass(), **{"s.py": """
        import os

        import numpy as np

        def chunk_bytes_from_env():
            return int(os.getenv("CHUNK_MB", "4")) * 2**20

        def biggest(grads):
            return float(np.max(grads[0]))

        def stream(tree, grads):
            return chunk_schedule(tree, chunk_bytes_from_env())

        def stream2(tree, grads):
            return bucket_schedule(tree, biggest(grads))
    """})
    msgs = " ".join(f.message for f in findings)
    assert len(findings) == 2
    assert "env read" in msgs and "tensor-value read" in msgs


def test_schedule_purity_reports_env_subscript_once():
    # review regression: os.environ["X"] is one hazard, not two
    # findings (the Subscript and its Attribute base both matched)
    findings = fire_project(SchedulePurityPass(), **{"s.py": """
        import os

        def from_env():
            return int(os.environ["KF_CHUNK"]) * 2**20

        def stream(tree):
            return chunk_schedule(tree, from_env())
    """})
    assert len(findings) == 1
    assert "os.environ[...]" in findings[0].message


def test_schedule_purity_reports_env_get_once():
    # review regression: os.environ.get() matched both the Call branch
    # and its inner os.environ Attribute — one hazard, one finding
    findings = fire_project(SchedulePurityPass(), **{"s.py": """
        import os

        def from_env():
            return int(os.environ.get("KF_CHUNK", "4")) * 2**20

        def stream(tree):
            return chunk_schedule(tree, from_env())
    """})
    assert len(findings) == 1
    assert "os.environ.get()" in findings[0].message


def test_schedule_purity_fires_on_shard_schedule_feeder():
    """The checkpoint shard scheduler is a schedule function too: an
    env read feeding its chunk size at call time means per-rank owner
    maps — a checkpoint that looks complete but cannot restore."""
    findings = fire_project(SchedulePurityPass(), **{"s.py": """
        import os

        def chunk_from_env():
            return int(os.getenv("KF_CKPT_CHUNK_MB", "4")) * 2**20

        def save(tree, nprocs):
            return shard_schedule(tree, chunk_from_env(), nprocs)
    """})
    assert len(findings) == 1
    assert "shard_schedule" in findings[0].message
    assert "env read" in findings[0].message


def test_schedule_purity_quiet_on_shard_schedule_shape_feeder():
    findings = fire_project(SchedulePurityPass(), **{"s.py": """
        import os

        import numpy as np

        def from_env():
            return int(os.getenv("KF_CKPT_CHUNK_MB", "4")) * 2**20

        def spans_bytes(tree):
            return int(np.prod(np.shape(tree[0]))) * 4

        class Ckpt:
            def __init__(self, tree, nprocs):
                # construction-time env read: uniform for the
                # object's lifetime (AsyncShardedCheckpointer's rule)
                self._sched = shard_schedule(tree, from_env(), nprocs)

        def save(tree, nprocs):
            return shard_schedule(tree, spans_bytes(tree), nprocs)
    """})
    assert findings == []


def test_schedule_purity_quiet_on_init_and_shapes():
    findings = fire_project(SchedulePurityPass(), **{"s.py": """
        import os

        import numpy as np

        def from_env():
            return int(os.getenv("CHUNK_MB", "4")) * 2**20

        def shape_bytes(tree):
            return int(np.prod(np.shape(tree[0])))

        class Pipe:
            def __init__(self, tree):
                # construction-time env read: uniform for the object's
                # lifetime, exactly like GradBucketPipeline
                self._schedule = bucket_schedule(tree, from_env())

        def stream(tree):
            return chunk_schedule(tree, shape_bytes(tree))
    """})
    assert findings == []


def test_schedule_purity_fires_on_impure_scenario_compiler():
    """The scenario->ChaosSchedule compiler is a schedule function
    (every rank replays the plan from its own env copy): a clock or
    env read inside the lowering means two ranks replay DIFFERENT
    traces — the same divergence class as a per-rank chunk layout."""
    findings = fire_project(SchedulePurityPass(), **{"s.py": """
        import os
        import time

        def compile_scenario(scenario):
            jitter = time.time() % 1.0
            lead = int(os.getenv("KF_LEAD_STEPS", "1"))
            return {"faults": [{"type": "preempt_warning",
                                "step": int(jitter * 10) + lead}]}
    """})
    msgs = " ".join(f.message for f in findings)
    assert len(findings) == 2
    assert "compile_scenario" in msgs
    assert "nondeterministic call" in msgs and "env read" in msgs


def test_schedule_purity_fires_on_scenario_compiler_feeder():
    # the argument side: a spec materialized from the environment at
    # call time feeds the compiler — two ranks may compile different
    # plans even though the lowering itself is pure
    findings = fire_project(SchedulePurityPass(), **{"s.py": """
        import os

        def spec_from_env():
            return {"steps": int(os.environ["KF_STEPS"])}

        def replay():
            spec = spec_from_env()
            return compile_scenario(spec)
    """})
    assert len(findings) == 1
    assert "compile_scenario" in findings[0].message
    assert "env read" in findings[0].message


def test_schedule_purity_quiet_on_pure_scenario_compiler():
    # the shape the real compiler has: plan derived from the spec's
    # fields alone (kungfu_tpu/scenario/compiler.py)
    findings = fire_project(SchedulePurityPass(), **{"s.py": """
        def compile_scenario(scenario):
            faults = []
            for ev in scenario["events"]:
                if ev["kind"] == "preempt":
                    faults.append({"type": "crash_worker",
                                   "step": int(ev["step"])})
            return {"seed": int(scenario.get("seed", 0)),
                    "faults": faults}

        def replay(spec):
            return compile_scenario(spec)
    """})
    assert findings == []


# -- kfverify: strategy-graph ------------------------------------------------


def test_strategy_graph_fires_on_rank_divergent_generator():
    # the acceptance fixture (ISSUE 13): a topology generator that
    # consults "who am I" builds per-rank graphs — rank A waits on an
    # edge rank B never drew, a deadlock with no error message
    findings = fire_project(StrategyGraphPass(), **{"topo.py": """
        import os
        import socket

        def gen_fast_tree(peers, cfg):
            g = Graph(len(peers))
            me = cfg.rank
            for r in range(len(peers)):
                if r != me:
                    g.add_edge(me, r)
            return g

        def gen_host_ring(peers):
            g = Graph(len(peers))
            first = socket.gethostname()
            return g, first

        def gen_tuned_star(peers):
            k = len(peers)
            root = int(os.environ.get("KF_ROOT", "0"))
            g = Graph(k)
            for i in range(k):
                if i != root:
                    g.add_edge(root, i)
            return g
    """})
    msgs = " ".join(f.message for f in findings)
    assert len(findings) == 3
    assert "rank-identity read .rank" in msgs
    assert "host-identity call socket.gethostname()" in msgs
    assert "env read" in msgs
    assert all(f.pass_name == "strategy-graph" for f in findings)


def test_strategy_graph_fires_on_clock_in_generator():
    findings = fire_project(StrategyGraphPass(), **{"topo.py": """
        import time

        def gen_rotating_ring(peers):
            g = Graph(len(peers))
            r = int(time.time()) % len(peers)
            for i in range(1, len(peers)):
                g.add_edge((r + i - 1) % g.n, (r + i) % g.n)
            return g
    """})
    assert len(findings) == 1
    assert "nondeterministic call" in findings[0].message


def test_strategy_graph_quiet_on_replica_pure_generator():
    # the shipped shape: graphs from the PeerList replica alone;
    # PeerList.rank(q) as a METHOD CALL is the pure peer->index map
    findings = fire_project(StrategyGraphPass(), **{"topo.py": """
        def _local_masters(peers):
            masters, host_master = [], {}
            for rank, p in enumerate(peers):
                if p.ipv4 not in host_master:
                    host_master[p.ipv4] = rank
                    masters.append(rank)
            return masters, host_master

        def gen_tree(peers):
            g = Graph(len(peers))
            masters, host_master = _local_masters(peers)
            for rank, p in enumerate(peers):
                if host_master[p.ipv4] != rank:
                    g.add_edge(host_master[p.ipv4], rank)
            for m in masters[1:]:
                g.add_edge(masters[0], m)
            return g

        def gen_rooted_star(peers, root_peer):
            root = peers.rank(root_peer)
            g = Graph(len(peers))
            for i in range(len(peers)):
                if i != root:
                    g.add_edge(root, i)
            return g
    """})
    assert findings == []


def test_strategy_graph_quiet_on_shipped_tree():
    # the real generators (plan/topology.py + friends) must stay clean
    findings = [f for f in run_paths([PKG])
                if f.pass_name == "strategy-graph"]
    assert findings == []


# -- kfverify: lock-order ----------------------------------------------------


def test_lock_order_fires_on_ab_ba_cycle():
    findings = fire_project(LockOrderPass(), **{"l.py": """
        import threading

        _a = threading.Lock()
        _b = threading.Lock()

        def one():
            with _a:
                with _b:
                    pass

        def two():
            with _b:
                with _a:
                    pass
    """})
    assert len(findings) == 1
    assert "lock-order cycle" in findings[0].message
    assert "_a" in findings[0].message and "_b" in findings[0].message


def test_lock_order_fires_across_modules_via_calls():
    findings = fire_project(LockOrderPass(), **{"m1.py": """
        import threading

        import m2

        _a = threading.Lock()

        def outer():
            with _a:
                m2.inner()
    """, "m2.py": """
        import threading

        import m1

        _b = threading.Lock()

        def inner():
            with _b:
                pass

        def reverse():
            with _b:
                m1.outer()
    """})
    cycles = [f for f in findings if "lock-order cycle" in f.message]
    assert len(cycles) == 1
    # the fixture also contains a real secondary hazard the pass must
    # see: reverse -> outer -> inner re-acquires _b while held
    assert any("re-acquisition" in f.message for f in findings)


def test_lock_order_fires_on_self_deadlock():
    findings = fire_project(LockOrderPass(), **{"l.py": """
        import threading

        class Engine:
            def __init__(self):
                self._mu = threading.Lock()

            def tick(self):
                with self._mu:
                    self.flush()

            def flush(self):
                with self._mu:
                    pass
    """})
    assert len(findings) == 1
    assert "re-acquisition" in findings[0].message


def test_lock_order_quiet_on_consistent_order_and_rlock():
    findings = fire_project(LockOrderPass(), **{"l.py": """
        import threading

        _a = threading.Lock()
        _b = threading.Lock()
        _r = threading.RLock()

        def one():
            with _a:
                with _b:
                    pass

        def two():
            with _a:
                with _b:
                    pass

        def reent():
            with _r:
                again()

        def again():
            with _r:
                pass

        def submitter(pool):
            with _b:
                pool.submit(one)  # worker runs WITHOUT _b: no edge
    """})
    assert findings == []


# -- kfverify: the small-scope explorer --------------------------------------


def test_explorer_extracts_template_from_real_pipeline():
    slots = explore._default_slots()
    kinds = [k for k, _ in slots]
    assert explore.EPOCH_F in kinds
    assert explore.STEP_F in kinds
    assert explore.BUCKET_F in kinds


def test_explorer_reproduces_pr5_divergence_trace():
    slots = explore._default_slots()
    bad = explore.explore_epoch_switch("local-counter", slots)
    assert bad, "the PR 5 binding must diverge"
    trace = bad[0].trace()
    # two ranks offering DIFFERENT names for the same bucket slot
    offers = set(bad[0].offers.values())
    assert len(offers) == 2
    assert all(o.endswith(":b0") for o in offers)
    assert "divergence" in trace and "offers" in trace


def test_explorer_agreed_binding_completes_every_interleaving():
    slots = explore._default_slots()
    assert explore.explore_epoch_switch("agreed", slots) == []


def test_explorer_lockstep_reports_exhausted_rank():
    d = explore.check_lockstep({0: ["a", "b"], 1: ["a"]})
    assert d is not None and d.at == 1
    assert d.offers[1] is None  # rank 1 exhausted: rank 0 hangs


# -- lock-discipline: closure-local guarded state ----------------------------


def test_lock_closure_fires_on_unlocked_nested_write():
    findings = fire(LockDisciplinePass(), """
        import threading

        def pipeline(n):
            mu = threading.Lock()
            flats = [None] * n  # kf: guarded_by(mu)

            def fetch(i):
                flats[i] = i  # missing lock!

            return fetch
    """)
    assert len(findings) == 1
    assert "flats" in findings[0].message


def test_lock_closure_quiet_on_locked_defining_and_shadow():
    findings = fire(LockDisciplinePass(), """
        import threading

        def pipeline(n):
            mu = threading.Lock()
            flats = [None] * n  # kf: guarded_by(mu)
            flats[0] = 0        # defining scope: pre-thread, exempt

            def fetch(i):
                with mu:
                    flats[i] = i

            def shadow(i):
                flats = []      # local twin: not the shared closure
                flats.append(i)

            return fetch
    """)
    assert findings == []


# -- stale-suppression audit + CLI JSON/baseline -----------------------------


def test_stale_suppression_flagged(tmp_path):
    live = tmp_path / "live.py"
    live.write_text(textwrap.dedent("""
        def f():
            try:
                g()
            # kflint: disable=retry-discipline
            except Exception:
                pass
    """))
    stale = tmp_path / "stale.py"
    stale.write_text(textwrap.dedent("""
        def f():
            # kflint: disable=retry-discipline
            return 1

        def g():
            return 2  # kflint: disable=no-such-pass
    """))
    findings = run_paths([str(tmp_path)])
    stale_f = [f for f in findings
               if f.pass_name == "stale-suppression"]
    assert len(stale_f) == 2
    msgs = " ".join(f.message for f in stale_f)
    assert "no longer matches" in msgs
    assert "unknown pass" in msgs
    assert all(f.path == str(stale) for f in stale_f)


def test_disable_inside_string_literal_is_inert(tmp_path):
    # a STRING mentioning the marker must neither suppress findings on
    # its line nor register as a stale suppression
    p = tmp_path / "s.py"
    p.write_text('MSG = "justify with # kflint: disable=retry-'
                 'discipline"\n')
    findings = run_paths([str(p)])
    assert [f for f in findings
            if f.pass_name == "stale-suppression"] == []


def test_cli_json_ids_are_stable(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def f():\n    try:\n        g()\n    except:\n"
                   "        pass\n")
    r = subprocess.run(
        [sys.executable, "-m", "kungfu_tpu.analysis", str(bad),
         "--select", "retry-discipline", "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 1
    doc = json.loads(r.stdout)
    assert doc["count"] == 1
    fid = doc["findings"][0]["id"]
    pass_name, path, line, digest = fid.rsplit(":", 3)
    assert pass_name == "retry-discipline"
    assert path.endswith("bad.py") and line == "4"
    assert len(digest) == 8
    # stable: a second run yields the identical id
    r2 = subprocess.run(
        [sys.executable, "-m", "kungfu_tpu.analysis", str(bad),
         "--select", "retry-discipline", "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert json.loads(r2.stdout)["findings"][0]["id"] == fid


def test_cli_baseline_gates_on_new_findings_only(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def f():\n    try:\n        g()\n    except:\n"
                   "        pass\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    # full-suite runs: the baseline is a full-run artifact (--select
    # with --baseline is rejected, see the mutual-exclusion test)
    run = [sys.executable, "-m", "kungfu_tpu.analysis", str(bad)]
    r = subprocess.run(run + ["--json"], cwd=REPO, capture_output=True,
                       text=True, timeout=120, env=env)
    fid = json.loads(r.stdout)["findings"][0]["id"]
    baseline = tmp_path / "baseline.json"
    # the committed-debt case: finding in baseline -> exit 0
    baseline.write_text(json.dumps({"version": 1, "ids": [fid]}))
    r = subprocess.run(run + ["--baseline", str(baseline)], cwd=REPO,
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 0, r.stderr
    assert "no new findings" in r.stderr
    # the regression case: empty baseline -> exit 1, NEW reported
    baseline.write_text(json.dumps({"version": 1, "ids": []}))
    r = subprocess.run(run + ["--baseline", str(baseline)], cwd=REPO,
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 1
    assert "NEW finding(s)" in r.stderr
    # the fixed case: baseline lists a gone finding -> reported, exit 0
    baseline.write_text(json.dumps({"version": 1,
                                    "ids": [fid, "gone:x.py:1:deadbeef"]}))
    r = subprocess.run(run + ["--baseline", str(baseline)], cwd=REPO,
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 0
    assert "1 baseline finding(s) fixed" in r.stderr


def test_baseline_diff_survives_line_shifts():
    # review regression: a pure line shift (import added above a
    # baselined finding) must not turn committed debt into a NEW gate
    # failure — but a SECOND instance of the same hazard must
    from kungfu_tpu.analysis.__main__ import diff_baseline

    new, fixed = diff_baseline(
        {"retry-discipline:foo.py:121:abcd1234"},
        {"retry-discipline:foo.py:120:abcd1234"})
    assert new == set() and fixed == set()
    new, fixed = diff_baseline(
        {"retry-discipline:foo.py:121:abcd1234",
         "retry-discipline:foo.py:300:abcd1234"},
        {"retry-discipline:foo.py:120:abcd1234"})
    assert len(new) == 1 and fixed == set()
    new, fixed = diff_baseline(
        set(), {"retry-discipline:foo.py:120:abcd1234"})
    assert new == set()
    assert fixed == {"retry-discipline:foo.py:120:abcd1234"}


def test_cli_select_and_baseline_are_mutually_exclusive(tmp_path):
    # review regression: a subset run diffed against the full-run
    # baseline reports every other pass's IDs as "fixed" and invites a
    # baseline regeneration that breaks the next full run
    p = tmp_path / "ok.py"
    p.write_text("x = 1\n")
    b = tmp_path / "b.json"
    b.write_text('{"version": 1, "ids": []}')
    r = subprocess.run(
        [sys.executable, "-m", "kungfu_tpu.analysis", str(p),
         "--select", "retry-discipline", "--baseline", str(b)],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 2
    assert "mutually exclusive" in r.stderr


def test_cli_errors_on_missing_or_corrupt_baseline(tmp_path):
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    run = [sys.executable, "-m", "kungfu_tpu.analysis", str(ok)]
    r = subprocess.run(run + ["--baseline", str(tmp_path / "no.json")],
                       cwd=REPO, capture_output=True, text=True,
                       timeout=120, env=env)
    assert r.returncode == 2  # unreadable baseline must not green CI
    assert "cannot read baseline" in r.stderr
    # a truncated/corrupted write (valid JSON, wrong shape) must hit
    # the same diagnostic, not an uncaught traceback
    corrupt = tmp_path / "corrupt.json"
    corrupt.write_text("null")
    r = subprocess.run(run + ["--baseline", str(corrupt)], cwd=REPO,
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 2
    assert "cannot read baseline" in r.stderr


def test_stale_audit_skips_single_file_spot_checks():
    # review regression: the interprocedural passes need the files a
    # suppression's call chain crosses — a single-file invocation must
    # not flag the tree's deliberate suppressions as stale
    findings = run_paths([os.path.join(PKG, "peer.py")])
    assert [f for f in findings
            if f.pass_name == "stale-suppression"] == []


# -- shard-rules (kfspec): hand-rolled specs, rules-backed axes --------------


def test_shard_rules_fires_on_literal_partition_spec():
    from kungfu_tpu.analysis.shard_rules import HandRolledSpecPass

    findings = fire(HandRolledSpecPass(), """
        from jax.sharding import PartitionSpec
        import jax.sharding

        def f():
            a = PartitionSpec("data")
            b = jax.sharding.PartitionSpec(None, "model")
            return a, b
    """)
    assert len(findings) == 2
    assert all("hand-rolled PartitionSpec" in f.message
               for f in findings)


def test_shard_rules_fires_on_aliased_import():
    from kungfu_tpu.analysis.shard_rules import HandRolledSpecPass

    findings = fire(HandRolledSpecPass(), """
        from jax.sharding import PartitionSpec as P

        SPEC = P("data", None)
    """)
    assert len(findings) == 1


def test_shard_rules_quiet_on_engine_helpers_and_rules_module():
    from kungfu_tpu.analysis.shard_rules import HandRolledSpecPass

    # the helpers ARE the migration target: no finding
    assert fire(HandRolledSpecPass(), """
        from kungfu_tpu.parallel.rules import rows, stacked

        def f():
            return stacked("data"), rows("model")
    """) == []
    # the engine module itself is where literals live
    assert run_source(
        HandRolledSpecPass(),
        "from jax.sharding import PartitionSpec\n"
        "X = PartitionSpec('a')\n",
        path="kungfu_tpu/parallel/rules.py") == []


def test_shard_rules_suppression_needs_reason_comment():
    from kungfu_tpu.analysis.shard_rules import HandRolledSpecPass

    assert fire(HandRolledSpecPass(), """
        from jax.sharding import PartitionSpec as P

        def f():
            # kflint: disable=shard-rules — throwaway debug literal
            return P("data")
    """) == []


def test_axis_consistency_resolves_axes_from_rules_table():
    # specs-as-data: the table call declares its axis universe via the
    # live registry (rules.TABLE_AXES), so a collective naming an axis
    # outside it fires even with zero spec literals in the module...
    findings = fire(AxisConsistencyPass(), """
        from jax import lax, shard_map
        from kungfu_tpu.parallel.rules import gpt_tp_rules

        RULES = gpt_tp_rules()

        def build(mesh, specs):
            def body(x):
                return lax.psum(x, "modle")
            return shard_map(body, mesh=mesh, in_specs=specs,
                             out_specs=specs)
    """)
    assert len(findings) == 1
    assert "modle" in findings[0].message


def test_axis_consistency_quiet_on_table_declared_axis():
    # ...and stays quiet when the axis IS in the table's universe
    findings = fire(AxisConsistencyPass(), """
        from jax import lax, shard_map
        from kungfu_tpu.parallel.rules import gpt_tp_rules

        RULES = gpt_tp_rules()

        def build(mesh, specs):
            def body(x):
                return lax.psum(x, "model")
            return shard_map(body, mesh=mesh, in_specs=specs,
                             out_specs=specs)
    """)
    assert findings == []


def test_axis_consistency_literal_fallback_via_helper_args():
    # the literal path survives the rewire: a spec-helper call's
    # string argument declares the axis at the call site
    fire_src = """
        from jax import lax, shard_map
        from kungfu_tpu.parallel.rules import stacked

        def build(mesh):
            def body(x):
                return lax.psum(x, "AXIS")
            return shard_map(body, mesh=mesh,
                             in_specs=(stacked("data"),),
                             out_specs=stacked("data"))
    """
    assert len(fire(AxisConsistencyPass(), fire_src)) == 1
    assert fire(AxisConsistencyPass(),
                fire_src.replace('"AXIS"', '"data"')) == []


def test_schedule_purity_fires_on_impure_rules_table():
    findings = fire_project(SchedulePurityPass(), mod="""
        import os

        def my_rules():
            if os.environ.get("KF_TP_AXIS"):
                return (("a", 1),)
            return (("b", 2),)
    """)
    assert findings
    assert "rules table my_rules()" in findings[0].message


def test_schedule_purity_quiet_on_pure_rules_table():
    assert fire_project(SchedulePurityPass(), mod="""
        def my_rules(axis="model"):
            return ((".*kernel", axis), (".*", None))
    """) == []


def test_stale_shard_rules_suppression_audits(tmp_path):
    # the audit covers the new marker: a `# kflint: disable=shard-rules`
    # that no longer suppresses a live finding is itself a finding
    f = tmp_path / "stale.py"
    f.write_text("# kflint: disable=shard-rules — nothing here\n"
                 "X = 1\n")
    findings = run_paths([str(tmp_path)])
    assert any(x.pass_name == "stale-suppression"
               and "shard-rules" in x.message for x in findings)


def test_schedule_purity_covers_match_partition_rules_feeders():
    findings = fire_project(SchedulePurityPass(), mod="""
        import os

        def match_partition_rules(rules, tree):
            return rules

        def pick_table():
            return os.environ.get("KF_TABLE")

        def derive_plan(tree):
            t = pick_table()
            return match_partition_rules(t, tree)
    """)
    assert findings
    assert any("match_partition_rules() argument fed by "
               "pick_table()" in f.message for f in findings)


# -- suppression / plumbing --------------------------------------------------


def test_skip_file_marker():
    findings = fire(RetryDisciplinePass(), """
        # kflint: skip-file
        def f():
            try:
                g()
            except:
                pass
    """)
    assert findings == []


def test_pass_registry_names_are_unique_and_complete():
    # core.PASS_SPECS is THE registry: the CLI, run_paths and this
    # suite all derive from it, so a pass cannot exist without its
    # CLI/baseline wiring (the old two-list split allowed exactly
    # that silent skip)
    from kungfu_tpu.analysis.core import PASS_SPECS

    passes = all_passes()
    names = [p.name for p in passes]
    assert len(names) == len(set(names))
    assert len(passes) == len(PASS_SPECS)
    assert set(names) >= {"retry-discipline", "axis-consistency",
                          "trace-purity", "vmem-budget",
                          "lock-discipline", "unused-imports",
                          "shard-rules", "shard-rule-coverage",
                          "shard-rule-mesh",
                          "wire-name-determinism", "collective-order",
                          "schedule-purity", "lock-order",
                          "ack-ordering", "term-fence",
                          "handler-exception-safety"}


def test_cli_list_shows_every_registered_pass():
    # --list renders from the same registry; a row missing here means
    # a pass the CLI cannot select or baseline
    r = subprocess.run(
        [sys.executable, "-m", "kungfu_tpu.analysis", "--list"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0
    listed = {line.split()[0] for line in r.stdout.splitlines()
              if line.strip()}
    assert listed == {p.name for p in all_passes()}


# -- the point: the tree itself lints clean ----------------------------------


def test_tree_is_clean():
    findings = run_paths([PKG])
    assert findings == [], "\n".join(str(f) for f in findings)


def test_cli_exits_zero_on_tree():
    r = subprocess.run(
        [sys.executable, "-m", "kungfu_tpu.analysis", "kungfu_tpu/"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-2000:])
    assert "clean" in r.stderr


def test_cli_errors_on_missing_path():
    # a typo'd path must FAIL the gate (exit 2), not green it by
    # checking zero files
    r = subprocess.run(
        [sys.executable, "-m", "kungfu_tpu.analysis", "kungfu_tp/"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 2
    assert "no such path" in r.stderr


def test_cli_exits_nonzero_on_findings(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def f():\n    try:\n        g()\n    except:\n"
                   "        pass\n")
    r = subprocess.run(
        [sys.executable, "-m", "kungfu_tpu.analysis", str(bad),
         "--select", "retry-discipline"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 1
    assert "bare except" in r.stdout
