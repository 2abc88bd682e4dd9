#!/usr/bin/env bash
# The full local CI gate: one command reproduces everything the suite
# checks, mirroring the reference's pipeline (reference:
# .github/workflows/ci.yaml:27-41 — build, unit tests, integration
# sweep, examples) including its np x strategy integration sweep
# (reference: scripts/tests/run-integration-tests.sh:18-40).
#
# Usage: scripts/run-all.sh [--quick]
#   --quick  skip the pytest suite (sweep + examples only)
set -euo pipefail
cd "$(dirname "$0")/.."

QUICK=0
[ "${1:-}" = "--quick" ] && QUICK=1

echo "== [0/7] lint: kflint + kfverify (+ruff/mypy when available) =="
# the tree must pass its own static-analysis suite — the per-file
# kflint passes AND the interprocedural kfverify protocol passes
# (docs/static_analysis.md). The committed JSON baseline makes the
# gate a diff: stable finding IDs, fail only on NEW findings, report
# fixed ones so the baseline can ratchet down. (It is empty today —
# the tree is clean — so this is equivalent to pass/fail until a
# stricter pass lands with debt.)
JAX_PLATFORMS=cpu python -m kungfu_tpu.analysis kungfu_tpu/ \
  --baseline scripts/kflint_baseline.json
# the consensus gate (docs/static_analysis.md "The consensus
# checker"): extract the election/replication machine out of
# replica.py/wal.py (raises on drift), prove the four invariants over
# every 2-3-replica interleaving, and require all 12 incident-shaped
# MUST-FIRE ablations to diverge — through the same stable-ID
# baseline discipline as kflint above
JAX_PLATFORMS=cpu python -m kungfu_tpu.analysis.consensus \
  --baseline scripts/kfconsensus_baseline.json
# pyproject.toml carries the ruff/mypy baselines; the container doesn't
# ship them, so they gate only where installed (dev machines, CI)
if python -c "import ruff" 2>/dev/null; then
  python -m ruff check kungfu_tpu/
elif command -v ruff >/dev/null; then
  ruff check kungfu_tpu/
fi
if python -c "import mypy" 2>/dev/null; then
  python -m mypy --config-file pyproject.toml
fi

echo "== [1/7] native build + C++ smoke =="
make -C kungfu_tpu/native -j"$(nproc)"
make -C kungfu_tpu/native test

echo "== [2/7] sanitize: C++ tidy gate + ASan/UBSan/TSan smoke loops =="
if [ "$QUICK" = 0 ]; then
  scripts/sanitize.sh --rounds 1
else
  echo "   skipped (--quick); run scripts/sanitize.sh for the full matrix"
fi

if [ "$QUICK" = 0 ]; then
  echo "== [3/7] pytest suite =="
  # per-test timeouts need pytest-timeout (CI installs it); locally the
  # suite runs without it rather than failing on the missing plugin
  if python -c "import pytest_timeout" 2>/dev/null; then
    python -m pytest tests/ -q -m "not sanitize" --timeout=900
  else
    timeout 2700 python -m pytest tests/ -q -m "not sanitize"
  fi
else
  echo "== [3/7] pytest suite skipped (--quick) =="
fi

echo "== [4/7] integration sweep: np x strategy =="
# the reference sweeps np=1..4 x all strategies with a per-run timeout
# (run-integration-tests.sh:18-40); same sweep, same fake trainer idea
export JAX_PLATFORMS=cpu
export KF_LOG_LEVEL=warn
export PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}"
for np in 1 2 3 4; do
  for strategy in STAR RING CLIQUE TREE BINARY_TREE BINARY_TREE_STAR \
                  MULTI_BINARY_TREE_STAR AUTO; do
    echo "-- np=$np strategy=$strategy"
    timeout 60 python -m kungfu_tpu.run \
      -np "$np" -H "127.0.0.1:$np" -strategy "$strategy" \
      -port-range 26000-26999 -logdir .kf-ci-logs -q \
      -- python tests/workers/fake_trainer.py \
      || { echo "SWEEP FAILED: np=$np strategy=$strategy"; exit 1; }
  done
done

echo "== [4b/7] gradient-pipeline smoke: 4-peer bucketed + compressed =="
# the per-step DCN gradient path (docs/grad_pipeline.md): reverse-
# backward buckets overlapped with a simulated backward, int8-EF
# compressed wire (scale negotiation + saturating sum) over 4 peers
timeout 180 python -m kungfu_tpu.run \
  -np 4 -H 127.0.0.1:4 -port-range 26000-26999 \
  -logdir .kf-ci-logs -q \
  -- python -m kungfu_tpu.benchmarks.allreduce --grad-worker \
     --model mlp-mnist --steps 2 --warmup 1 --pipeline bucketed \
     --compress int8 --backward-ms 50 --bucket-mb 0.1 \
  || { echo "GRAD PIPELINE SMOKE FAILED"; exit 1; }

echo "== [4c/7] checkpoint smoke: save under training -> whole-cluster kill -> reshard restore =="
# the durable rung of the recovery state machine
# (docs/fault_tolerance.md): async sharded generations land while a
# 4-worker cluster trains, a chaos schedule SIGKILLs every worker at
# one step, and a 2-worker relaunch restores the latest complete
# generation with loss continuity asserted
timeout 300 python - <<'EOF'
import tempfile
from kungfu_tpu.elastic.harness import run_checkpoint_restore
with tempfile.TemporaryDirectory() as d:
    run_checkpoint_restore(d + "/ckpt", save_np=4, restore_np=2,
                           kill_step=9, save_every=2,
                           port_range="26000-26999", timeout=240)
print("CHECKPOINT SMOKE OK")
EOF

# mesh-shape-change restore (kfspec, docs/sharding_rules.md): a
# checkpoint saved under a dp x tp layout restores onto a tp x pp
# mesh via the rules-table spec diff — placement validated at plan
# time, leaf hashes verified by restore_sharded
timeout 120 env JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=4" python - <<'EOF'
import tempfile
import jax, jax.numpy as jnp, numpy as np
from kungfu_tpu import checkpoint_async as ca
from kungfu_tpu.models import BertConfig, BertEncoder
from kungfu_tpu.parallel import rules
cfg = BertConfig(vocab_size=64, hidden_size=32, num_layers=1,
                 num_heads=4, intermediate_size=64, max_position=8,
                 dtype=jnp.float32)
params = jax.device_get(BertEncoder(cfg).init(
    jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))["params"])
with tempfile.TemporaryDirectory() as d:
    ca.save_sharded(d, params, step=3, rank=0, nprocs=1,
                    mesh_axes={"data": 2, "model": 2})
    mesh = jax.sharding.Mesh(
        np.array(jax.devices("cpu")[:4]).reshape(2, 2),
        ("model", "pipe"))
    placed, step, meta, _, diff = ca.restore_on_mesh(
        d, params, mesh=mesh, rules_table=rules.bert_tp_rules())
    assert step == 3 and diff == {}, (step, diff)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(jax.device_get(placed))):
        np.testing.assert_array_equal(a, b)
print("DP*TP -> TP*PP RESTORE SMOKE OK")
EOF

echo "== [4d/7] kftrace smoke: 2-peer traced resize -> Chrome trace validates =="
# the observability plane (docs/observability.md): a traced elastic
# run must flight-dump per-rank JSONL, the exporter must merge it into
# Chrome trace JSON, and the validator must accept it (loads, required
# keys, spans nest within their track) — malformed output fails here
timeout 300 python - <<'EOF'
import os, subprocess, sys, tempfile
d = tempfile.mkdtemp(prefix="kf-trace-smoke-")
os.environ["KF_TRACE"] = "1"
os.environ["KF_TRACE_DIR"] = d
from kungfu_tpu.elastic.harness import run_loss_continuity
run_loss_continuity(schedule="4:2,4:3", total_steps=9, start_np=2,
                    port_range="26000-26999", timeout=240)
out = os.path.join(d, "trace.json")
for args in (["--dir", d, "-o", out], ["--validate", out]):
    r = subprocess.run([sys.executable, "-m", "kungfu_tpu.trace"] + args)
    if r.returncode:
        sys.exit(f"kftrace smoke failed at {' '.join(args)}")
print("KFTRACE SMOKE OK")
EOF

echo "== [4e/7] goodput gate: shortest canned scenario replay -> phase-sum invariant =="
# the operator-facing number (docs/observability.md "goodput"): replay
# the shortest canned scenario (spot_preempt @ np0=2: whole-allocation
# kill at step 8, cold restore from the sharded checkpoint tier) under
# KF_TRACE=1 and gate on `--goodput` — the decomposition must sum to
# rank-active wallclock within tolerance and attribute the victims'
# lost steps from their flight dumps, or this stage exits nonzero.
# The full scenario x np matrix is scripts/chaos.sh territory.
timeout 300 python - <<'EOF'
import subprocess, sys, tempfile
from kungfu_tpu.scenario import run_scenario
d = tempfile.mkdtemp(prefix="kf-goodput-smoke-")
run = run_scenario("spot_preempt", trace_dir=d + "/trace",
                   port_range="26000-26999")
r = subprocess.run([sys.executable, "-m", "kungfu_tpu.trace",
                    "--dir", d + "/trace", "--goodput"])
if r.returncode:
    sys.exit("GOODPUT GATE FAILED: decomposition invariant violated")
print("GOODPUT GATE OK")
EOF

echo "== [4f/7] hierarchical + shm collectives smoke: 4 peers over two simulated hosts =="
# topology-aware collectives (docs/collectives.md): a 2x2-host
# in-process cluster (127.0.0.1 + 127.0.0.2) under KF_HIER=1 must (a)
# run hierarchical graphs, (b) sum exactly, (c) move every colocated
# byte off the socket stack (leaves' egress is 100% shm), and (d)
# re-derive the hierarchy across an epoch shrink
timeout 120 python - <<'EOF'
import threading
import numpy as np
from kungfu_tpu.ffi import NativePeer
import os
os.environ["KF_HIER"] = "1"
specs = ["127.0.0.1:26600", "127.0.0.1:26601",
         "127.0.0.2:26600", "127.0.0.2:26601"]
spec = ",".join(specs)
ps = [NativePeer(s, spec, version=0, strategy="STAR", timeout_ms=20000)
      for s in specs]
for p in ps:
    p.start()
def on_all(fn):
    out, errs = [None]*4, []
    def w(i):
        try: out[i] = fn(ps[i], i)
        except Exception as e: errs.append(e)
    ts = [threading.Thread(target=w, args=(i,)) for i in range(4)]
    [t.start() for t in ts]; [t.join() for t in ts]
    if errs: raise errs[0]
    return out
assert all(p.hierarchical for p in ps), "KF_HIER=1 session not hierarchical"
for r in on_all(lambda p, i: p.all_reduce(
        np.full(5000, float(i + 1), np.float32), name="smoke")):
    np.testing.assert_array_equal(r, np.full(5000, 10.0, np.float32))
for leaf in (1, 3):
    eg = ps[leaf].link_stats()["egress"]
    assert eg["shm"] > 0 and eg["tcp"] == 0 and eg["unix"] == 0, eg
for p in ps[:2]:
    p.update(",".join(specs[:2]), 1)
for r in on_all(lambda p, i: p.all_reduce(
        np.ones(64, np.int64), name="post") if i < 2 else None)[:2]:
    np.testing.assert_array_equal(r, np.full(64, 2, np.int64))
for p in ps:
    p.close()
print("HIER+SHM SMOKE OK")
EOF

echo "== [4g/7] fault-tolerant hier+shm: master-kill recovery smoke over two hosts =="
# the robustness analog of 4f (docs/fault_tolerance.md "host death"):
# np=4 over two emulated hosts (one kfrun per host) with KF_HIER=1 and
# the shm rings on the wire; a chaos schedule SIGKILLs host 2's MASTER
# mid-step. Survivors — including the dead master's colocated leaf,
# promoted to master by the recovery re-derivation — must shrink
# through the survivor path, keep loss continuity, and the schedule
# re-grows back to 4. The harness asserts every RECOVERY_MARKER.
timeout 300 python - <<'EOF'
from kungfu_tpu.elastic.harness import run_survivor_recovery
logs = run_survivor_recovery(
    crash_rank=2, crash_step=5, total_steps=12, start_np=4,
    hosts="127.0.0.1:2,127.0.0.2:2", port_range="26000-26999",
    timeout=240, extra_env={"KF_HIER": "1"})
assert "KF_RECOVERY_DONE rank=0 size=3" in logs, logs[-2000:]
assert "size=4 step=12" in logs, logs[-2000:]
print("MASTER-KILL HIER+SHM RECOVERY SMOKE OK")
EOF

echo "== [4h/7] serving smoke: 2-worker decode tier, mid-traffic grow 2->3 =="
# the kfserve decode tier (docs/serving.md): a 2-replica continuous-
# batching cluster serves a live request mix; once a quarter of it
# completed the harness grows the tier 2->3 through the consensus-
# resize path WHILE traffic is in flight (joiner adopts weights via
# the boot broadcast, survivors' paged KV pools ride through), and
# the run gates on every request completing + zero request-ledger
# invariant violations — the request-plane analog of the --goodput
# phase-sum gate.
timeout 400 python - <<'EOF'
from kungfu_tpu.serve.harness import (RESIZE_MARKERS, default_requests,
                                      run_serve_cluster)
out = run_serve_cluster(
    default_requests(12, gen_len=48), start_np=2, warmup=2,
    grow_when_done=5, extra_env={"KF_SERVE_MAX_BATCH": "4"},
    port_range="26000-26999", timeout=360, markers=RESIZE_MARKERS)
st = out["stats"]
assert st["failed"] == 0 and st["done"] == 14, st
print(f"SERVE SMOKE OK: {st['done']} requests, "
      f"p99 {st['p99_ms']:.0f} ms through the grow")
EOF

# the serving fast path (docs/serving.md "The fast path"): the same
# tier on a prefix-heavy mix (one 48-token common prefix, short
# unique tails) with CoW prefix sharing + chunked prefill ON —
# sharing must actually engage (peak KV blocks stay well under the
# unshared mix's footprint) and every request must still complete
# with zero ledger violations.
timeout 400 python - <<'EOF'
from kungfu_tpu.serve.harness import (SERVE_MARKERS, prefix_requests,
                                      run_serve_cluster)
out = run_serve_cluster(
    prefix_requests(8, prefix_len=48, gen_len=12), start_np=2,
    warmup=2,
    extra_env={"KF_SERVE_MAX_BATCH": "4",
               "KF_SERVE_SHARE_PREFIX": "1",
               "KF_SERVE_PREFILL_CHUNK": "16"},
    port_range="26000-26999", timeout=360, markers=SERVE_MARKERS)
st = out["stats"]
assert st["failed"] == 0 and st["done"] == 10, st
import re
chunks = sum(int(m) for m in
             re.findall(r"prefill_chunks=(\d+)", out["logs"]))
peaks = [int(m) for m in
         re.findall(r"peak_blocks=(\d+)", out["logs"])]
assert chunks > 0, "chunked prefill never engaged:\n" + out["logs"][-2000:]
# 4 prompts/worker x 4 blocks each = 16 unshared; sharing keeps the
# common 3 blocks single-copy per worker
assert peaks and max(peaks) < 16, (peaks, out["logs"][-2000:])
print(f"SERVE FAST-PATH SMOKE OK: {st['done']} requests, "
      f"{chunks} prefill chunks, peak KV blocks {max(peaks)}")
EOF

echo "== [4i/7] replicated control plane: kill leader mid-resize under live traffic =="
# the replicated config tier (docs/control_plane.md): a 3-replica
# leader-leased tier fronts the SAME 2-worker decode cluster as 4h,
# and a kill_config_replica chaos fault PERMANENTLY kills the leader
# on the exact /addworker of the mid-traffic grow. The new leader's
# takeover must renew the in-flight serve leases and re-push state so
# EVERY request completes, the membership version advances gap-free
# on every survivor, and the ledger invariants stay clean — the
# client side rides KF_CONFIG_SERVERS failover with a retry deadline
# sized past the election window (the documented client contract).
timeout 400 python - <<'EOF'
from kungfu_tpu import chaos
from kungfu_tpu.elastic.replica import ReplicaTier
from kungfu_tpu.serve.harness import (RESIZE_MARKERS, default_requests,
                                      run_serve_cluster)
tier = ReplicaTier(n=3, lease_ms=500.0)
try:
    chaos.load({"faults": [{"type": "kill_config_replica",
                            "role": "leader", "path": "/addworker"}]})
    out = run_serve_cluster(
        default_requests(12, gen_len=48), start_np=2,
        grow_when_done=5, server=tier,
        extra_env={**tier.env(), "KF_SERVE_MAX_BATCH": "4",
                   "KF_SERVE_LEASE_MS": "3000",
                   "KF_RETRY_ATTEMPTS": "10",
                   "KF_RETRY_DEADLINE_MS": "30000"},
        port_range="26000-26999", timeout=360, markers=RESIZE_MARKERS)
    st = out["stats"]
    assert st["failed"] == 0 and st["done"] == 12, st
    dead = [r.index for r in tier.replicas if r.dead]
    assert len(dead) == 1, dead
    versions = tier.stage_versions()
    assert versions == [1, 1], versions
    viol = tier.serve_ledger.check_invariants()
    assert viol == [], viol
    lead = tier.wait_leader()
    assert set(lead.mttr_marks) >= {"detect", "elected",
                                    "catchup_done"}, lead.mttr_marks
finally:
    tier.stop()
    chaos.load(None)
    chaos._reset()
print(f"CONTROL-PLANE SMOKE OK: leader r{dead[0]} killed mid-resize, "
      f"12/12 served, stage v{versions[0]} on both survivors")
EOF

echo "== [4j/7] admission routers: kill a router mid-traffic, zero drops =="
# the stateless admission tier (docs/serving.md): two routers front a
# 3-replica config tier serving the SAME 2-worker decode cluster, all
# client traffic (submits AND result polls) enters through the
# routers, and a kill_router chaos fault permanently kills router 0
# mid-burst. Routers hold no request state — pending un-acked submits
# die with the router and the client lap-loop resubmits on the
# survivor — so the gate is the tier's whole point: every request
# completes exactly once and the ledger invariants stay clean.
timeout 400 python - <<'EOF'
from kungfu_tpu import chaos
from kungfu_tpu.elastic.replica import ReplicaTier
from kungfu_tpu.retrying import NO_RETRY
from kungfu_tpu.serve import frontend
from kungfu_tpu.serve.harness import default_requests, run_serve_cluster
from kungfu_tpu.serve.router import Router
import time


class RouterFront:
    """ConfigServer duck-type for run_serve_cluster with the request
    plane re-pointed at the router tier: workers still talk straight
    to the config tier (get_url), but every feeder submit/result/
    stats/invariants call enters through a router."""

    def __init__(self, tier, routers):
        self.tier = tier
        self.routers = routers

    @property
    def get_url(self):
        return self.tier.get_url

    @property
    def serve_ledger(self):
        return self

    def _call(self, fn, deadline_s=30.0):
        last = None
        deadline = time.monotonic() + deadline_s
        while time.monotonic() < deadline:
            for r in self.routers:
                if r.dead:
                    continue
                try:
                    return fn(r.base)
                except (OSError, ValueError) as e:
                    last = e  # killed router: lap to the survivor
            time.sleep(0.05)
        raise TimeoutError(f"no router answered: {last}")

    def submit(self, prompt, max_new):
        return self._call(lambda b: frontend.submit(
            b, prompt, max_new, retry=NO_RETRY))

    def result(self, rid):
        return self._call(lambda b: frontend.result(
            b, rid, retry=NO_RETRY))

    def stats(self):
        return self._call(lambda b: frontend.stats(b, retry=NO_RETRY))

    def check_invariants(self):
        return self._call(lambda b: frontend.invariants(
            b, retry=NO_RETRY))

    # scenario ledger knobs pass through to the real tier
    @property
    def lease_ms(self):
        return self.tier.serve_ledger.lease_ms

    @lease_ms.setter
    def lease_ms(self, v):
        self.tier.serve_ledger.lease_ms = v

    @property
    def max_queue(self):
        return self.tier.serve_ledger.max_queue

    @max_queue.setter
    def max_queue(self, v):
        self.tier.serve_ledger.max_queue = v


tier = ReplicaTier(n=3, lease_ms=500.0)
routers = []
try:
    routers = [Router(tier.bases, index=i).start() for i in range(2)]
    chaos.load({"faults": [{"type": "kill_router", "router": 0,
                            "after_requests": 5}]})
    front = RouterFront(tier, routers)
    out = run_serve_cluster(
        default_requests(12, gen_len=12), start_np=2, server=front,
        extra_env={**tier.env(), "KF_SERVE_MAX_BATCH": "4",
                   "KF_SERVE_LEASE_MS": "3000"},
        port_range="26000-26999", timeout=360)
    st = out["stats"]
    assert st["failed"] == 0 and st["done"] == 12, st
    assert routers[0].dead, "chaos never killed router 0"
    assert not routers[1].dead, "survivor router died too"
    hz = routers[1].healthz()
    assert hz["submitted"] > 0, hz
    viol = front.check_invariants()
    assert viol == [], viol
finally:
    for r in routers:
        r.stop()
    tier.stop()
    chaos.load(None)
    chaos._reset()
print(f"ROUTER SMOKE OK: router 0 killed mid-traffic, 12/12 served "
      f"through survivor (submitted {hz['submitted']} there), "
      f"zero drops")
EOF

echo "== [4k/7] durable control plane: whole-tier death mid-resize, relaunch from WALs =="
# the durability gate (docs/control_plane.md "Durability"): the SAME
# 2-worker decode cluster as 4i, but every config replica writes a
# write-ahead log — and the moment the mid-traffic grow commits
# (membership v1), ALL THREE replicas are SIGKILL-crashed at once
# while the new worker is still booting against them. After a 1 s
# dark window the tier relaunches from its WALs on the same ports:
# the run must complete 12/12 (zero acked writes lost — every acked
# op was fsynced on every reachable replica before its 200), the
# grow must survive gap-free (v1 on every member), and the ledger
# invariants must hold. Clients ride the outage on the documented
# retry contract (deadline sized past kill -> relaunch -> election).
timeout 450 python - <<'EOF'
import tempfile
import threading
import time

from kungfu_tpu.elastic.replica import ReplicaTier
from kungfu_tpu.serve.harness import (RESIZE_MARKERS, default_requests,
                                      run_serve_cluster)

wal_dir = tempfile.mkdtemp(prefix="kf-run-all-cp-wal-")
tier = ReplicaTier(n=3, lease_ms=500.0, wal_dir=wal_dir)
outage = {}


def executioner():
    deadline = time.monotonic() + 240.0
    while time.monotonic() < deadline:
        try:
            vs = tier.stage_versions()
        except Exception:  # mid-churn reads can race
            vs = []
        if vs and all(v == 1 for v in vs):
            break
        time.sleep(0.05)
    else:
        outage["error"] = "resize never landed"
        return
    tier.kill_all()
    time.sleep(1.0)  # a real outage window, requests in flight
    tier.relaunch()
    outage["t_up"] = time.monotonic()


ex = threading.Thread(target=executioner, daemon=True)
try:
    ex.start()
    out = run_serve_cluster(
        default_requests(12, gen_len=48), start_np=2,
        grow_when_done=5, server=tier,
        extra_env={**tier.env(), "KF_SERVE_MAX_BATCH": "4",
                   "KF_SERVE_LEASE_MS": "3000",
                   "KF_RETRY_ATTEMPTS": "12",
                   "KF_RETRY_DEADLINE_MS": "45000"},
        port_range="26000-26999", timeout=360, markers=RESIZE_MARKERS)
    ex.join(30)
    assert "error" not in outage, outage
    assert "t_up" in outage, "tier was never relaunched"
    st = out["stats"]
    assert st["failed"] == 0 and st["done"] == 12, st
    for r in tier.replicas:
        assert not r.dead and r.status()["wal"], r.index
    versions = tier.stage_versions()
    assert versions == [1, 1, 1], versions
    viol = tier.serve_ledger.check_invariants()
    assert viol == [], viol
    seqs = [r.seq for r in tier.replicas]
finally:
    tier.stop()
print(f"DURABLE CONTROL-PLANE SMOKE OK: whole tier killed mid-resize, "
      f"relaunched from WALs (seqs {seqs}), 12/12 served, "
      f"stage v1 on all three members")
EOF

echo "== [5/7] examples smoke =="
timeout 300 python examples/mnist_slp_sync.py --steps 20
timeout 300 python examples/mnist_elastic.py --launch \
  --schedule 3:2,3:3 --steps 6

if [ "$QUICK" = 0 ]; then
  echo "== [6/7] docs build =="
  python scripts/build-docs.py
else
  # CI runs --quick and builds the docs in its own named step
  echo "== [6/7] docs build skipped (--quick) =="
fi

echo "ALL GREEN"
