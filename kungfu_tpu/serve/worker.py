"""The elastic decode worker: `python -m kungfu_tpu.serve.worker`.

Run under kfrun like any trainer. Each worker is a data-parallel
serving replica: it leases requests from the config server's ledger
(`serve.frontend`), runs them through its own `DecodeEngine`
(continuous batching over the paged KV pool), and streams tokens
back — so the tier scales request throughput with worker count and
NO request state lives in any worker longer than one lease.

The elastic story is the training runtime's, unchanged
(docs/serving.md "Elastic serving"):

- **membership** rides `ElasticCallback.after_step` once per decode
  iteration: planned resizes (TEST_SCHEDULE) and policy-driven ones
  (KF_POLICY=slo -> `SLOPolicy` reading /serve/stats) both go through
  the consensus-resize path; survivors keep their engines — their
  in-flight requests decode straight through the epoch switch, which
  is why the benchmark can report p99 *through* a resize instead of
  around one;
- **params** prove the same continuity training proves: a joiner
  (launch version > 0) adopts survivors' weights via the boot-time
  broadcast, survivors answer from their `changed` branch; a COLD
  boot with KF_CKPT_DIR restores the sharded checkpoint tier
  re-sharded to this np (`restore_sharded`) — the serving replica's
  weights come from the training tier's durable rung, not from a
  side channel;
- **failure**: a peer death surfaces as KfError in the membership
  collectives; with KF_RECOVER=1 the worker rides
  `ElasticCallback.recover` and keeps serving. The dead worker's
  leases expire on the ledger and its requests resume elsewhere —
  completion-after-recovery, asserted by the chaos e2e
  (tests/test_serve_elastic.py) and the `spot_serve_kill` scenario.

Markers (parsed by `serve.harness`): KF_SERVE_READY / KF_SERVE_RESTORED
/ KF_SERVE_JOINER / KF_SERVE_RESIZED / KF_SERVE_RECOVERED /
KF_SERVE_EVICTED / KF_SERVE_DONE.
"""

import os
import time

import jax
import jax.numpy as jnp

import kungfu_tpu
from kungfu_tpu import trace
from kungfu_tpu.elastic import ElasticCallback
from kungfu_tpu.env import env_choice, env_flag, env_float, env_int
from kungfu_tpu.ffi import KfError
from kungfu_tpu.initializer import broadcast_variables
from kungfu_tpu.serve import frontend
from kungfu_tpu.serve.engine import DecodeEngine, build_lm
from kungfu_tpu.trace import metrics

MAX_BATCH = env_int("KF_SERVE_MAX_BATCH", 8, minimum=1)
BLOCK_TOKENS = env_int("KF_KV_BLOCK_TOKENS", 16, minimum=1)
SLO_P99_MS = env_float("KF_SLO_P99_MS", 0.0, minimum=0.0)
MODEL_SIZE = os.environ.get("KF_SERVE_MODEL", "tiny")
MAX_LEN = env_int("KF_SERVE_MAX_LEN", 64, minimum=2)
#: pool sizing override: 0 = worst-case (max_batch full-length seqs);
#: tests shrink it to drive the preemption path
NUM_BLOCKS = env_int("KF_SERVE_BLOCKS", 0, minimum=0)
#: exit once the ledger reports this many finished requests (0 = run
#: until the iteration cap — the benchmark/harness always sets it)
EXPECT = env_int("KF_SERVE_EXPECT", 0, minimum=0)
MAX_ITERS = env_int("KF_SERVE_MAX_ITERS", 20_000, minimum=1)
#: fast-path knobs (docs/serving.md "The fast path"): decode kernel
#: selection, chunked-prefill size (0 = whole-prompt), CoW prefix
#: sharing across requests
KERNEL = env_choice("KF_SERVE_KERNEL", "auto",
                    ("auto", "kernel", "functional"))
PREFILL_CHUNK = env_int("KF_SERVE_PREFILL_CHUNK", 0, minimum=0)
SHARE_PREFIX = env_flag("KF_SERVE_SHARE_PREFIX", True)
SCHEDULE = os.environ.get("TEST_SCHEDULE", "")
POLICY = os.environ.get("KF_POLICY", "")
RECOVER = os.environ.get("KF_RECOVER", "0") == "1"
RECOVERY_DEADLINE_S = float(
    os.environ.get("KF_RECOVERY_DEADLINE_MS", "30000")) / 1e3
CKPT_DIR = os.environ.get("KF_CKPT_DIR", "")

peer = kungfu_tpu.init()
url = peer.config.config_server
if not url:
    raise SystemExit("serve.worker needs a config server "
                     "(kfrun -w -config-server ...)")
#: stable worker identity for lease fencing: rank changes across
#: epochs, the bound self address does not
WID = str(peer.config.self_id)

model, params, _mesh = build_lm(
    MODEL_SIZE, max_position=MAX_LEN,
    dtype=jnp.float32 if jax.devices()[0].platform == "cpu" else None)

policy = None
if POLICY == "slo":
    from kungfu_tpu.elastic.policy import SLOPolicy

    policy = SLOPolicy(p99_target_ms=SLO_P99_MS,
                       capacity_per_worker=MAX_BATCH)
elif POLICY:
    raise SystemExit(f"unknown KF_POLICY {POLICY!r} for serving "
                     "(known: slo)")
elastic = ElasticCallback(peer, schedule="" if policy else SCHEDULE,
                          policy=policy)

def tier_drained() -> bool:
    """True once the ledger reports every expected request finished.

    The end-of-run escape hatch for membership collectives: near the
    drain, a policy/schedule proposal can still be in flight while
    peers exit on EXPECT — a joiner booting into (or a survivor
    consenting with) an already-exited peer sees KfError. When the
    tier is drained that is a clean shutdown, not a failure."""
    if EXPECT <= 0:
        return False
    try:
        st = frontend.stats(url)
    except (OSError, ValueError, KeyError):
        return False
    return st["done"] + st["failed"] >= EXPECT


if peer.config.version > 0:
    # joiner: adopt the cluster-agreed iteration count FIRST (a
    # replacement replica restarting at step 0 would replay the chaos
    # schedule's already-fired step coordinates — the same
    # lesson PR 5 learned about wire names), then the survivors'
    # weights (they may be restored/trained state, not this process's
    # seed init). Rank-divergent by protocol — the survivor half
    # answers from its `changed` branch.
    try:
        elastic.sync_position()
        params = broadcast_variables(params, peer=peer)
    except KfError:
        if tier_drained():
            # spawned just as the tier finished: nothing to join
            print(f"KF_SERVE_DRAINED rank={peer.rank} (joiner)",
                  flush=True)
            raise SystemExit(0) from None
        raise
    print(f"KF_SERVE_JOINER rank={peer.rank} size={peer.size} "
          f"step={elastic.state.step}", flush=True)
elif CKPT_DIR:
    # cold boot: restore the sharded checkpoint tier re-sharded to
    # THIS np (the whole point of serving off the training tier's
    # durable rung). Entered unconditionally on every version-0 rank;
    # rank 0's pick broadcast agrees on the candidate (or on "none":
    # every rank falls through together).
    from kungfu_tpu.checkpoint_async import (CheckpointError,
                                             restore_sharded)
    try:
        out, step0, _meta, _res = restore_sharded(CKPT_DIR, params,
                                                  peer=peer)
        params = out
        print(f"KF_SERVE_RESTORED rank={peer.rank} step={step0}",
              flush=True)
    except CheckpointError as e:
        print(f"KF_SERVE_RESTORE_NONE rank={peer.rank}: {e}",
              flush=True)

engine = DecodeEngine(model, params, max_batch=MAX_BATCH,
                      block_tokens=BLOCK_TOKENS, max_len=MAX_LEN,
                      num_blocks=NUM_BLOCKS, kernel=KERNEL,
                      prefill_chunk=PREFILL_CHUNK,
                      share_prefix=SHARE_PREFIX)
# compile before READY: a replica that jits on its first lease stalls
# that request for seconds and contends every peer on a shared host
_t0 = time.perf_counter()
engine.warm()
warm_s = time.perf_counter() - _t0
#: ledger position each live sequence appends at next
positions = {}
served = 0
#: wall seconds spent in control-plane round trips (lease/append/
#: stats) — the KF_SERVE_TIMING breakdown the benchmark parses
control_s = 0.0
#: high-water mark of KV blocks in use — the prefix-sharing
#: benchmark cell's collapse observable
peak_blocks = 0


def timed(fn, *args, **kwargs):
    global control_s
    t0 = time.perf_counter()
    try:
        return fn(*args, **kwargs)
    finally:
        control_s += time.perf_counter() - t0


print(f"KF_SERVE_READY rank={peer.rank} size={peer.size} "
      f"max_batch={MAX_BATCH} block_tokens={BLOCK_TOKENS} "
      f"kernel={engine.kernel} chunk={PREFILL_CHUNK} "
      f"share={int(SHARE_PREFIX)}", flush=True)


def release_all(note: str) -> None:
    """Return every live sequence to the ledger (their tokens are
    already recorded; a later lease resumes them elsewhere)."""
    for s in engine.live():
        engine.drain(s)
        try:
            frontend.release(url, int(s), WID)
        except (OSError, ValueError, KeyError) as e:
            # control plane unreachable: the lease expiry reclaims it
            print(f"[kf-serve] release({s}) after {note}: {e}",
                  flush=True)
        positions.pop(s, None)


def survivor_recover() -> None:
    """Adopt the runner's shrunken stage and keep serving; the engine
    (and every in-flight request on THIS worker) survives untouched."""
    out = elastic.recover(params=params,
                          deadline_s=RECOVERY_DEADLINE_S)
    if out is None:
        if not elastic.state.keep:
            release_all("eviction")
            print(f"KF_SERVE_EVICTED rank={peer.rank}", flush=True)
            raise SystemExit(0)
        if tier_drained():
            # no recovery stage will come: the "dead" peer exited
            # cleanly on EXPECT and the runner has nothing to reap
            print(f"KF_SERVE_DRAINED rank={peer.rank} (recovery)",
                  flush=True)
            raise SystemExit(0)
        raise SystemExit(43)
    print(f"KF_SERVE_RECOVERED rank={peer.rank} size={peer.size} "
          f"epoch={peer.version}", flush=True)


for _ in range(MAX_ITERS):
    # rows for THIS iteration's single /serve/append_batch round trip
    # (one POST per iteration instead of one per sequence — the
    # per-sequence append storm was BENCH_r15's inverse np scaling)
    rows = []
    # -- admit: fill free slots from the ledger -----------------------------
    if engine.free_slots() > 0:
        try:
            leased = timed(frontend.lease, url, engine.free_slots(),
                           WID)
        except (OSError, ValueError, KeyError) as e:
            print(f"[kf-serve] lease failed after bounded retries: "
                  f"{e}", flush=True)
            leased = []
        for r in leased:
            rid = int(r["id"])
            if engine.is_live(rid):
                # our OWN expired lease came back (a stalled iteration
                # outlived KF_SERVE_LEASE_MS): we now hold the fresh
                # lease and the sequence is still decoding — keep it,
                # do not double-admit (engine.admit would raise)
                continue
            prompt = [int(t) for t in r["prompt"]] + \
                [int(t) for t in r["tokens"]]
            remaining = int(r["max_new"]) - int(r["pos"])
            if remaining <= 0 or not engine.can_admit(len(prompt)):
                timed(frontend.release, url, rid, WID)
                continue
            tok, done = engine.admit(rid, prompt, remaining)
            if tok is None:
                # deferred (chunked/shared) prefill: step() emits the
                # first token through its `emitted` map at this pos
                positions[rid] = int(r["pos"])
                continue
            positions[rid] = int(r["pos"]) + 1
            # the one append that stays un-batched: it renews this
            # request's lease BEFORE the iteration's decode/compile
            # work (a boot-time compile can outlive the lease, and a
            # first-iteration "stale" would bounce the whole batch
            # back to the queue)
            status = timed(frontend.append, url, rid, int(r["pos"]),
                           [tok], done, WID)
            if status != "ok":
                engine.drain(rid)
                positions.pop(rid, None)
            elif done:
                served += 1
                positions.pop(rid, None)

    # -- one continuous-batching decode iteration ---------------------------
    emitted, preempted = engine.step()
    for s in preempted:
        timed(frontend.release, url, int(s), WID)
        positions.pop(s, None)
    for s, (tok, done) in emitted.items():
        rows.append({"id": int(s), "pos": positions[s],
                     "tokens": [tok], "done": done})
        positions[s] = positions[s] + 1
    for s in engine.prefilling():
        if s not in emitted:
            # heartbeat: an empty in-place append renews the lease of
            # a sequence that spends several iterations in chunked
            # prefill without emitting anything
            rows.append({"id": int(s), "pos": positions[s],
                         "tokens": [], "done": False})
    stats = None
    if rows:
        statuses, stats = timed(frontend.append_batch, url, rows, WID)
        for row, status in zip(rows, statuses):
            rid = row["id"]
            if status != "ok":
                # "stale": our lease was reclaimed; "done": a resumed
                # lease finished the request elsewhere while we
                # stalled (e.g. through a recovery window) — keeping
                # the dead sequence would burn a batch slot for up to
                # max_new more iterations
                engine.drain(rid)
                positions.pop(rid, None)
            elif row["done"]:
                served += 1
                positions.pop(rid, None)
    metrics.REGISTRY.set("kf_serve_active", engine.active)
    peak_blocks = max(peak_blocks, engine.pool.blocks_in_use)

    # -- elastic membership (the training runtime's path, unchanged) --------
    try:
        if policy is not None:
            if stats is None:
                stats = timed(frontend.stats, url)
            policy.observe(stats["queue_depth"], stats["running"],
                           stats["p99_ms"])
        with trace.span("step.hook", cat="serve"):
            changed = elastic.after_step()
    except KfError:
        if not RECOVER:
            if tier_drained():
                break  # a peer exited on EXPECT mid-consensus
            raise
        survivor_recover()
        continue
    if changed:
        if not elastic.state.keep:
            release_all("eviction")
            print(f"KF_SERVE_EVICTED rank={peer.rank}", flush=True)
            raise SystemExit(0)
        # survivor half of the joiner's boot-time resync (position,
        # then weights); the engine's KV pool is per-process state
        # and rides through
        try:
            elastic.sync_position()
            params = broadcast_variables(params, peer=peer)
        except KfError:
            if not RECOVER:
                if tier_drained():
                    break  # resync raced the drain; work is done
                raise
            survivor_recover()
            continue
        print(f"KF_SERVE_RESIZED rank={peer.rank} size={peer.size} "
              f"epoch={peer.version} step={elastic.state.step}",
              flush=True)

    # -- drain / idle -------------------------------------------------------
    if EXPECT > 0:
        try:
            stats = stats or timed(frontend.stats, url)
        except (OSError, ValueError, KeyError):
            stats = None
        if stats and stats["done"] + stats["failed"] >= EXPECT:
            break
    if engine.active == 0:
        time.sleep(0.01)

release_all("shutdown")  # no-op on a drained ledger (EXPECT reached);
#                          an iteration-cap exit returns its leases
print(f"KF_SERVE_TIMING rank={peer.rank} steps={engine.steps} "
      f"decode_ms={engine.decode_s * 1e3:.1f} "
      f"prefill_ms={engine.prefill_s * 1e3:.1f} "
      f"prefill_chunks={engine.prefill_chunks} "
      f"control_ms={control_s * 1e3:.1f} "
      f"warm_ms={warm_s * 1e3:.1f} "
      f"peak_blocks={peak_blocks}", flush=True)
print(f"KF_SERVE_DONE rank={peer.rank} size={peer.size} "
      f"served={served} iters={elastic.state.step}", flush=True)
