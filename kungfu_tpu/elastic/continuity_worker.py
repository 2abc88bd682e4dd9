"""Elastic resize with LOSS CONTINUITY asserted (real training).

An SLP trains under S-SGD while the schedule grows the cluster; on
resize every worker re-syncs position and weights. The continuity
checks make the state broadcast load-bearing:

- a JOINER evaluates its first batch twice — with its fresh-init
  weights and with the broadcast weights — and asserts the broadcast
  model is strictly better (it adopted trained state, not an init);
- a SURVIVOR asserts the first post-resize loss stays near its
  pre-resize loss (no reset to init-level loss).

With KF_RECOVER=1 the same trainer also exercises the survivor-driven
FAILURE path: when a peer dies mid-step (e.g. a chaos-scheduled
crash_worker fault), the collective fails fast with KF_ERR_CONN, the
worker calls `ElasticCallback.recover` — adopting the shrunken stage
the detecting runner proposed, re-broadcasting params+optimizer state
from the new rank 0 — and continues training with the SAME survivor
loss-continuity assertion as a planned resize. No operator action.

With KF_CKPT_DIR set the trainer also exercises the DURABLE rung of
the recovery state machine: every KF_CKPT_EVERY steps each peer
asynchronously writes its shard of (params, opt_state) — plus its
per-rank gradient-pipeline residuals — and a COLD-BOOTED cluster
(launch version 0, i.e. nobody alive to resync from: the whole-cluster
death case) restores the latest complete generation instead of
starting from init, re-sharded to whatever np it was launched with.
The restore proves itself the same way the joiner broadcast does:
first-batch loss under the restored weights must beat this process's
fresh init (KF_RESTORE_CONTINUITY marker).

Markers: CONTINUITY_MARKERS in `elastic.harness` — parsed by
tests/test_elastic.py and the driver's
`__graft_entry__.dryrun_multichip` elastic phase, both via
`kungfu_tpu.elastic.harness.run_loss_continuity`; recovery runs add
KF_RECOVERY_CAUGHT / KF_RECOVERY_DONE (see harness.RECOVERY_MARKERS);
checkpointed runs add KF_CKPT_SAVED / KF_RESTORE_CONTINUITY (see
harness.run_checkpoint_restore).

Run under kfrun as `python -m kungfu_tpu.elastic.continuity_worker`.
"""

import os
import time

os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import optax

import kungfu_tpu
from kungfu_tpu import compile_cache, trace
from kungfu_tpu.data import ElasticSampler
from kungfu_tpu.trace import metrics
from kungfu_tpu.datasets import load_synthetic_split
from kungfu_tpu.elastic import ElasticCallback
from kungfu_tpu.ffi import KfError
from kungfu_tpu.grad_pipeline import GradBucketPipeline, grad_bucket_bytes
from kungfu_tpu.initializer import broadcast_variables
from kungfu_tpu.models import SLP
from kungfu_tpu.ops.collective import defuse, fuse

TOTAL_STEPS = int(os.environ.get("TEST_TOTAL_STEPS", "12"))
SCHEDULE = os.environ.get("TEST_SCHEDULE", "6:2,6:4")
# KF_POLICY switches the sizing driver from the static schedule to a
# monitor-driven policy (docs/observability.md "GoodputPolicy"):
# "goodput" = cost-aware ride-out/shed + priced re-grow,
# "naive_straggler" = the shed-on-first-spike baseline. The scenario
# runner sets this to compare adaptation policies on one trace.
POLICY = os.environ.get("KF_POLICY", "")
RECOVER = os.environ.get("KF_RECOVER", "0") == "1"
RECOVERY_DEADLINE_S = float(
    os.environ.get("KF_RECOVERY_DEADLINE_MS", "30000")) / 1e3
# the durable-checkpoint rung: a directory enables async sharded
# saves every KF_CKPT_EVERY steps (docs/fault_tolerance.md)
CKPT_DIR = os.environ.get("KF_CKPT_DIR", "")
CKPT_EVERY = int(os.environ.get("KF_CKPT_EVERY", "4"))
BATCH = int(os.environ.get("TEST_DEVICE_BATCH", "64"))
LR = 0.1

# the compile ledger (`compile_cache.py`): a joiner's first step is
# mostly its compile, and the goodput plane bills that to "compile"
compiles = compile_cache.enable()
peer = kungfu_tpu.init()
ds = load_synthetic_split(n=2048, seed=0)
x, y = ds.images, ds.labels
model = SLP(num_classes=10)
params = model.init(jax.random.PRNGKey(0), x[:1])["params"]
tx = optax.sgd(LR)
opt_state = tx.init(params)


@jax.jit
def loss_and_grads(params, batch):
    def loss_fn(p):
        logits = model.apply({"params": p}, batch["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()

    return jax.value_and_grad(loss_fn)(params)


policy = None
if POLICY:
    from kungfu_tpu.elastic.policy import (GoodputPolicy,
                                           NaiveStragglerPolicy)

    if POLICY == "goodput":
        policy = GoodputPolicy()
    elif POLICY == "naive_straggler":
        policy = NaiveStragglerPolicy()
    else:
        # a typo'd policy silently running the wrong baseline would
        # corrupt every comparison derived from this run
        raise SystemExit(f"unknown KF_POLICY {POLICY!r} "
                         "(known: goodput, naive_straggler)")
# a policy run is monitor-driven: the schedule must not also steer
# (ElasticCallback consults the policy only when no schedule is set)
elastic = ElasticCallback(peer, schedule="" if policy else SCHEDULE,
                          samples_per_step=BATCH, policy=policy)

# the live goodput families (kf_goodput_ratio, kf_useful_ms_total,
# kf_lost_ms_total{phase=...}): fed per step below, read back by the
# policies and scraped via /metrics (trace/goodput.py)
from kungfu_tpu.trace.goodput import GoodputMeter

meter = GoodputMeter()

# KF_GRAD_BUCKET_MB > 0 switches the gradient all-reduce from the
# monolithic lump to the bucketed, overlapped pipeline (compression
# from KF_GRAD_COMPRESS). Its error-feedback residuals are PER-RANK
# state living in the pipe object: survivors keep theirs across every
# epoch switch below (the pipe outlives resizes — the model shape
# never changes, only the peer set), joiners start at zero, and
# durable checkpoints carry them via pipe.state() next to opt_state.
GRAD_BUCKET_BYTES = grad_bucket_bytes(
    None if os.environ.get("KF_GRAD_BUCKET_MB") else 0)
pipe = (GradBucketPipeline(peer, params,
                           bucket_bytes=GRAD_BUCKET_BYTES)
        if GRAD_BUCKET_BYTES > 0 else None)


def make_sampler():
    return ElasticSampler(len(x), BATCH, peer.rank, peer.size, seed=1,
                          offset=elastic.state.trained_samples)


ckpt = None


def make_checkpointer():
    """(Re)build the sharded checkpointer for the CURRENT membership —
    rank/size bind the shard schedule, so every epoch switch (resize or
    recovery) swaps it; pending writes of the old epoch are drained."""
    global ckpt
    if not CKPT_DIR:
        return
    from kungfu_tpu.checkpoint_async import AsyncShardedCheckpointer
    if ckpt is not None:
        ckpt.close()
    ckpt = AsyncShardedCheckpointer(CKPT_DIR, peer)


def maybe_save():
    if ckpt is None or CKPT_EVERY <= 0 \
            or elastic.state.step % CKPT_EVERY != 0:
        return
    t0 = time.perf_counter()
    g = ckpt.save(
        (params, opt_state), step=elastic.state.step,
        meta={"trained_samples": elastic.state.trained_samples},
        residual=pipe.state() if pipe is not None else None)
    # only the synchronous snapshot stall is exposed overhead; the
    # writer thread's wall rides the ckpt.save span instead
    meter.observe("checkpoint", (time.perf_counter() - t0) * 1e3)
    print(f"KF_CKPT_SAVED gen={g} step={elastic.state.step} "
          f"rank={peer.rank}", flush=True)


make_checkpointer()

if peer.config.version > 0:
    # joiner: adopt position + weights, then PROVE the weights are
    # trained state by comparing against this process's fresh init.
    # The launch-version branch IS rank-divergent, by protocol: these
    # are the joiner-side halves of the resync rendezvous — survivors
    # issue the matching sync_position/broadcast from their after_step
    # `changed` branch below, and the pairing is asserted end to end
    # by tests/test_elastic.py + the chaos e2e.
    # kflint: disable=collective-order
    elastic.sync_position()
    fresh = params
    # kflint: disable=collective-order — survivor half in `changed`
    params = broadcast_variables(params, peer=peer)
    sampler = make_sampler()
    idx = sampler.next_indices()
    batch = {"x": x[idx], "y": y[idx]}
    fresh_loss = float(loss_and_grads(fresh, batch)[0])
    got_loss = float(loss_and_grads(params, batch)[0])
    print(f"KF_JOINER_CONTINUITY rank={peer.rank} "
          f"fresh={fresh_loss:.4f} broadcast={got_loss:.4f}", flush=True)
    assert got_loss < fresh_loss - 0.05, (
        f"joiner's broadcast weights are no better than a fresh init "
        f"({got_loss:.4f} vs {fresh_loss:.4f}): state broadcast failed")
else:
    # cold boot (launch version 0): the last rung of the recovery
    # state machine. If a durable checkpoint exists, this cluster is a
    # relaunch after whole-cluster death — restore the latest complete
    # generation (re-sharded to THIS np, which may differ from the
    # saving cluster's) instead of training from init, and PROVE the
    # restored weights are trained state exactly like a joiner proves
    # its broadcast.
    restored = None
    if ckpt is not None:
        from kungfu_tpu.checkpoint_async import (CheckpointError,
                                                 restore_sharded)
        try:
            # the cold-boot branch IS rank-uniform: EVERY member
            # of the initial cluster launches with version 0 and
            # enters the restore rendezvous together; joiners
            # (version > 0) adopt state via the live broadcast
            # above instead. The launch-version test separates
            # boot cohorts, not ranks within one epoch.
            #
            # Entered UNCONDITIONALLY — no local list_generations
            # gate: whether a generation exists is decided inside
            # restore_sharded by rank 0's pick broadcast, so a
            # lagging or divergent local view of KF_CKPT_DIR (which
            # must be shared storage, see docs/fault_tolerance.md)
            # cannot split the cluster into some ranks joining the
            # restore collectives while others skip to fresh init —
            # a version-0 boot deadlock. "No checkpoint at all" is
            # the same agreed walk reporting no candidate: every
            # rank raises together.
            # kflint: disable=collective-order
            restored = restore_sharded(CKPT_DIR,
                                       (params, opt_state),
                                       peer=peer)
        except CheckpointError as e:
            # every rank rejects in lockstep (rank-0 pick + vote),
            # so falling through to fresh init is cluster-uniform
            print(f"KF_CKPT_RESTORE_NONE rank={peer.rank}: {e}",
                  flush=True)
    if restored is not None:
        out, step0, meta0, residual0 = restored
        fresh = params
        params, opt_state = out
        elastic.state.step = int(step0)
        elastic.state.trained_samples = int(
            meta0.get("trained_samples", 0))
        # the goodput plane's lost-work anchor: any step computed
        # BEFORE this instant and PAST this generation was discarded
        # by the whole-cluster death (trace/goodput.py; the victims'
        # own flight dumps supply those spans)
        trace.set_context(rank=peer.rank, version=peer.version,
                          step=int(step0))
        trace.event("ckpt.restored", cat="ckpt", gen_step=int(step0))
        if pipe is not None:
            if residual0 is not None:
                # survivor semantics: this rank ran in the saving
                # cluster too — adopt its own residuals byte-exactly
                pipe.load_state(residual0)
                print(f"KF_CKPT_RESIDUALS rank={peer.rank} "
                      f"adopted", flush=True)
            else:
                # joiner semantics (restore np > save np): start at
                # zero, per docs/grad_pipeline.md
                print(f"KF_CKPT_RESIDUALS rank={peer.rank} zero",
                      flush=True)
        sampler = make_sampler()
        idx = sampler.next_indices()
        batch = {"x": x[idx], "y": y[idx]}
        fresh_loss = float(loss_and_grads(fresh, batch)[0])
        got_loss = float(loss_and_grads(params, batch)[0])
        print(f"KF_RESTORE_CONTINUITY rank={peer.rank} "
              f"step={elastic.state.step} fresh={fresh_loss:.4f} "
              f"restored={got_loss:.4f}", flush=True)
        assert got_loss < fresh_loss - 0.05, (
            f"restored weights are no better than a fresh init "
            f"({got_loss:.4f} vs {fresh_loss:.4f}): the durable "
            "checkpoint did not carry trained state")
    else:
        sampler = make_sampler()

just_recovered = False


def try_recover():
    """Survivor path: adopt the runner-proposed shrunken stage and
    restore params+optimizer state from the new rank 0, mutating the
    module-level params/opt_state/sampler in place. On failure it exits:
    SystemExit(0) when the recovery stage evicted this worker (same
    clean exit as a planned-resize eviction), SystemExit(43) when no
    recovery stage arrived in time (fail fast)."""
    global params, opt_state, sampler, pending_continuity, just_recovered
    print(f"KF_RECOVERY_CAUGHT rank={peer.rank} "
          f"step={elastic.state.step}", flush=True)
    t_rec0 = time.perf_counter()
    out = elastic.recover(params=(params, opt_state),
                          deadline_s=RECOVERY_DEADLINE_S)
    meter.observe("recovery", (time.perf_counter() - t_rec0) * 1e3)
    if out is None:
        if not elastic.state.keep:
            # the recovery stage evicted US — a legitimate outcome,
            # same clean exit as a planned-resize eviction
            print(f"evicted during recovery at step "
                  f"{elastic.state.step}", flush=True)
            raise SystemExit(0)
        raise SystemExit(43)  # no recovery stage in time: fail fast
    params, opt_state = out
    sampler = make_sampler()
    make_checkpointer()  # rank/size changed: rebind the shard schedule
    pending_continuity = last_loss
    just_recovered = True
    print(f"KF_RECOVERY_DONE rank={peer.rank} size={peer.size} "
          f"epoch={peer.version} step={elastic.state.step}", flush=True)


last_loss = None
pending_continuity = None  # survivor's pre-resize/pre-recovery loss
# bind the step context before the first span: a compute span tagged
# step=k is the computation OF step k+1 on every boot path — fresh
# init (0), joiner (synced position), cold restore (generation step) —
# so the goodput plane's step normalization holds uniformly
trace.set_context(rank=peer.rank, version=peer.version,
                  step=elastic.state.step)
while elastic.state.step < TOTAL_STEPS:
    t_step0 = time.perf_counter()
    idx = sampler.next_indices()
    batch = {"x": x[idx], "y": y[idx]}
    # the three structured train-step phases (docs/observability.md):
    # compute (jitted fwd/bwd incl. the host sync that materializes
    # the loss), grad-wire (the DCN all-reduce — lump or bucketed
    # pipeline), hook (schedule/consensus poll). Spans wrap the CALL
    # SITES; nothing records inside the jitted body (the trace-purity
    # lint holds the whole tree to that).
    t_compute0, compiled0 = time.perf_counter(), compiles.compile_s()
    with trace.span("step.compute", cat="step"):
        loss, grads = loss_and_grads(params, batch)
        loss = float(loss)
    t_compute = time.perf_counter()
    compute_ms = (t_compute - t_compute0) * 1e3
    # the ledger's seconds are the process's: what another thread
    # compiled meanwhile is not this span's, so never more than the span
    compile_ms = min((compiles.compile_s() - compiled0) * 1e3, compute_ms)
    try:
        with trace.span("step.grad_wire", cat="step"):
            if pipe is not None:
                # the agreed step tags the wire names: a replacement
                # joiner's fresh pipe must align with survivors' pipes
                grads = pipe.all_reduce(grads, step=elastic.state.step)
            else:
                buf = peer.all_reduce(
                    np.asarray(fuse(grads)),
                    name=f"g:{peer.version}:{elastic.state.step}")
    except KfError:
        if not RECOVER:
            raise
        try_recover()
        continue  # redo this step in the shrunken epoch
    # feed the live goodput families BEFORE after_step so a policy
    # consulted there sees THIS step's wire wait (a straggler spike
    # must be actionable the step it happens, not one step late)
    # compute is measured over the step.compute span's window (not
    # from t_step0) so the live kf_useful_ms_total agrees with what
    # the offline taxonomy bills as compute; sampling/batch assembly
    # stays unattributed in both planes
    t_wire = time.perf_counter()
    meter.observe("compile", compile_ms)
    meter.observe_step(
        compute_ms=compute_ms - compile_ms,
        wire_ms=(t_wire - t_compute) * 1e3)
    if just_recovered:
        # first data-plane collective of the recovered epoch succeeded:
        # this closes the MTTR window the recovery benchmark measures
        print(f"KF_MTTR resumed t={time.time() * 1e3:.1f} "
              f"rank={peer.rank} step={elastic.state.step}", flush=True)
        trace.event("recovery.resume", cat="recovery")
        just_recovered = False
    if pipe is None:
        grads = defuse(jnp.asarray(buf) / peer.size, grads)
    updates, opt_state = tx.update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)

    if pending_continuity is not None:
        print(f"KF_SURVIVOR_CONTINUITY rank={peer.rank} "
              f"pre={pending_continuity:.4f} post={loss:.4f}",
              flush=True)
        assert loss < pending_continuity + 0.5, (
            f"post-resize loss {loss:.4f} jumped from "
            f"{pending_continuity:.4f}: training state was lost")
        pending_continuity = None
    last_loss = loss

    if policy is not None:
        # the amortization horizon for priced re-grows
        policy.observe_progress(elastic.state.step, TOTAL_STEPS)
    t_hook0 = time.perf_counter()
    try:
        with trace.span("step.hook", cat="step"):
            changed = elastic.after_step()
    except KfError:
        # a peer died inside the resize consensus round (or the chaos
        # victim was *us* and this line never returns)
        if not RECOVER:
            raise
        try_recover()
        continue
    meter.observe("hook", (time.perf_counter() - t_hook0) * 1e3)
    if changed:
        if not elastic.state.keep:
            print(f"evicted at step {elastic.state.step}", flush=True)
            raise SystemExit(0)
        # one resize.resync span per planned epoch switch, so the
        # goodput plane bills the resync to "resize" instead of
        # leaving it in the unattributed residual
        t_rs0 = time.perf_counter()
        with trace.span("resize.resync", cat="elastic",
                        size=peer.size):
            elastic.sync_position()
            params = broadcast_variables(params, peer=peer)
        meter.observe("resize", (time.perf_counter() - t_rs0) * 1e3)
        sampler = make_sampler()
        make_checkpointer()  # rank/size changed: rebind the schedule
        pending_continuity = last_loss
        print(f"resized: epoch {peer.version} size={peer.size} "
              f"step={elastic.state.step}", flush=True)
    maybe_save()
    # the /metrics step-latency histogram (kf_step_latency_ms) — the
    # headline family an operator watches for stalls
    metrics.REGISTRY.observe("kf_step_latency_ms",
                             (time.perf_counter() - t_step0) * 1e3)

if ckpt is not None:
    ckpt.close()  # drain pending async generations before exit
print(f"KF_CONTINUITY_DONE rank={peer.rank} size={peer.size} "
      f"step={elastic.state.step} loss={last_loss:.4f}", flush=True)
