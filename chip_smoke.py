"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one TPU chip: four phases
    python chip_smoke.py --chips 4   # one four-chip host: the two paths
                                     # that exist only across chips

Drives the main path once through the entry points a user calls, at the
full width of the models the repo benchmarks, and checks what comes out
by the repo's own means. One chip:

- `trainer-resnet50`   `python bench.py`: ResNet-50 bf16, 128 x 224 x 224
  a chip, sync_sgd through `build_train_step_with_state`;
- `trainer-gpt2-small` `python -m kungfu_tpu.benchmarks.lm --size small
  --batch 8 --seq 1024 --attention flash`: the compiled step must hold
  the Pallas kernels, not their fallbacks, and the loss must fall;
- `launcher`           `python -m kungfu_tpu.run -np 1 -H 127.0.0.1:1 --
  <the ResNet-50 trainer>`: the runner stays off JAX, its worker owns
  the chip, and the worker's compile of the step the first phase
  compiled is a hit in the one compile cache;
- `serve`              `DecodeEngine` at the GPT-2-small preset, a few
  requests of mixed prompt length, the paged-attention kernel in the
  step: as served (bf16, `KF_SERVE_KERNEL=auto`), every decode step's
  logits against the functional gather's on the same pool; and with
  float32 weights and full-precision matmuls, in the resident and in
  the stream scheme, where the tokens equal `gpt_generate`'s one for
  one.

Four chips (`--chips 4`, these and nothing else):

- `spmd-4`   the ResNet-50 sync_sgd step on `data_mesh(4)` beside the
  same step on `data_mesh(1)`, one process;
- `kfrun-4`  `python -m kungfu_tpu.run -np 4 -H 127.0.0.1:4 -- <worker>`:
  one process a chip, gradients all-reduced over libkf.

This process never imports JAX: a chip belongs to one process at a
time, so each phase is a child process, one after the other, and every
child's first act is to fail unless `jax.devices()[0].platform` is
"tpu". Nothing here shrinks itself for a CPU. `libkf.so` is built first
from the tracked sources. One JSON line per phase; the first phase that
fails ends the run with a non-zero exit code; the last line of a run
that passed is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as the children reported it.
"""

import argparse
import glob
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

SELF = os.path.abspath(__file__)
ROOT = os.path.dirname(SELF)
DEVICE_TAG = "CHIP_SMOKE_DEVICE "
FACTS_TAG = "CHIP_SMOKE_FACTS "

#: timed steps of each trainer: enough to see the loss move
TRAIN_STEPS = 5
#: bench.py's own per-chip batch and image size on a TPU; the four-chip
#: phases build the same step and pass them on
RESNET_BATCH, IMAGE = 128, 224
LM_ARGS = ["--size", "small", "--batch", "8", "--seq", "1024",
           "--attention", "flash", "--iters", str(TRAIN_STEPS)]
LM_LAYERS = 12
#: serve.worker's defaults (KF_SERVE_MAX_BATCH, KF_KV_BLOCK_TOKENS) at
#: GPT-2's full context
SERVE = {"size": "small", "max_batch": 8, "block_tokens": 16,
         "max_len": 1024, "max_new": 8, "prompt_lens": (5, 40, 200),
         # a float32 pool takes twice the VMEM: the resident scheme
         # holds half the context there (`paged_plan`)
         "max_len_f32_resident": 512}
KFRUN_STEPS = 3


class PhaseFailed(Exception):
    pass


def check(ok, what):
    if not ok:
        raise PhaseFailed(what)


# -- children: the only code here that touches JAX ----------------------------


def _require_tpu():
    """Every child's first act: report the device, fail off the chip."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    print(DEVICE_TAG + json.dumps(
        {"platform": dev.platform, "kind": dev.device_kind,
         "count": len(devices)}), flush=True)
    return devices


def child_trainer_resnet50():
    import runpy

    _require_tpu()
    sys.argv = ["bench.py", "--steps", str(TRAIN_STEPS)]
    runpy.run_path(os.path.join(ROOT, "bench.py"), run_name="__main__")


def child_trainer_gpt2_small():
    import runpy

    _require_tpu()
    sys.argv = ["kungfu_tpu.benchmarks.lm"] + LM_ARGS
    runpy.run_module("kungfu_tpu.benchmarks.lm", run_name="__main__",
                     alter_sys=True)


def _serve(model, params, prompts, kernel, max_len,
           against_functional=False):
    """One DecodeEngine as serve.worker builds it: admit / step /
    release over `prompts`. Returns the facts the parent checks. This
    looks inside the engine on purpose (`_decode`): what is compiled,
    counted and compared is the step the engine serves from, not a
    second copy of it."""
    import functools

    import numpy as np

    import jax

    from kungfu_tpu.compile_cache import timed_compile
    from kungfu_tpu.serve import paged
    from kungfu_tpu.serve.engine import DecodeEngine

    engine = DecodeEngine(
        model, params, max_batch=SERVE["max_batch"],
        block_tokens=SERVE["block_tokens"], max_len=max_len,
        kernel=kernel)
    # the decode step at its one fixed shape
    tables = engine.pool.batch_tables([], engine.max_blocks,
                                      pad_rows=engine.max_batch)
    zeros = np.zeros(engine.max_batch, np.int32)
    compile_s, compiled = timed_compile(
        engine._decode, params, engine.pool_k, engine.pool_v, tables,
        zeros, zeros)
    facts = {"kernel": engine.kernel, "max_len": max_len,
             "dtype": str(np.dtype(model.config.dtype)),
             "pallas_kernels": compiled.as_text().count(
                 "tpu_custom_call"),
             "compile_s": round(compile_s, 2)}

    steps = []
    if against_functional:
        # every decode step's logits, as the engine served them,
        # against the stock-JAX gather on the same pool
        # (tests/test_serve.py's parity check, here on the chip at the
        # served dtype)
        functional = jax.jit(functools.partial(
            paged.decode_step, model.config, kernel="functional"))
        served = engine._decode

        def both(params, pool_k, pool_v, tables, lengths, tokens):
            # the gather first: the engine's step donates the pools
            want = np.asarray(functional(params, pool_k, pool_v, tables,
                                         lengths, tokens)[0])
            out = served(params, pool_k, pool_v, tables, lengths, tokens)
            live = np.asarray(lengths) > 0
            got, want = np.asarray(out[0])[live], want[live]
            picked = np.take_along_axis(
                want, got.argmax(-1)[:, None], -1)[:, 0]
            steps.append({
                "rows": int(live.sum()),
                "finite": bool(np.isfinite(got).all()),
                "max_abs_logit_diff": float(np.abs(got - want).max()),
                # how far below the gather's best logit the served
                # token sits under the gather's own logits: 0 unless a
                # near-tie fell the other way
                "served_token_deficit": float(
                    (want.max(-1) - picked).max())})
            return out

        engine._decode = both

    max_new = SERVE["max_new"]
    t0 = time.perf_counter()
    got = {}
    for name, prompt in prompts.items():
        token, _done = engine.admit(name, prompt, max_new)
        got[name] = [token]
    for _ in range(max_new + 2):
        emitted, preempted = engine.step()
        if preempted:
            raise SystemExit(f"serve: preempted {preempted}")
        for name, (token, _done) in emitted.items():
            got[name].append(token)
        if not engine.live():
            break
    facts.update({
        "serve_s": round(time.perf_counter() - t0, 2), "tokens": got,
        "steps": steps, "blocks_in_use": engine.pool.blocks_in_use,
        "pool_invariants": engine.pool.check_invariants()})
    return facts


def child_serve():
    """The decode engine three times. As served (bf16, the scheme
    `KF_SERVE_KERNEL=auto` resolves to): it answers, and at every
    decode step the logits it served sit within bf16's reach of the
    functional gather's. Then with float32 weights and full-precision
    matmuls, where a token is no longer decided by rounding, once in
    each scheme: tokens equal `gpt_generate`'s, one for one. (At bf16
    with seeded random weights the top-2 logit margin is often below
    what two compilations of the same math differ by — `gpt_generate`
    and its own unrolled loop pick different tokens on the chip — so
    token equality means nothing there; see PERF.md, PR 21.)"""
    _require_tpu()
    import numpy as np

    import jax
    import jax.numpy as jnp

    from kungfu_tpu import compile_cache
    from kungfu_tpu.env import env_choice
    from kungfu_tpu.models import gpt_generate
    from kungfu_tpu.serve.engine import build_lm

    cache = compile_cache.enable()
    model, params, _ = build_lm(SERVE["size"],
                                max_position=SERVE["max_len"])
    vocab = model.config.vocab_size
    rng = np.random.default_rng(0)
    prompts = {f"len{n}": rng.integers(0, vocab, n).tolist()
               for n in SERVE["prompt_lens"]}
    knob = env_choice("KF_SERVE_KERNEL", "auto",
                      ("auto", "kernel", "functional"))
    served = _serve(model, params, prompts, knob, SERVE["max_len"],
                    against_functional=True)
    served["vocab"] = vocab

    with jax.default_matmul_precision("highest"):
        model, params, _ = build_lm(SERVE["size"],
                                    max_position=SERVE["max_len"],
                                    dtype=jnp.float32)
        exact = {
            scheme: _serve(model, params, prompts, scheme, max_len)
            for scheme, max_len in (
                ("resident", SERVE["max_len_f32_resident"]),
                ("stream", SERVE["max_len"]))}
        generate = jax.jit(
            lambda p, t: gpt_generate(model, p, t, SERVE["max_new"]))
        reference = {
            name: [int(t) for t in np.asarray(generate(
                params, jnp.asarray(prompt, jnp.int32)[None])
            )[0, len(prompt):]]
            for name, prompt in prompts.items()}
    print(FACTS_TAG + json.dumps({
        "served": served, "exact": exact, "reference": reference,
        "compile_cache": cache.as_dict()}), flush=True)


def _placement(state):
    """Where the worker-stacked state landed: per device, the bytes of
    state shards and of every live array, and the allocator's view."""
    import jax

    held, live, rows = {}, {}, set()
    for leaf in jax.tree_util.tree_leaves(state):
        for shard in leaf.addressable_shards:
            rows.add(shard.data.shape[0])
            held[shard.device.id] = (held.get(shard.device.id, 0)
                                     + shard.data.nbytes)
    for arr in jax.live_arrays():
        for shard in arr.addressable_shards:
            live[shard.device.id] = (live.get(shard.device.id, 0)
                                     + shard.data.nbytes)
    stats = {d.id: d.memory_stats() or {} for d in jax.devices()}
    return {
        "rows_per_shard": sorted(rows),
        "state_bytes": held, "live_bytes": live,
        "bytes_in_use": {i: s.get("bytes_in_use")
                         for i, s in stats.items()},
        "peak_bytes_in_use": {i: s.get("peak_bytes_in_use")
                              for i, s in stats.items()},
    }


def child_spmd_4():
    """bench.py's step on data_mesh(4) beside data_mesh(1)."""
    devices = _require_tpu()
    import gc

    import jax
    import numpy as np

    import bench
    from kungfu_tpu import compile_cache
    from kungfu_tpu.parallel import data_mesh

    cache = compile_cache.enable()
    facts = {"chips": len(devices), "runs": {}}
    row0 = {}
    for chips in (len(devices), 1):
        step, state, batch = bench.build(data_mesh(chips), RESNET_BATCH,
                                         IMAGE)
        gc.collect()
        if chips > 1:
            facts["placement"] = _placement(state)
        compile_s, compiled = compile_cache.timed_compile(
            step, *state, batch)
        text = compiled.as_text()
        del compiled
        losses, identical = [], []
        for _ in range(TRAIN_STEPS):
            *state, loss = step(*state, batch)
            losses.append(float(loss))
            identical.append(bench.rows_identical(state))
        row0[chips] = jax.device_get(
            jax.tree_util.tree_map(lambda x: x[0], state[0]))
        facts["runs"][chips] = {
            "all_reduce": "all-reduce" in text,
            "losses": losses, "rows_identical": identical,
            "compile_s": round(compile_s, 2),
        }
        del step, state, batch
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(np.max(np.abs(a - b))),
        row0[len(devices)], row0[1])
    facts["params_max_abs_diff_vs_one_chip"] = max(
        jax.tree_util.tree_leaves(diffs))
    facts["compile_cache"] = cache.as_dict()
    print(FACTS_TAG + json.dumps(facts), flush=True)


def child_kfrun_worker():
    """One of kfrun-4's workers: ResNet-50 gradients on its own chip,
    averaged over libkf — the multi-process form of sync SGD
    (examples/mnist_multiworker.py at the benchmark's model)."""
    devices = _require_tpu()
    import hashlib

    import numpy as np

    import jax
    import jax.numpy as jnp
    import optax

    import bench
    import kungfu_tpu
    from kungfu_tpu import compile_cache
    from kungfu_tpu.initializer import broadcast_variables
    from kungfu_tpu.ops.collective import defuse, fuse

    compile_cache.enable()
    peer = kungfu_tpu.init()
    model, loss_fn = bench.model_and_loss()
    kx, ky = jax.random.split(jax.random.PRNGKey(100 + peer.rank))
    batch = {
        "x": jax.random.normal(kx, (RESNET_BATCH, IMAGE, IMAGE, 3),
                               jnp.float32),
        "y": jax.random.randint(ky, (RESNET_BATCH,), 0, 1000, jnp.int32),
    }
    # every rank draws its own weights; rank 0's are what all train
    variables = model.init(jax.random.PRNGKey(peer.rank), batch["x"][:2],
                           train=True)
    params = broadcast_variables(variables["params"], peer=peer)
    stats = variables["batch_stats"]
    tx = optax.sgd(0.1, momentum=0.9)
    opt = tx.init(params)

    @jax.jit
    def local_grads(params, stats, batch):
        (loss, stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, stats, batch)
        return loss, stats, grads

    @jax.jit
    def apply(params, opt, grads):
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt

    losses = []
    for step in range(KFRUN_STEPS):
        loss, stats, grads = local_grads(params, stats, batch)
        buf = peer.all_reduce(np.asarray(fuse(grads)), name=f"g:{step}")
        grads = defuse(jnp.asarray(buf) / peer.size, grads)
        params, opt = apply(params, opt, grads)
        losses.append(float(loss))
    flat = np.asarray(fuse(params))
    dev = devices[0]
    print(FACTS_TAG + json.dumps({
        "rank": peer.rank, "size": peer.size,
        "slot": os.environ.get("TPU_VISIBLE_DEVICES"),
        "device_id": dev.id, "coords": list(getattr(dev, "coords", ())),
        # the chip's device file this process holds open
        "device_files": sorted(
            {os.path.realpath(p)
             for p in glob.glob("/proc/self/fd/*")
             if os.path.realpath(p).startswith(("/dev/accel",
                                                "/dev/vfio/"))}),
        "losses": losses, "param_bytes": int(flat.nbytes),
        "params_sha256": hashlib.sha256(flat.tobytes()).hexdigest(),
    }), flush=True)
    peer.barrier()


CHILDREN = {
    "trainer-resnet50": child_trainer_resnet50,
    "trainer-gpt2-small": child_trainer_gpt2_small,
    "serve": child_serve,
    "spmd-4": child_spmd_4,
    "kfrun-worker": child_kfrun_worker,
}


# -- parent: no JAX below this line -------------------------------------------


def run(cmd, timeout, env=None):
    """Run `cmd` in a process group of its own and return its stdout;
    the whole group is gone when this returns, however it ends."""
    proc = subprocess.Popen(cmd, cwd=ROOT, env=env, text=True,
                            stdout=subprocess.PIPE,
                            start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        raise PhaseFailed(f"no end after {timeout} s: {cmd[1:]}")
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        proc.wait()
    if proc.returncode:
        raise PhaseFailed(f"exit code {proc.returncode}: {cmd[1:]}; "
                          f"its last output: {out[-800:]!r}")
    return out


def run_child(name, timeout=600, env=None):
    return run([sys.executable, SELF, "--child", name], timeout, env=env)


def tagged(out, tag):
    return [json.loads(line[len(tag):]) for line in out.splitlines()
            if line.startswith(tag)]


def one_device(out, count):
    """The child's device report: a TPU, `count` devices."""
    reports = tagged(out, DEVICE_TAG)
    check(len(reports) == 1, f"expected one device report: {reports}")
    dev = reports[0]
    check(dev["platform"] == "tpu", f"not a TPU: {dev}")
    check(dev["count"] == count,
          f"expected {count} device(s), the child saw {dev['count']}")
    return dev


def metric_details(out):
    """`details` of the JSON line the benchmark entry points print."""
    rows = [line for line in out.splitlines()
            if line.startswith('{"metric"')]
    check(rows, "the entry point printed no result line")
    row = json.loads(rows[-1])
    return {"value": row["value"], "unit": row["unit"], **row["details"]}


def check_resnet50(d):
    check(d["platform"] == "tpu" and d["chips"] == 1, f"device: {d}")
    check((d["per_chip_batch"], d["image_size"], d["iters"])
          == (RESNET_BATCH, IMAGE, TRAIN_STEPS),
          f"bench.py did not run at full size: {d}")
    check(math.isfinite(d["final_loss"]), f"loss {d['final_loss']}")
    check(d["rows_identical"] is True, "worker rows differ")
    return ["128x224x224 bf16 a chip", "loss finite",
            "worker rows bit-identical"]


def phase_trainer_resnet50(ctx):
    out = run_child("trainer-resnet50")
    d = metric_details(out)
    ctx["resnet50"] = d
    return {"device": one_device(out, 1), "checked": check_resnet50(d),
            "compile_s": d["compile_s"],
            "compile_cache": d["compile_cache"],
            "step_time_ms": d["step_time_ms"], "final_loss":
            d["final_loss"]}


def phase_trainer_gpt2_small(ctx):
    out = run_child("trainer-gpt2-small")
    d = metric_details(out)
    check(d["platform"] == "tpu" and d["devices"] == 1, f"device: {d}")
    check((d["size"], d["per_data_batch"], d["seq"], d["attention"])
          == ("small", 8, 1024, "flash"),
          f"the LM benchmark did not run at full size: {d}")
    # flash forward, dq and dkv in every layer, the fused head forward
    # and its backward
    want = 3 * LM_LAYERS + 2
    check(d["pallas_kernels"] >= want,
          f"{d['pallas_kernels']} Pallas kernels in the compiled step, "
          f"expected {want}: a fallback took a kernel's place")
    losses = d["losses"]
    check(all(math.isfinite(x) for x in losses), f"losses {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return {"device": one_device(out, 1),
            "checked": ["small b8 T1024 bf16 flash",
                        f"{d['pallas_kernels']} Pallas kernels in the "
                        "compiled step", "loss finite and falling"],
            "compile_s": d["compile_s"],
            "compile_cache": d["compile_cache"],
            "step_time_ms": d["step_time_ms"], "losses": losses}


def worker_logs(logdir):
    out = []
    for path in sorted(glob.glob(os.path.join(logdir, "worker-*.log"))):
        with open(path, errors="replace") as f:
            out.append(f.read())
    return out


def kfrun(np_, worker, timeout):
    """`python -m kungfu_tpu.run -np N -H 127.0.0.1:N -- <worker>`;
    returns each worker's output (kfrun keeps it in its log directory)."""
    logdir = tempfile.mkdtemp(prefix="chip_smoke-kfrun-")
    try:
        run([sys.executable, "-m", "kungfu_tpu.run", "-np", str(np_),
             "-H", f"127.0.0.1:{np_}", "-logdir", logdir, "--",
             sys.executable, SELF, "--child", worker], timeout)
    except PhaseFailed as e:
        tails = [log[-1500:] for log in worker_logs(logdir)]
        raise PhaseFailed(f"{e}; worker logs end: {tails!r}")
    else:
        logs = worker_logs(logdir)
        check(len(logs) == np_, f"{len(logs)} worker logs, not {np_}")
        return logs
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def phase_launcher(ctx):
    # the runner must leave the chip to its worker
    run([sys.executable, "-c",
         "import sys, kungfu_tpu.run.__main__; "
         "sys.exit('jax' in sys.modules)"], 60)
    (log,) = kfrun(1, "trainer-resnet50", 600)
    d = metric_details(log)
    checked = check_resnet50(d) + ["runner imports no JAX",
                                   "worker saw exactly one TPU device"]
    cold, warm = ctx["resnet50"], d
    check(warm["compile_cache"]["dir"] == cold["compile_cache"]["dir"],
          f"two caches: {cold['compile_cache']['dir']} and "
          f"{warm['compile_cache']['dir']}")
    check(warm["compile_cache"]["hits"] >= 1,
          "the worker compiled the step the first phase had compiled: "
          f"{warm['compile_cache']}")
    checked.append("worker's compile was a cache hit")
    if not cold["compile_cache"]["hits"]:
        # the first phase found nothing in the cache: it compiled the
        # step cold in this run. (A miss says less: a program that
        # compiles in under a second is never kept, and misses always)
        check(warm["compile_s"] < 0.5 * cold["compile_s"],
              f"warm compile {warm['compile_s']} s, cold "
              f"{cold['compile_s']} s")
        checked.append("warm compile under half the cold one")
    return {"device": one_device(log, 1), "checked": checked,
            "compile_s": warm["compile_s"],
            "compile_s_first_phase": cold["compile_s"],
            "compile_cache": warm["compile_cache"],
            "step_time_ms": d["step_time_ms"]}


#: how far the bf16 logits of the paged kernel (exact f32 scores on the
#: VPU) and of the functional gather (a bf16 einsum on the MXU) may sit
#: apart after 12 layers, on the same pool: 0.037 at the first step
#: and at most 0.06 between any two bf16 paths over all steps (my chip
#: runs, PR 21, calls 3-4). A kernel that reads the wrong block or masks
#: the wrong position is off by O(1)
BF16_LOGIT_ATOL = 0.1


def phase_serve(ctx):
    out = run_child("serve",
                    env={**os.environ, "KF_SERVE_KERNEL": "auto"})
    (f,) = tagged(out, FACTS_TAG)
    served, exact = f["served"], f["exact"]
    check(served["kernel"] in ("resident", "stream"),
          f"KF_SERVE_KERNEL=auto resolved to {served['kernel']!r} on a "
          "TPU")
    check(sorted(exact) == ["resident", "stream"]
          and all(run_["kernel"] == scheme
                  for scheme, run_ in exact.items()),
          f"schemes: { {k: v['kernel'] for k, v in exact.items()} }")
    for run_ in (served, *exact.values()):
        check(run_["pallas_kernels"] == LM_LAYERS,
              f"{run_['pallas_kernels']} paged-attention kernels in the "
              f"compiled decode step, expected {LM_LAYERS}")
        check(all(len(t) == SERVE["max_new"]
                  for t in run_["tokens"].values()),
              f"short answers: {run_['tokens']}")
        check(run_["blocks_in_use"] == 0
              and run_["pool_invariants"] == [],
              f"pool after release: {run_}")
    check(all(0 <= t < served["vocab"]
              for ts in served["tokens"].values() for t in ts),
          f"tokens out of range: {served['tokens']}")
    n = len(served["tokens"])
    steps = served["steps"]
    # the first token comes from the prefill, the others one a step
    check(len(steps) == SERVE["max_new"] - 1
          and all(st["rows"] == n for st in steps),
          f"compared decode steps: {steps}")
    diffs = [st["max_abs_logit_diff"] for st in steps]
    check(all(st["finite"] for st in steps)
          and max(diffs) <= BF16_LOGIT_ATOL,
          f"{served['kernel']} kernel's logits differ from the "
          f"functional gather's by {diffs} a step at {served['dtype']}")
    for scheme, run_ in exact.items():
        check(run_["tokens"] == f["reference"],
              f"{scheme} tokens differ from gpt_generate: "
              f"{run_['tokens']} != {f['reference']}")
    return {"device": one_device(out, 1),
            "checked": [f"as served ({served['dtype']}, kernel="
                        f"{served['kernel']}): {n} requests answered, "
                        f"served logits within {BF16_LOGIT_ATOL} of the "
                        f"functional path at each of {len(steps)} decode "
                        "steps"]
            + [f"{run_['dtype']} with full-precision matmuls, kernel="
               f"{scheme} at max_len {run_['max_len']}: {n} requests x "
               f"{SERVE['max_new']} tokens equal gpt_generate"
               for scheme, run_ in exact.items()]
            + [f"{LM_LAYERS} Pallas kernels in each compiled decode "
               "step", "all blocks released"],
            "compile_s": served["compile_s"],
            "compile_s_exact": {k: v["compile_s"]
                                for k, v in exact.items()},
            "serve_s": served["serve_s"],
            "kernel_vs_functional_max_abs_logit_diff": diffs,
            "served_token_deficit": [st["served_token_deficit"]
                                     for st in steps],
            "compile_cache": f["compile_cache"]}


def phase_spmd_4(ctx):
    out = run_child("spmd-4")
    (f,) = tagged(out, FACTS_TAG)
    # JSON turned the device ids into strings
    four, one = f["runs"]["4"], f["runs"]["1"]
    place = f["placement"]
    check(place["rows_per_shard"] == [1],
          f"a shard of the stacked state holds {place['rows_per_shard']} "
          "rows")
    held = place["state_bytes"]
    check(len(held) == 4 and len(set(held.values())) == 1,
          f"state bytes per device: {held}")
    live = place["live_bytes"]
    # a stack left whole on one device would weigh three models more
    check(max(live.values()) - min(live.values()) < 16 << 20,
          f"live bytes per device after placement: {live}")
    check(four["all_reduce"], "no all-reduce in the four-chip step")
    for run_ in (four, one):
        check(all(math.isfinite(x) for x in run_["losses"]),
              f"losses {run_['losses']}")
        check(all(run_["rows_identical"]),
              f"rows identical per step: {run_['rows_identical']}")
    # same weights, same batch on every chip: the first step's loss is
    # the one-chip loss
    check(abs(four["losses"][0] - one["losses"][0])
          <= 1e-3 * abs(one["losses"][0]),
          f"first loss {four['losses'][0]} on four chips, "
          f"{one['losses'][0]} on one")
    return {"device": one_device(out, 4),
            "checked": ["each device holds one row of the stacked state "
                        "and no more", "all-reduce in the compiled step",
                        "rows bit-identical after each step",
                        "loss finite", "first loss equals one chip's"],
            "compile_s": four["compile_s"],
            "compile_s_one_chip": one["compile_s"],
            "losses": four["losses"], "losses_one_chip": one["losses"],
            "params_max_abs_diff_vs_one_chip":
            f["params_max_abs_diff_vs_one_chip"],
            "placement": place}


def phase_kfrun_4(ctx):
    logs = kfrun(4, "kfrun-worker", 600)
    devs = [one_device(log, 1) for log in logs]
    facts = [tagged(log, FACTS_TAG) for log in logs]
    check(all(len(f) == 1 for f in facts), f"worker facts: {facts}")
    facts = sorted((f[0] for f in facts), key=lambda f: f["rank"])
    check([f["rank"] for f in facts] == [0, 1, 2, 3]
          and all(f["size"] == 4 for f in facts), f"ranks: {facts}")
    check(len({f["slot"] for f in facts}) == 4,
          f"chip slots: {[f['slot'] for f in facts]}")
    # which chip a worker holds: libtpu numbers the one chip of every
    # one-chip slice 0, and the slot only echoes what the launcher set,
    # so the chip's own device file (its VFIO group; /dev/vfio/vfio is
    # the container all share) is what tells four chips apart
    files = [set(f["device_files"]) - {"/dev/vfio/vfio"} for f in facts]
    check(all(files), f"a worker holds no chip's device file: {files}")
    check(len(set().union(*files)) == sum(len(x) for x in files),
          f"two workers hold one chip's device file: {files}")
    check(len({f["params_sha256"] for f in facts}) == 1,
          "parameters differ after the all-reduced steps: "
          f"{[f['params_sha256'][:12] for f in facts]}")
    check(all(math.isfinite(x) for f in facts for x in f["losses"]),
          f"losses: {[f['losses'] for f in facts]}")
    check(len(set(json.dumps(d, sort_keys=True) for d in devs)) == 1,
          f"devices: {devs}")
    return {"device": devs[0],
            "checked": ["four workers, one TPU device each",
                        "four chip slots, four different device files "
                        "held open (device ids are 0 in every one-chip "
                        "slice)", "identical parameters on all "
                        f"four after {KFRUN_STEPS} all-reduced steps",
                        "loss finite"],
            "workers": [{k: f[k] for k in ("rank", "slot", "device_id",
                                           "coords", "device_files",
                                           "losses")} for f in facts],
            "params_sha256": facts[0]["params_sha256"],
            "param_bytes": facts[0]["param_bytes"]}


PHASES = {
    1: [("trainer-resnet50", phase_trainer_resnet50),
        ("trainer-gpt2-small", phase_trainer_gpt2_small),
        ("launcher", phase_launcher),
        ("serve", phase_serve)],
    4: [("spmd-4", phase_spmd_4),
        ("kfrun-4", phase_kfrun_4)],
}


def build_native(ctx):
    # -B: whatever libkf.so lies on disk is not part of the program
    run(["make", "-B", "-C", os.path.join(ROOT, "kungfu_tpu", "native"),
         "libkf.so"], 600)
    return {"checked": ["libkf.so built from the tracked sources"]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=sorted(PHASES),
                    default=1)
    ap.add_argument("--child", choices=sorted(CHILDREN),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        CHILDREN[args.child]()
        return 0

    ctx, device = {}, None
    for name, phase in [("build", build_native)] + PHASES[args.chips]:
        t0 = time.perf_counter()
        try:
            result = phase(ctx)
            seen = result.pop("device", None)
            if name == "kfrun-4":
                # four one-chip workers; the count is the mesh phase's
                seen = seen and {**seen, "count": device["count"]}
            check(device is None or seen is None or seen == device,
                  f"device changed between phases: {device} then {seen}")
            device = seen or device
            check(name != PHASES[args.chips][-1][0]
                  or device["count"] == args.chips, f"device {device}")
        except PhaseFailed as e:
            print(json.dumps({"phase": name, "ok": False, "seconds":
                              round(time.perf_counter() - t0, 1),
                              "error": str(e)}), flush=True)
            return 1
        print(json.dumps({"phase": name, "ok": True, "seconds":
                          round(time.perf_counter() - t0, 1), **result}),
              flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
