"""Run every BASELINE config and record the results.

The reference publishes relative-throughput / convergence plots across
five scenarios (reference: README.md:188-205, benchmarks/{system,
adaptation,monitoring}/); `BASELINE.json` declares the TPU-rebuild
equivalents. This module runs the four non-headline configs (the
ResNet-50 headline lives in `bench.py`) and merges the numbers into
`BASELINE.json.published`:

  mnist-slp          MNIST SLP + SyncSGD: throughput + final accuracy
                     (reference: examples/tf2_mnist_gradient_tape.py).
  pair-convergence   PairAveraging vs SyncSGD vs SMA on the same data +
                     step budget: does decentralized gossip converge?
                     (reference: PairAveragingOptimizer claims,
                     README.md:188-193).
  bert-sma-gns       BERT-ish encoder + SMA, with/without the
                     gradient-noise-scale monitor: monitoring overhead
                     (reference: benchmarks/monitoring/benchmark.py).
  adaptation         online resize latency via the elastic runtime
                     (reference: benchmarks/adaptation/).

Each subcommand prints ONE JSON line. `--all` runs each config in a
subprocess pinned to an 8-device virtual CPU mesh (deterministic,
hardware-independent; the headline number is the TPU one) and rewrites
`BASELINE.json`:

  python -m kungfu_tpu.benchmarks.publish --all [--json path/BASELINE.json]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

N_WORKERS = 8  # virtual CPU mesh width for the published configs

#: repo root (BASELINE.json / BENCH_rNN.json / CHANGES.md live here)
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def current_round(changes_path: str = "") -> int:
    """The repo's current PR round, from CHANGES.md ("PR N (round M)"
    entries — the one place every session appends to). Rounds 1-5
    emitted `BENCH_rNN.json` per round; 6-11 silently stopped, so the
    perf-trajectory feed read empty — `emit_bench`/`--check-round`
    restore and enforce the per-round file."""
    path = changes_path or os.path.join(REPO, "CHANGES.md")
    try:
        with open(path, encoding="utf-8") as f:
            rounds = re.findall(r"\(round (\d+)\)", f.read())
    except OSError:
        return 0
    return max((int(r) for r in rounds), default=0)


def bench_path_for(rnd: int) -> str:
    return os.path.join(REPO, f"BENCH_r{rnd:02d}.json")


def emit_bench(rnd: int, parsed: dict, cmd: str, tail: str,
               rc: int = 0) -> str:
    """Write the round's `BENCH_rNN.json` in the r01-r05 schema
    ({n, cmd, rc, tail, parsed}) so the perf-trajectory feed keeps one
    headline metric per round."""
    path = bench_path_for(rnd)
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"n": rnd, "cmd": cmd, "rc": rc,
                   "tail": tail[-4000:], "parsed": parsed}, f,
                  indent=2)
        f.write("\n")
    return path


def publish_result(metric: str, result: dict, parsed: dict, cmd: str,
                   json_path: str = "") -> str:
    """Merge one benchmark's `result` into BASELINE.json under
    ``published[metric]`` (stamping the current round) and emit the
    round's BENCH_rNN.json with `parsed` as the headline — the one
    publish protocol, so the goodput/strategy/transport publishers
    cannot drift from each other or from the round gate."""
    json_path = json_path or os.path.join(REPO, "BASELINE.json")
    with open(json_path) as f:
        baseline = json.load(f)
    rnd = current_round()
    result["round"] = rnd
    baseline.setdefault("published", {})[metric] = result
    with open(json_path, "w") as f:
        json.dump(baseline, f, indent=2)
        f.write("\n")
    bench_path = emit_bench(rnd, parsed=parsed, cmd=cmd,
                            tail=json.dumps(result))
    print(f"published {metric} -> {json_path} and {bench_path}",
          flush=True)
    return bench_path


def check_round() -> int:
    """CI gate (scripts/run-all.sh stage 0): the current round's
    BENCH file must exist — a round that only updates BASELINE.json
    leaves the perf trajectory blind, loudly."""
    rnd = current_round()
    if rnd <= 0:
        print("publish --check-round: no '(round N)' entries in "
              "CHANGES.md", file=sys.stderr)
        return 1
    path = bench_path_for(rnd)
    if not os.path.exists(path):
        print(
            f"publish --check-round: BENCH_r{rnd:02d}.json is MISSING "
            f"for the current round {rnd} (CHANGES.md). Every round "
            "must publish its headline metric — run e.g. `python -m "
            "kungfu_tpu.benchmarks.goodput --publish` (or emit_bench "
            "from the round's own benchmark) before shipping.",
            file=sys.stderr)
        return 1
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        doc = e  # unreadable/truncated: same loud diagnostic below
    if not isinstance(doc, dict) or doc.get("n") != rnd \
            or not isinstance(doc.get("parsed"), dict):
        detail = (f"n={doc.get('n')!r}" if isinstance(doc, dict)
                  else repr(doc))
        print(f"publish --check-round: {path} is malformed "
              f"({detail}, round {rnd})", file=sys.stderr)
        return 1
    print(f"publish --check-round: BENCH_r{rnd:02d}.json ok "
          f"({doc['parsed'].get('metric')})")
    return 0


def _synthetic_mnist(n=8192, seed=0):
    """Deterministic MNIST-shaped data (examples/common.py without the
    examples/ dir on sys.path)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    y = rng.integers(0, 10, size=n)
    centers = rng.normal(0.5, 0.5, size=(10, 28 * 28))
    x = centers[y] + rng.normal(0.0, 0.35, size=(n, 28 * 28))
    x = np.clip(x, 0.0, 1.0).astype(np.float32).reshape(n, 28, 28, 1)
    return x, y.astype(np.int32)


def _slp_setup(mesh, lr=0.1):
    import jax
    import optax

    from kungfu_tpu.models import SLP

    model = SLP(num_classes=10)
    x, y = _synthetic_mnist()
    params = model.init(jax.random.PRNGKey(0), x[:1])["params"]

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()

    def acc_fn(params, batch):
        logits = model.apply({"params": params}, batch["x"])
        return (logits.argmax(-1) == batch["y"]).mean()

    return model, x, y, params, loss_fn, acc_fn


def _train(tx, mesh, steps, batch_per_worker, loss_fn, params, x, y,
           per_worker_streams=False):
    """Run `steps` of the compiled SPMD step; returns final stacked params
    and wall seconds over the timed region."""
    import jax

    from kungfu_tpu.data import ElasticSampler
    from kungfu_tpu.parallel import (
        build_train_step,
        init_worker_state,
        replicate_to_workers,
        shard_batch,
    )

    n = jax.device_count()
    params_s = replicate_to_workers(params, mesh)
    opt_s = init_worker_state(tx, params_s, mesh)
    step = build_train_step(loss_fn, tx, mesh)

    if per_worker_streams:
        # averaging runs decorrelate rows: per-worker sample streams
        samplers = [
            ElasticSampler(len(x), batch_per_worker, rank=r, size=n, seed=1)
            for r in range(n)
        ]

        def next_batch():
            import numpy as np

            idx = np.concatenate([s.next_indices() for s in samplers])
            return {"x": x[idx], "y": y[idx]}
    else:
        sampler = ElasticSampler(len(x), batch_per_worker * n, rank=0,
                                 size=1, seed=1)

        def next_batch():
            idx = sampler.next_indices()
            return {"x": x[idx], "y": y[idx]}

    # warmup/compile step outside the timed region
    b0 = shard_batch(next_batch(), mesh)
    params_s, opt_s, _ = step(params_s, opt_s, b0)
    jax.block_until_ready(params_s)
    t0 = time.perf_counter()
    for _ in range(steps):
        batch = shard_batch(next_batch(), mesh)
        params_s, opt_s, _ = step(params_s, opt_s, batch)
    jax.block_until_ready(params_s)
    return params_s, time.perf_counter() - t0


def _accuracy(params_s, acc_fn, mesh, x, y, row=0):
    """Full-dataset accuracy of worker `row`'s model."""
    import jax
    import numpy as np

    params = jax.tree_util.tree_map(lambda t: t[row], params_s)
    correct = 0
    for i in range(0, len(x), 2048):
        batch = {"x": x[i:i + 2048], "y": y[i:i + 2048]}
        correct += float(acc_fn(params, batch)) * len(batch["y"])
    return correct / len(x)


def run_mnist_slp(args):
    import jax

    from kungfu_tpu.optimizers import sync_sgd
    import optax

    from kungfu_tpu.parallel import data_mesh

    n = jax.device_count()
    mesh = data_mesh(n)
    model, x, y, params, loss_fn, acc_fn = _slp_setup(mesh)
    tx = sync_sgd(optax.sgd(args.lr))
    params_s, secs = _train(tx, mesh, args.steps, args.batch, loss_fn,
                            params, x, y)
    acc = _accuracy(params_s, jax.jit(acc_fn), mesh, x, y)
    images = args.steps * args.batch * n
    return {
        "config": (
            f"MNIST-shaped SLP, SyncSGD(sgd {args.lr}), {n} workers x "
            f"batch {args.batch}, {args.steps} steps, synthetic data "
            "(zero-egress; examples/common.py distribution)"
        ),
        "final_train_accuracy": round(acc, 4),
        "images_per_sec": round(images / secs, 1),
        "workers": n,
    }


def run_pair_convergence(args):
    import jax
    import optax

    from kungfu_tpu.optimizers import pair_averaging, sma, sync_sgd
    from kungfu_tpu.parallel import data_mesh

    n = jax.device_count()
    mesh = data_mesh(n)
    model, x, y, params, loss_fn, acc_fn = _slp_setup(mesh)
    jit_acc = jax.jit(acc_fn)
    budgets = {"converged": (args.steps, args.lr),
               "tight_budget": (max(args.steps // 30, 5), args.lr / 5)}
    out = {}
    for bname, (steps, lr) in budgets.items():
        accs = {}
        for name, tx, streams in (
            ("sync_sgd", sync_sgd(optax.sgd(lr)), False),
            ("pair_averaging", pair_averaging(optax.sgd(lr)), True),
            ("sma", sma(optax.sgd(lr), alpha=0.1), True),
        ):
            params_s, _ = _train(tx, mesh, steps, args.batch, loss_fn,
                                 params, x, y, per_worker_streams=streams)
            # averaging runs: every row must independently be a good model
            row_accs = [_accuracy(params_s, jit_acc, mesh, x, y, row=r)
                        for r in (0, n - 1)]
            accs[name] = round(min(row_accs), 4)
        out[bname] = {"steps": steps, "lr": lr, "accuracy": accs,
                      "pair_vs_sync_gap": round(
                          accs["sync_sgd"] - accs["pair_averaging"], 4)}
    return {
        "config": (
            f"{n} workers x batch {args.batch}, same data + step budget "
            "per variant; accuracy is the WORST worker row (averaging "
            "runs must leave every row a good model)"
        ),
        "budgets": out,
        "workers": n,
    }


def run_digits_convergence(args):
    """REAL-data convergence: the reference's accuracy-parity claim
    (reference: README.md:184-193, ImageNet table) at the scale this
    zero-egress environment allows. sklearn's bundled `load_digits`
    (1797 real 8x8 handwritten digit images — UCI/NIST test data, the
    only non-synthetic image set on this machine) trained to a held-out
    TEST accuracy under SyncSGD vs PairAveraging vs SMA on the 8-worker
    mesh. Unlike the synthetic rows, memorization cannot inflate this
    number: the test split is disjoint."""
    import jax
    import numpy as np
    import optax

    from kungfu_tpu.models import MLP
    from kungfu_tpu.optimizers import pair_averaging, sma, sync_sgd
    from kungfu_tpu.parallel import data_mesh

    from sklearn.datasets import load_digits

    d = load_digits()
    rng = np.random.RandomState(0)
    order = rng.permutation(len(d.target))
    xs = (d.images[order] / 16.0).astype(np.float32)
    ys = d.target[order].astype(np.int32)
    n_test = 297
    x_tr, y_tr = xs[:-n_test], ys[:-n_test]          # 1500 train
    x_te, y_te = xs[-n_test:], ys[-n_test:]

    n = jax.device_count()
    mesh = data_mesh(n)
    model = MLP(features=(64,), num_classes=10)
    params = model.init(jax.random.PRNGKey(0), x_tr[:1])["params"]

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()

    def acc_fn(params, batch):
        logits = model.apply({"params": params}, batch["x"])
        return (logits.argmax(-1) == batch["y"]).mean()

    jit_acc = jax.jit(acc_fn)
    accs = {}
    for name, tx, streams in (
        ("sync_sgd", sync_sgd(optax.sgd(args.lr)), False),
        ("pair_averaging", pair_averaging(optax.sgd(args.lr)), True),
        ("sma", sma(optax.sgd(args.lr), alpha=0.1), True),
    ):
        params_s, _ = _train(tx, mesh, args.steps, args.batch, loss_fn,
                             params, x_tr, y_tr,
                             per_worker_streams=streams)
        # averaging runs: EVERY row must independently be a good model
        # (all n rows checked — a collapsed middle row must not hide)
        row_accs = [_accuracy(params_s, jit_acc, mesh, x_te, y_te,
                              row=r) for r in range(n)]
        accs[name] = round(min(row_accs), 4)
    return {
        "config": (
            f"sklearn load_digits (1797 REAL 8x8 handwritten digit "
            f"images; 1500 train / {n_test} held-out test), MLP-64, "
            f"{n} workers x batch {args.batch}, {args.steps} steps, "
            f"sgd lr={args.lr}; accuracy is held-out TEST accuracy of "
            "the WORST worker row"
        ),
        "test_accuracy": accs,
        "pair_vs_sync_gap": round(
            accs["sync_sgd"] - accs["pair_averaging"], 4),
        "real_data": True,
        "workers": n,
    }


def run_bert_sma_gns(args):
    import jax
    import jax.numpy as jnp
    import optax

    from kungfu_tpu.models import BertConfig, BertEncoder
    from kungfu_tpu.optimizers import attach_gradient_noise_scale, sma
    from kungfu_tpu.parallel import (
        build_train_step,
        data_mesh,
        init_worker_state,
        replicate_to_workers,
        shard_batch,
    )

    n = jax.device_count()
    mesh = data_mesh(n)
    platform = jax.devices()[0].platform
    cfg = (BertConfig()  # BERT-base
           if platform != "cpu" else
           BertConfig(num_layers=2, hidden_size=128, num_heads=2,
                      intermediate_size=512, vocab_size=1024,
                      max_position=128))
    seq = 128 if platform != "cpu" else 64
    model = BertEncoder(cfg)
    # varied tokens per worker so cross-worker gradient noise is
    # non-degenerate; MLM-style objective against the encoder's own head
    kt, kl = jax.random.split(jax.random.PRNGKey(2))
    tokens = jax.random.randint(kt, (args.batch * n, seq), 0,
                                cfg.vocab_size, jnp.int32)
    labels = jax.random.randint(kl, (args.batch * n, seq), 0,
                                cfg.vocab_size, jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["x"])  # [B, T, V]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()

    batch = shard_batch({"x": tokens, "y": labels}, mesh)
    variants = {}
    for name, tx in (
        ("sma", sma(optax.sgd(args.lr), alpha=0.1)),
        ("sma+gns", attach_gradient_noise_scale(
            sma(optax.sgd(args.lr), alpha=0.1),
            device_batch_size=args.batch)),
    ):
        params_s = replicate_to_workers(params, mesh)
        opt_s = init_worker_state(tx, params_s, mesh)
        step = build_train_step(loss_fn, tx, mesh)
        for _ in range(2):  # compile + warm
            params_s, opt_s, _ = step(params_s, opt_s, batch)
        jax.block_until_ready(params_s)
        variants[name] = (step, params_s, opt_s)

    # interleave short blocks of each variant and take medians, so shared
    # machine-load drift cancels instead of appearing as monitor overhead
    import numpy as np

    block = 3
    samples = {name: [] for name in variants}
    for _ in range(max(args.iters // block, 4)):
        for name, (step, params_s, opt_s) in variants.items():
            t0 = time.perf_counter()
            for _ in range(block):
                params_s, opt_s, _ = step(params_s, opt_s, batch)
            jax.block_until_ready(params_s)
            samples[name].append(
                (time.perf_counter() - t0) / block * 1e3)
            variants[name] = (step, params_s, opt_s)
    times = {name: float(np.median(v)) for name, v in samples.items()}
    overhead = 100.0 * (times["sma+gns"] - times["sma"]) / times["sma"]
    return {
        "config": (
            f"BERT encoder L{cfg.num_layers}/H{cfg.hidden_size} seq {seq}, "
            f"SMA(alpha=0.1) with vs without GNS monitor, {n} workers x "
            f"batch {args.batch} ({platform}; interleaved-block medians)"
        ),
        "sma_ms_per_step": round(times["sma"], 3),
        "sma_gns_ms_per_step": round(times["sma+gns"], 3),
        "gns_overhead_pct": round(overhead, 1),
        "workers": n,
    }


def run_adaptation(args):
    """Elastic resize latency: drive the real multi-process runtime."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "kungfu_tpu.benchmarks.adaptation",
         "--launch", "--schedule", "8:2,8:4,8:1", "--steps", "24",
         "--np", "2", "--payload-mb", str(args.payload_mb),
         "--step-ms", "500",  # steady-state resizes: warm pool populated
         "--port-range", "28100-28999"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    summary = None
    for line in (out.stdout + out.stderr).splitlines():
        # worker stdout arrives with a colored "[rank]" prefix
        pos = line.find("adaptation np0=")
        if pos >= 0:
            summary = line[pos:]
    if out.returncode != 0 or summary is None:
        raise RuntimeError(
            f"adaptation bench failed rc={out.returncode}:\n"
            f"{out.stdout[-2000:]}\n{out.stderr[-2000:]}")
    # "adaptation np0=2 resizes=2 payload=4MiB mean=X ms max=Y ms"
    fields = dict(
        kv.split("=") for kv in summary.split() if "=" in kv)
    return {
        "config": (
            "elastic run: schedule 2->4->1 workers, "
            f"{args.payload_mb} MiB joiner payload"
            + (" (= fp32 ResNet-50 state)" if args.payload_mb == 98
               else "")
            + ", real kfrun + config server + consensus resize + resync "
            "(loopback; joiners activate from the runner's pre-warmed "
            "interpreter pool — see run/prewarm.py — measured from "
            "steady state at 500 ms/step)"
        ),
        "resizes": int(fields["resizes"]),
        "mean_resize_ms": float(fields["mean"]),
        "max_resize_ms": float(fields["max"]),
    }


def run_straggler(args):
    """The reference's async-scalability claim, measured: one worker
    sleeps 100 ms/step; barrier-free pair averaging must hold cluster
    throughput while S-SGD tracks the straggler (reference:
    README.md:207-209, benchmarks/system/result/async-scalability.svg)."""
    from .straggler import measure

    np_ = 8
    ms = 100
    res = measure(np_=np_, straggler_ms=ms, steps=40, batch=64,
                  port_range="29100-29999")
    return {
        "config": (
            f"{np_} kfrun worker processes, SLP on synthetic MNIST, "
            f"batch 64/worker; one worker sleeps {ms} ms/step; cluster "
            "throughput = sum of per-worker sample rates; retention = "
            "straggler-run / clean-run throughput"
        ),
        "results": res,
        "async_holds": res["pair"]["retention"] > 0.7,
        "sync_tracks_straggler": res["sync"]["retention"] < 0.6,
    }


CONFIG_KEYS = {
    "mnist-slp": ("mnist_slp_syncsgd", run_mnist_slp),
    "pair-convergence": ("resnet50_pair_averaging_convergence_proxy",
                         run_pair_convergence),
    "bert-sma-gns": ("bert_sma_gns_monitor", run_bert_sma_gns),
    "adaptation": ("elastic_adaptation_latency", run_adaptation),
    "digits-convergence": ("real_digits_convergence",
                           run_digits_convergence),
    "straggler": ("async_straggler_scalability", run_straggler),
}


def run_all(args):
    """Run each config in a subprocess on a virtual 8-device CPU mesh and
    merge the results into BASELINE.json."""
    json_path = args.json or os.path.join(REPO, "BASELINE.json")
    with open(json_path) as f:
        baseline = json.load(f)
    published = baseline.setdefault("published", {})
    for sub, (key, _) in CONFIG_KEYS.items():
        env = dict(os.environ)
        if sub != "adaptation":  # adaptation pins its workers itself
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={N_WORKERS}"
            ).strip()
        t0 = time.perf_counter()
        flags = ["--steps", str(args.steps), "--iters", str(args.iters),
                 "--batch", str(args.batch), "--lr", str(args.lr),
                 "--payload-mb", str(args.payload_mb)]
        out = subprocess.run(
            [sys.executable, "-m", "kungfu_tpu.benchmarks.publish", sub,
             *flags],
            env=env, capture_output=True, text=True, timeout=1200,
        )
        if out.returncode != 0:
            print(f"FAIL {sub}:\n{out.stdout[-2000:]}\n"
                  f"{out.stderr[-2000:]}", file=sys.stderr)
            return 1
        line = out.stdout.strip().splitlines()[-1]
        result = json.loads(line)
        result["round"] = args.round
        published[key] = result
        # write after every config so a late failure keeps earlier results
        with open(json_path, "w") as f:
            json.dump(baseline, f, indent=2)
            f.write("\n")
        print(f"ok {sub} ({time.perf_counter() - t0:.0f}s): {line}",
              flush=True)
    print(f"published {len(CONFIG_KEYS)} configs -> {json_path}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("subcommand", nargs="?", choices=sorted(CONFIG_KEYS))
    ap.add_argument("--all", dest="all_", action="store_true",
                    help="run every config and update BASELINE.json")
    ap.add_argument("--json", default="", help="path to BASELINE.json")
    ap.add_argument("--round", type=int, default=2)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--payload-mb", type=int, default=98,
                    help="joiner payload; 98 MiB = fp32 ResNet-50 state")
    ap.add_argument("--check-round", dest="check_round",
                    action="store_true",
                    help="fail unless the current round's "
                         "BENCH_rNN.json exists (CI gate)")
    args = ap.parse_args(argv)
    if args.check_round:
        return check_round()
    if args.all_ or args.subcommand is None:
        return run_all(args)
    _, fn = CONFIG_KEYS[args.subcommand]
    print(json.dumps(fn(args)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
