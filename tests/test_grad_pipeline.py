"""Bucketed, overlapped, compressed gradient pipeline: parity guards.

The guards that the per-step DCN gradient path can never silently
change training semantics (docs/grad_pipeline.md):

- the bucket schedule covers every gradient element exactly once, in
  dtype-homogeneous reverse-backward buckets, derived from shapes only;
- the uncompressed bucketed-overlapped all-reduce equals the monolithic
  lump (`fuse -> peer.all_reduce -> defuse/np`) BIT FOR BIT over real
  multi-peer clusters;
- bf16 / int8 error-feedback variants are bounded-error per step, and
  the residual carry makes the compression error CANCEL over steps
  instead of accumulate (the EF-SGD property), held on a small GPT
  training fixture;
- EF residuals are per-rank state that survives an elastic epoch
  switch untouched, and round-trips byte-exactly through the streaming
  resync / checkpoint machinery that carries them next to optimizer
  state.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from kungfu_tpu import env as kfenv
from kungfu_tpu.grad_pipeline import (DEFAULT_BUCKET_MB,
                                      GradBucketPipeline,
                                      grad_bucket_bytes,
                                      grad_compression)
from kungfu_tpu.ops.collective import bucket_schedule, defuse, fuse
from kungfu_tpu.peer import Peer
from kungfu_tpu.plan import PeerList


def grads_tree(seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return {
        "w0": (scale * rng.standard_normal((300, 130))).astype(np.float32),
        "b0": (scale * rng.standard_normal(1000)).astype(np.float32),
        "w1": (scale * rng.standard_normal((64, 33))).astype(np.float32),
        "tail": (scale * rng.standard_normal(7)).astype(np.float32),
        "zero": np.zeros((0,), np.float32),
    }


class TestBucketSchedule:
    @pytest.mark.parametrize("bucket_bytes", [64, 1000, 4096, 10**9])
    def test_covers_every_element_once(self, bucket_bytes):
        tree = {"a": np.zeros((40, 11), np.float32),
                "b": np.zeros(301, np.float32),
                "i": np.zeros(63, np.int32),
                "h": np.zeros(17, np.float16),
                "z": np.zeros((0,), np.float32)}
        leaves = jax.tree_util.tree_leaves(tree)
        seen = [np.zeros(l.size, bool) for l in leaves]
        for dt, spans in bucket_schedule(tree, bucket_bytes):
            total = 0
            for i, o, n in spans:
                assert n > 0
                assert leaves[i].dtype == dt  # dtype-homogeneous
                assert not seen[i][o:o + n].any()
                seen[i][o:o + n] = True
                total += n
            if len(spans) > 1:  # coalesced buckets respect the bound
                assert total * dt.itemsize <= bucket_bytes
        for i, s in enumerate(seen):
            assert s.all(), f"leaf {i} not fully covered"

    def test_reverse_backward_order(self):
        """The first bucket must hold the LAST leaves — the gradients
        backward produces first."""
        tree = {"a": np.zeros(100, np.float32),
                "b": np.zeros(100, np.float32),
                "c": np.zeros(100, np.float32)}
        sched = bucket_schedule(tree, 400)
        first = [i for _, spans in sched[:1] for i, _, _ in spans]
        assert first[0] == 2  # leaf "c": last in leaf order

    def test_schedule_is_shape_only(self):
        a = grads_tree(seed=0)
        b = grads_tree(seed=9, scale=100.0)
        assert bucket_schedule(a, 777) == bucket_schedule(b, 777)

    def test_rejects_nonpositive(self):
        with pytest.raises(ValueError):
            bucket_schedule(grads_tree(), 0)


class TestEnvResolution:
    def test_bucket_env(self, monkeypatch):
        monkeypatch.delenv("KF_GRAD_BUCKET_MB", raising=False)
        assert grad_bucket_bytes() == int(DEFAULT_BUCKET_MB * 2**20)
        monkeypatch.setenv("KF_GRAD_BUCKET_MB", "2")
        assert grad_bucket_bytes() == 2 * 2**20
        monkeypatch.setenv("KF_GRAD_BUCKET_MB", "0")
        assert grad_bucket_bytes() == 0  # disabled -> lump path
        assert grad_bucket_bytes(0.5) == 2**19  # arg beats env

    def test_bad_values_raise_at_parse_time(self, monkeypatch):
        monkeypatch.setenv("KF_GRAD_BUCKET_MB", "4MB")
        with pytest.raises(ValueError, match="KF_GRAD_BUCKET_MB"):
            grad_bucket_bytes()
        monkeypatch.setenv("KF_GRAD_COMPRESS", "int4")
        with pytest.raises(ValueError, match="KF_GRAD_COMPRESS"):
            grad_compression()
        monkeypatch.setenv("KF_GRAD_COMPRESS", "int8")
        assert grad_compression() == "int8"

    def test_stream_chunk_validation(self, monkeypatch):
        from kungfu_tpu.elastic.streaming import stream_chunk_bytes

        monkeypatch.setenv("KF_STREAM_CHUNK_MB", "fast")
        with pytest.raises(ValueError, match="KF_STREAM_CHUNK_MB"):
            stream_chunk_bytes()

    def test_compression_requires_f32(self):
        p = Peer(kfenv.from_env({}))
        with pytest.raises(ValueError, match="float32"):
            GradBucketPipeline(p, {"i": np.zeros(8, np.int32)},
                               bucket_bytes=64, compression="bf16")


class TestSingleProcess:
    def test_none_is_identity(self):
        p = Peer(kfenv.from_env({}))
        g = grads_tree(seed=1)
        pipe = GradBucketPipeline(p, g, bucket_bytes=2048)
        out = pipe.all_reduce({k: v.copy() for k, v in g.items()})
        for k in g:
            np.testing.assert_array_equal(np.asarray(out[k]), g[k])
        info = pipe.last_step_info
        assert info["buckets"] == pipe.num_buckets > 1
        assert sorted(info["arrival"]) == sorted(
            f"b{k}" for k in range(pipe.num_buckets))
        pipe.close()

    @pytest.mark.parametrize("compression,tol", [("bf16", 1 / 64),
                                                 ("int8", 1 / 16)])
    def test_compression_bounded_error(self, compression, tol):
        p = Peer(kfenv.from_env({}))
        g = grads_tree(seed=2)
        pipe = GradBucketPipeline(p, g, bucket_bytes=4096,
                                  compression=compression)
        out = pipe.all_reduce({k: v.copy() for k, v in g.items()})
        for k in g:
            if g[k].size == 0:
                continue
            err = np.max(np.abs(np.asarray(out[k]) - g[k]))
            bound = tol * max(1.0, np.max(np.abs(g[k])))
            assert err <= bound, (k, err, bound)
        pipe.close()

    @pytest.mark.parametrize("compression", ["bf16", "int8"])
    def test_error_feedback_cancels_over_steps(self, compression):
        """EF-SGD's defining property: for a CONSTANT gradient, the
        cumulative decoded sum tracks the true cumulative gradient to
        within one quantization step — errors cancel via the residual
        instead of accumulating a per-step bias T times."""
        p = Peer(kfenv.from_env({}))
        g = {"w": (np.linspace(-1, 1, 513) ** 3).astype(np.float32)}
        pipe = GradBucketPipeline(p, g, bucket_bytes=4096,
                                  compression=compression)
        T = 50
        cum = np.zeros_like(g["w"])
        for _ in range(T):
            out = pipe.all_reduce({"w": g["w"].copy()})
            cum += np.asarray(out["w"])
        # one-step quantization granularity, NOT T * granularity
        granularity = (np.max(np.abs(g["w"])) / 127.0
                       if compression == "int8" else 1 / 64)
        drift = np.max(np.abs(cum - T * g["w"]))
        assert drift <= 2 * granularity, (drift, granularity)
        pipe.close()

    def test_residual_state_roundtrip(self):
        p = Peer(kfenv.from_env({}))
        g = grads_tree(seed=3)
        a = GradBucketPipeline(p, g, bucket_bytes=2048,
                               compression="int8")
        a.all_reduce({k: v.copy() for k, v in g.items()})
        st = a.state()
        assert any(np.abs(r).sum() > 0 for r in st["residual"])
        b = GradBucketPipeline(p, g, bucket_bytes=2048,
                               compression="int8")
        b.load_state(st)
        for ra, rb in zip(a._residual, b._residual):
            np.testing.assert_array_equal(ra, rb)
        with pytest.raises(ValueError, match="compression"):
            GradBucketPipeline(p, g, bucket_bytes=2048,
                               compression="bf16").load_state(st)
        a.close()
        b.close()


class TestGPTFixtureConvergence:
    """Residual-carry convergence on the small GPT fixture: int8-EF
    training must track the fp32 loss trajectory, not diverge."""

    def _train(self, compression, steps=10):
        from kungfu_tpu.models import GPTConfig, GPTLM, gpt_loss

        cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=1,
                        num_heads=2, intermediate_size=64,
                        max_position=16, dtype=jnp.float32)
        model = GPTLM(cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0,
                                    cfg.vocab_size)
        params = model.init(jax.random.PRNGKey(1), tokens)["params"]
        tx = optax.sgd(0.5)
        opt = tx.init(params)
        p = Peer(kfenv.from_env({}))
        pipe = (GradBucketPipeline(p, params, bucket_bytes=8192,
                                   compression=compression)
                if compression else None)

        @jax.jit
        def step(params):
            def loss_fn(q):
                logits = model.apply({"params": q}, tokens)
                return gpt_loss(logits, tokens)

            return jax.value_and_grad(loss_fn)(params)

        losses = []
        for _ in range(steps):
            loss, grads = step(params)
            losses.append(float(loss))
            if pipe is not None:
                grads = pipe.all_reduce(grads)
            updates, opt = tx.update(grads, opt, params)
            params = optax.apply_updates(params, updates)
        if pipe is not None:
            pipe.close()
        return losses

    def test_int8_ef_tracks_fp32(self):
        fp32 = self._train(None)
        int8 = self._train("int8")
        assert fp32[-1] < fp32[0]  # the fixture actually trains
        assert int8[-1] < int8[0]
        # bounded drift from the exact trajectory, not divergence
        assert abs(int8[-1] - fp32[-1]) < 0.2 * fp32[0], (fp32, int8)


class TestICIBucketedSyncSGD:
    """The ICI mirror: bucketing the pmean must be a pure op-count
    change — bitwise-identical updates to the per-leaf form."""

    def test_bitwise_equals_per_leaf(self):
        from functools import partial

        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map

        from kungfu_tpu.optimizers import sync_sgd, sync_sgd_bucketed

        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        rng = np.random.default_rng(0)
        grads = {
            "w": jnp.asarray(rng.standard_normal((8, 64, 9))
                             .astype(np.float32)),
            "b": jnp.asarray(rng.standard_normal((8, 33))
                             .astype(np.float32)),
        }
        params = jax.tree_util.tree_map(
            lambda g: jnp.zeros(g.shape[1:], g.dtype), grads)

        def run(tx):
            st = tx.init(params)

            def body(g, st):
                up, _ = tx.update(g, st, params)
                return up

            f = shard_map(partial(body, st=st), mesh=mesh,
                          in_specs=(P("data"),), out_specs=P("data"))
            return jax.jit(f)(grads)

        a = run(sync_sgd(optax.sgd(0.1)))
        b = run(sync_sgd_bucketed(optax.sgd(0.1), bucket_bytes=512))
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]))


def make_peer_cluster(n, base_port):
    peers = PeerList.parse(
        ",".join(f"127.0.0.1:{base_port + i}" for i in range(n)))
    return [Peer(kfenv.Config(self_id=peers[i], init_peers=peers,
                              version=0, timeout_ms=20000))
            for i in range(n)]


def run_on_all(peers, fn):
    results = [None] * len(peers)
    errors = []

    def work(i):
        try:
            results[i] = fn(peers[i], i)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    ts = [threading.Thread(target=work, args=(i,))
          for i in range(len(peers))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errors:
        raise errors[0]
    return results


class TestClusterParity:
    """Real in-process multi-peer clusters over actual sockets."""

    @pytest.mark.parametrize("n,bucket_bytes", [(2, 999), (3, 4096)],
                             ids=["2peer-tiny-buckets", "3peer-4k"])
    def test_bucketed_uncompressed_equals_lump_bitwise(self, n,
                                                      bucket_bytes):
        peers = make_peer_cluster(n, 23400 + 10 * n)
        try:
            run_on_all(peers, lambda p, i: p.start())

            def work(p, rank):
                g = grads_tree(seed=rank)
                pipe = GradBucketPipeline(p, g,
                                          bucket_bytes=bucket_bytes)
                out = pipe.all_reduce(
                    {k: v.copy() for k, v in g.items()})
                lump = p.all_reduce(np.asarray(fuse(g)), name="lump")
                lump_tree = defuse(jnp.asarray(lump) / p.size, g)
                pipe.close()
                return out, lump_tree

            for out, lump_tree in run_on_all(peers, work):
                for k in sorted(out):
                    np.testing.assert_array_equal(
                        np.asarray(out[k]), np.asarray(lump_tree[k]),
                        err_msg=k)
        finally:
            for p in peers:
                p.close()

    @pytest.mark.parametrize("compression", ["bf16", "int8"])
    def test_compressed_identical_across_ranks_and_bounded(
            self, compression):
        peers = make_peer_cluster(2, 23440 if compression == "bf16"
                                  else 23450)
        try:
            run_on_all(peers, lambda p, i: p.start())

            def work(p, rank):
                g = grads_tree(seed=rank)
                pipe = GradBucketPipeline(p, g, bucket_bytes=2048,
                                          compression=compression)
                out = pipe.all_reduce(
                    {k: v.copy() for k, v in g.items()})
                pipe.close()
                return out

            outs = run_on_all(peers, work)
            exact = jax.tree_util.tree_map(
                lambda a, b: (a + b) / 2.0,
                grads_tree(seed=0), grads_tree(seed=1))
            for k in sorted(exact):
                # every rank decodes the SAME wire bytes
                np.testing.assert_array_equal(
                    np.asarray(outs[0][k]), np.asarray(outs[1][k]))
                if exact[k].size == 0:
                    continue
                err = np.max(np.abs(np.asarray(outs[0][k]) - exact[k]))
                assert err <= 0.1 * max(1.0, np.max(np.abs(exact[k])))
        finally:
            for p in peers:
                p.close()

    def test_residuals_survive_epoch_switch(self):
        """An elastic resize must not touch the per-rank residuals:
        the pipe object outlives the epoch switch, and the shrunken
        cluster keeps compensating with the residuals accumulated
        before the switch."""
        peers = make_peer_cluster(3, 23470)
        try:
            run_on_all(peers, lambda p, i: p.start())
            g_by_rank = [grads_tree(seed=r) for r in range(3)]
            pipes = {}

            def step1(p, rank):
                pipe = GradBucketPipeline(p, g_by_rank[rank],
                                          bucket_bytes=2048,
                                          compression="int8")
                pipes[rank] = pipe
                pipe.all_reduce({k: v.copy()
                                 for k, v in g_by_rank[rank].items()})
                return [r.copy() for r in pipe._residual]

            pre = run_on_all(peers, step1)

            # epoch switch: shrink 3 -> 2 (rank 2 leaves), the native
            # membership swap every planned resize and recovery uses
            two = PeerList.parse("127.0.0.1:23470,127.0.0.1:23471")

            def switch(p, rank):
                if rank < 2:
                    p._native.update(str(two), 1)
                else:
                    p._native.update(f"127.0.0.1:{23470 + rank}", 1)

            run_on_all(peers, switch)

            for rank in (0, 1):  # untouched by the switch
                for a, b in zip(pre[rank], pipes[rank]._residual):
                    np.testing.assert_array_equal(a, b)

            def step2(p, rank):
                if rank >= 2:
                    return None
                return pipes[rank].all_reduce(
                    {k: v.copy() for k, v in g_by_rank[rank].items()})

            outs = run_on_all(peers, step2)
            # survivors still agree bit-for-bit in the new epoch
            for k in sorted(outs[0]):
                np.testing.assert_array_equal(
                    np.asarray(outs[0][k]), np.asarray(outs[1][k]))
        finally:
            for r, pipe in pipes.items():
                pipe.close()
            for p in peers:
                p.close()

    def test_residual_state_rides_streaming_resync(self):
        """pipe.state() is a plain numpy pytree: the streaming resync
        (the machinery that carries params+opt_state to joiners and
        restored workers) must move it byte-exactly."""
        from kungfu_tpu.elastic.streaming import stream_broadcast
        from kungfu_tpu.ops.collective import pack_bytes

        peers = make_peer_cluster(2, 23490)
        try:
            run_on_all(peers, lambda p, i: p.start())
            g = grads_tree(seed=5)

            def work(p, rank):
                pipe = GradBucketPipeline(p, g, bucket_bytes=2048,
                                          compression="bf16")
                # every rank accumulates its own (different) residual
                pipe.all_reduce({k: (v + rank).astype(v.dtype)
                                 for k, v in g.items()})
                st = pipe.state()
                out, _ = stream_broadcast(p, st, root=0,
                                          chunk_bytes=1024,
                                          name="kf::test::ef")
                pipe.close()
                return st, out

            results = run_on_all(peers, work)
            root_state = results[0][0]
            for _, received in results:
                np.testing.assert_array_equal(
                    pack_bytes(received), pack_bytes(root_state))
        finally:
            for p in peers:
                p.close()


@pytest.mark.slow
@pytest.mark.chaos
def test_pipeline_survivor_recovery_with_chaos():
    """The full acceptance scenario with the pipeline on the wire: a
    chaos schedule SIGKILLs a worker mid-step while gradients flow
    through the bucketed int8-EF pipeline; survivors shrink, restore,
    and finish with loss continuity — the per-rank residuals ride the
    epoch switch inside the living pipe objects."""
    from kungfu_tpu.elastic.harness import run_survivor_recovery

    logs = run_survivor_recovery(
        crash_rank=1, crash_step=5, total_steps=12, start_np=3,
        port_range="28200-28999", timeout=300,
        extra_env={"KF_GRAD_BUCKET_MB": "0.25",
                   "KF_GRAD_COMPRESS": "int8"})
    assert "KF_RECOVERY_DONE rank=0 size=2" in logs, logs[-3000:]
    assert "size=3 step=12" in logs, logs[-3000:]
