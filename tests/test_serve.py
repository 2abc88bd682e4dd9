"""kfserve: paged KV allocator, continuous-batching engine, ledger,
front-end routes and serving env knobs (docs/serving.md).

Fast sections run in tier-1; the end-to-end elastic/chaos cases live
in tests/test_serve_elastic.py behind the slow/chaos markers.
"""

import json

import numpy as np
import pytest

from kungfu_tpu.serve.kv_cache import (SCRATCH_BLOCK, KVPoolExhausted,
                                       PagedKVPool,
                                       pool_capacity_blocks)
from kungfu_tpu.serve.ledger import (DONE, FAILED, QUEUED, RUNNING,
                                     AdmissionFull, RequestLedger)


# -- the allocator (pure host-side, no JAX) -----------------------------------


class TestPagedAllocator:
    def test_admit_extend_release_roundtrip(self):
        p = PagedKVPool(num_blocks=6, block_tokens=4)
        t = p.admit("a", 5)                  # 5 tokens -> 2 blocks
        assert len(t) == 2 and p.blocks_in_use == 2
        p.grow("a", 8)                     # still 2 blocks
        assert len(p.table("a")) == 2
        p.grow("a", 9)                     # crosses into block 3
        assert len(p.table("a")) == 3
        assert p.check_invariants() == []
        p.release("a")
        assert p.blocks_in_use == 0 and p.free_blocks == 6
        assert p.check_invariants() == []

    def test_reuse_is_lifo(self):
        p = PagedKVPool(num_blocks=4, block_tokens=4)
        ta = p.admit("a", 4)
        p.release("a")
        tb = p.admit("b", 4)
        # the most recently freed block comes back first, so stale-
        # bytes bugs surface on the next admission, not never
        assert tb == ta

    def test_exhaustion_is_loud_and_allocates_nothing(self):
        p = PagedKVPool(num_blocks=2, block_tokens=4)
        p.admit("a", 8)
        with pytest.raises(KVPoolExhausted):
            p.admit("b", 1)
        with pytest.raises(KVPoolExhausted):
            p.grow("a", 9)
        assert p.length("a") == 8           # unchanged by the failure
        assert p.check_invariants() == []

    def test_scratch_block_never_circulates(self):
        p = PagedKVPool(num_blocks=3, block_tokens=2)
        owned = p.admit("a", 6)
        assert SCRATCH_BLOCK not in owned
        tables = p.batch_tables(["a"], max_blocks=4, pad_rows=1)
        assert tables.shape == (2, 4)
        # the pad row and the unused tail both point at scratch
        assert (tables[1] == SCRATCH_BLOCK).all()
        assert tables[0, 3] == SCRATCH_BLOCK
        assert list(tables[0, :3]) == owned

    def test_double_admit_rejected(self):
        p = PagedKVPool(num_blocks=4, block_tokens=4)
        p.admit("a", 1)
        with pytest.raises(ValueError):
            p.admit("a", 1)

    def test_batch_lengths(self):
        p = PagedKVPool(num_blocks=4, block_tokens=4)
        p.admit("a", 3)
        p.admit("b", 7)
        lens = p.batch_lengths(["b", "a"], pad_rows=2)
        assert list(lens) == [7, 3, 0, 0]

    def test_capacity_helper(self):
        assert pool_capacity_blocks(2, 32, 16) == 4
        assert pool_capacity_blocks(2, 33, 16) == 6


# -- copy-on-write prefix sharing (pure host-side, no JAX) --------------------


class TestCowPrefixSharing:
    def test_identical_prompt_maps_same_blocks(self):
        p = PagedKVPool(num_blocks=8, block_tokens=4)
        prompt = list(range(12))             # 3 full blocks
        ta = p.admit("a", 12, prompt=prompt)
        assert p.shared_tokens("a") == 0     # empty index: no donors
        p.commit_prefix("a", prompt)
        tb = p.admit("b", 12, prompt=prompt)
        assert tb == ta                      # the same physical blocks
        assert p.shared_tokens("b") == 12
        assert p.blocks_in_use == 3          # shared blocks count once
        assert p.check_invariants() == []

    def test_partial_last_block_shares_when_donor_extends(self):
        p = PagedKVPool(num_blocks=8, block_tokens=4)
        donor = list(range(12))
        ta = p.admit("a", 12, prompt=donor)
        p.commit_prefix("a", donor)
        # 10-token prompt = donor's first 10 tokens: 2 full-block hits
        # plus the partial third block (the donor's tail past length
        # 10 is masked, hence invisible to "b")
        tb = p.admit("b", 10, prompt=donor[:10])
        assert tb == ta
        assert p.shared_tokens("b") == 10
        assert p.blocks_in_use == 3
        assert p.check_invariants() == []

    def test_grow_cow_diverges_shared_write_target(self):
        p = PagedKVPool(num_blocks=8, block_tokens=4)
        donor = list(range(12))
        ta = p.admit("a", 12, prompt=donor)
        p.commit_prefix("a", donor)
        p.admit("b", 10, prompt=donor[:10])  # shares all 3 blocks
        # position 10 lands in the shared third block: grow must swap
        # in a private copy and report the pool-tensor copy to run
        copies = p.grow("b", 11)
        tb = p.table("b")
        assert copies == [(ta[2], tb[2])]
        assert tb[:2] == ta[:2] and tb[2] != ta[2]
        assert p.blocks_in_use == 4
        # the donor's block is untouched and still committed
        assert p.table("a") == ta
        assert p.check_invariants() == []

    def test_cow_for_write_respects_committed_even_at_refcount_one(self):
        p = PagedKVPool(num_blocks=8, block_tokens=4)
        prompt = list(range(8))
        ta = p.admit("a", 8, prompt=prompt)
        p.commit_prefix("a", prompt)
        # sole owner, but committed: a later admission may map the
        # block at any moment, so an in-place write is forbidden
        copies = p.cow_for_write("a", 7, 8)
        assert len(copies) == 1 and copies[0][0] == ta[1]
        assert p.table("a")[1] != ta[1]
        assert p.check_invariants() == []

    def test_release_order_conserves_blocks_and_evicts_index(self):
        p = PagedKVPool(num_blocks=8, block_tokens=4)
        prompt = list(range(8))
        ta = p.admit("a", 8, prompt=prompt)
        p.commit_prefix("a", prompt)
        p.admit("b", 8, prompt=prompt)
        # donor retires FIRST: the sharer's references keep the
        # blocks (and their index entries) alive
        p.release("a")
        assert p.blocks_in_use == 2
        assert p.check_invariants() == []
        tc = p.admit("c", 8, prompt=prompt)  # still a donor hit
        assert tc == ta and p.shared_tokens("c") == 8
        p.release("b")
        p.release("c")
        # last reference gone: blocks freed AND evicted from the
        # index — the next identical prompt must NOT match stale ids
        assert p.blocks_in_use == 0 and p.free_blocks == 8
        p.admit("d", 8, prompt=prompt)
        assert p.shared_tokens("d") == 0
        assert p.check_invariants() == []

    def test_churn_interleavings_keep_invariants(self):
        p = PagedKVPool(num_blocks=16, block_tokens=4)
        donor = list(range(12))
        p.admit("d0", 12, prompt=donor)
        p.commit_prefix("d0", donor)
        live = ["d0"]
        for i in range(6):
            s = f"s{i}"
            p.admit(s, 12, prompt=donor)
            live.append(s)
            if i % 2:                        # diverge half of them
                p.grow(s, 13)
            if i == 2:
                p.release(live.pop(0))       # donor leaves mid-churn
            if i == 4:
                p.release(live.pop(1))
            assert p.check_invariants() == [], (i, p.check_invariants())
        for s in live:
            p.release(s)
        assert p.blocks_in_use == 0 and p.free_blocks == 16
        assert p.check_invariants() == []

    def test_invariant_gate_catches_double_free(self):
        p = PagedKVPool(num_blocks=4, block_tokens=4)
        t = p.admit("a", 4)
        p.release("a")
        p._free.append(t[0])                 # corrupt: freed twice
        bad = p.check_invariants()
        assert any("double free" in m for m in bad), bad

    def test_invariant_gate_catches_freed_block_with_owner(self):
        p = PagedKVPool(num_blocks=4, block_tokens=4)
        t = p.admit("a", 4)
        p._free.append(t[0])                 # corrupt: owned AND free
        bad = p.check_invariants()
        assert any("freed block still has references" in m
                   for m in bad), bad


# -- the request ledger -------------------------------------------------------


class TestRequestLedger:
    def test_lifecycle_and_latency(self):
        led = RequestLedger()
        rid = led.submit([1, 2], 4)
        assert led.result(rid)["state"] == QUEUED
        (r,) = led.lease(4, "w0")
        assert r["prompt"] == [1, 2] and r["pos"] == 0
        assert led.append_tokens(rid, 0, [10, 11], False, "w0") == "ok"
        assert led.append_tokens(rid, 2, [12], True, "w0") == "ok"
        out = led.result(rid)
        assert out["state"] == DONE and out["tokens"] == [10, 11, 12]
        assert out["latency_ms"] >= 0
        assert led.check_invariants() == []

    def test_bounded_admission(self):
        led = RequestLedger(max_queue=2)
        led.submit([1], 1)
        led.submit([1], 1)
        with pytest.raises(AdmissionFull):
            led.submit([1], 1)

    def test_malformed_submit(self):
        led = RequestLedger()
        with pytest.raises(ValueError):
            led.submit([], 1)
        with pytest.raises(ValueError):
            led.submit([1], 0)

    def test_append_gap_raises(self):
        led = RequestLedger()
        rid = led.submit([1], 4)
        led.lease(1, "w0")
        with pytest.raises(ValueError):
            led.append_tokens(rid, 2, [5], False, "w0")

    def test_overlap_redelivery_idempotent_conflict_recorded(self):
        led = RequestLedger()
        rid = led.submit([1], 4)
        led.lease(1, "w0")
        led.append_tokens(rid, 0, [7, 8], False, "w0")
        # agreeing overlap: idempotent, nothing recorded
        assert led.append_tokens(rid, 1, [8, 9], False, "w0") == "ok"
        assert led.result(rid)["tokens"] == [7, 8, 9]
        assert led.check_invariants() == []
        # disagreeing overlap: recorded violation (greedy decode is
        # deterministic — disagreement is a real bug)
        led.append_tokens(rid, 0, [7, 99], False, "w0")
        assert any("overlap mismatch" in v
                   for v in led.check_invariants())

    def test_stale_worker_fenced_after_reclaim(self):
        led = RequestLedger(lease_ms=1.0)
        rid = led.submit([1], 4)
        led.lease(1, "w0")
        import time

        time.sleep(0.01)                    # expire w0's lease
        (r,) = led.lease(1, "w1")           # reclaim + re-lease
        assert r["id"] == rid and r["leases"] == 2
        assert led.append_tokens(rid, 0, [5], False, "w0") == "stale"
        assert led.append_tokens(rid, 0, [5], True, "w1") == "ok"
        assert led.check_invariants() == []

    def test_resume_carries_generated_tokens(self):
        led = RequestLedger(lease_ms=1.0)
        rid = led.submit([1, 2], 8)
        led.lease(1, "w0")
        led.append_tokens(rid, 0, [4, 5], False, "w0")
        import time

        time.sleep(0.01)
        (r,) = led.lease(1, "w1")
        # the resumed lease hands back prompt AND generated-so-far:
        # re-prefill prompt+tokens, continue at pos
        assert r["id"] == rid and r["tokens"] == [4, 5] \
            and r["pos"] == 2

    def test_poisonous_request_fails_after_max_leases(self):
        led = RequestLedger(lease_ms=1.0, max_leases=2)
        rid = led.submit([1], 4)
        import time

        for _ in range(2):
            led.lease(1, "w")
            time.sleep(0.01)
        led.stats()                          # reclaim sweep
        assert led.result(rid)["state"] == FAILED
        assert led.check_invariants() == []

    def test_release_requeues_with_tokens(self):
        led = RequestLedger()
        rid = led.submit([1], 8)
        led.lease(1, "w0")
        led.append_tokens(rid, 0, [3], False, "w0")
        led.release(rid, "w0")
        assert led.result(rid)["state"] == QUEUED
        (r,) = led.lease(1, "w1")
        assert r["tokens"] == [3]
        assert led.check_invariants() == []

    def test_max_new_overflow_is_a_violation_and_clamped(self):
        led = RequestLedger()
        rid = led.submit([1], 2)
        led.lease(1, "w0")
        led.append_tokens(rid, 0, [1, 2, 3], True, "w0")
        assert led.result(rid)["tokens"] == [1, 2]
        assert any("exceed max_new" in v
                   for v in led.check_invariants())

    def test_unadmittable_request_fails_at_lease_time_not_livelock(self):
        """A request every worker must release (e.g. a prompt no
        engine's max_len can hold) bounces lease->release; the poison
        bound applies at LEASE time, so it becomes FAILED after
        max_leases instead of starving the drain forever."""
        led = RequestLedger(max_leases=3)
        rid = led.submit([1] * 100, 4)
        for _ in range(3):
            (r,) = led.lease(1, "w")
            assert r["id"] == rid
            led.release(rid, "w")
        assert led.lease(1, "w") == []       # 4th attempt: refused
        assert led.result(rid)["state"] == FAILED
        assert led.check_invariants() == []

    def test_stats_percentiles_are_windowed_not_all_history(self):
        """The SLO signal recovers when latencies do: stats p50/p99
        come from the recent-completion window, never the run's whole
        history (one cold-boot spike must not pin a permanent grow)."""
        led = RequestLedger()
        rid = led.submit([1], 2)
        led.lease(1, "w")
        led.append_tokens(rid, 0, [5], True, "w")
        assert led.stats()["p99_ms"] >= 0 and led.stats()["done"] == 1
        led._recent.clear()                  # the window rolls off...
        st = led.stats()
        assert st["done"] == 1               # ...counts keep history
        assert st["p99_ms"] == 0.0           # ...percentiles do not

    def test_stats_counts(self):
        led = RequestLedger()
        a, b = led.submit([1], 2), led.submit([1], 2)
        led.lease(1, "w0")
        st = led.stats()
        assert st["submitted"] == 2 and st["queue_depth"] == 1 \
            and st["running"] == 1
        led.append_tokens(a, 0, [9], True, "w0")
        assert led.stats()["done"] == 1
        assert b in [r["id"] for r in led.results()]


# -- serving env knobs (the KF_NO_UNIX_SOCKET lesson) -------------------------


class TestServeKnobs:
    def test_env_int_rejects_garbage_and_fractions(self):
        from kungfu_tpu.env import env_int

        assert env_int("X", 3, {}) == 3
        assert env_int("X", 3, {"X": "7"}) == 7
        with pytest.raises(ValueError):
            env_int("X", 3, {"X": "2.5"})
        with pytest.raises(ValueError):
            env_int("X", 3, {"X": "many"})
        with pytest.raises(ValueError):
            env_int("X", 3, {"X": "0"}, minimum=1)

    @pytest.mark.parametrize("var,bad", [
        ("KF_SERVE_PORT", "http"),
        ("KF_SERVE_MAX_BATCH", "0"),
        ("KF_KV_BLOCK_TOKENS", "16.0"),
        ("KF_SLO_P99_MS", "fast"),
        ("KF_SERVE_QUEUE", "-1"),
        ("KF_SERVE_LEASE_MS", "50"),
    ])
    def test_garbage_raises_at_bootstrap(self, var, bad):
        from kungfu_tpu.env import from_env

        with pytest.raises(ValueError):
            from_env({var: bad})

    def test_valid_knobs_parse(self):
        from kungfu_tpu.env import CONFIG_VARS, from_env

        cfg = from_env({"KF_SERVE_PORT": "9200",
                        "KF_SERVE_MAX_BATCH": "4",
                        "KF_KV_BLOCK_TOKENS": "8",
                        "KF_SLO_P99_MS": "250"})
        assert cfg.single_process
        # kfrun forwards what CONFIG_VARS lists — the knob must be in
        # the launcher protocol or it silently never reaches a worker
        for var in ("KF_SERVE_PORT", "KF_SERVE_MAX_BATCH",
                    "KF_KV_BLOCK_TOKENS", "KF_SLO_P99_MS",
                    "KF_SERVE_QUEUE", "KF_SERVE_LEASE_MS",
                    "KF_SERVE_MODEL", "KF_SERVE_MAX_LEN",
                    "KF_SERVE_BLOCKS", "KF_SERVE_EXPECT",
                    "KF_SERVE_MAX_ITERS"):
            assert var in CONFIG_VARS, var


# -- the paged decode path (JAX; one tiny f32 fixture for the module) ---------


@pytest.fixture(scope="module")
def lm():
    import jax.numpy as jnp

    from kungfu_tpu.serve.engine import build_lm

    model, params, _ = build_lm("tiny", max_position=64,
                                dtype=jnp.float32)
    return model, params


def _run_engine(eng, prompts, max_new, max_iters=64):
    """Admit everything, decode to completion; {seq: tokens}."""
    got = {}
    for s, p in prompts.items():
        tok, done = eng.admit(s, p, max_new)
        got[s] = [tok]
    for _ in range(max_iters):
        emitted, preempted = eng.step()
        assert not preempted
        for s, (tok, _d) in emitted.items():
            got[s].append(tok)
        if not eng.live():
            break
    return got


class TestPagedEngine:
    def test_token_parity_with_gpt_generate(self, lm):
        import jax.numpy as jnp

        from kungfu_tpu.models import gpt_generate
        from kungfu_tpu.serve.engine import DecodeEngine

        model, params = lm
        prompts = {"a": [5, 7, 11, 13], "b": [2, 3],
                   "c": [40, 41, 42, 43, 44, 45, 46]}
        ref = {}
        for k, p in prompts.items():
            out = gpt_generate(model, params,
                               jnp.asarray(np.array(p)[None]), 5)
            ref[k] = [int(t) for t in np.asarray(out)[0, len(p):]]
        eng = DecodeEngine(model, params, max_batch=4,
                           block_tokens=4, max_len=32)
        got = _run_engine(eng, prompts, 5)
        assert got == ref
        assert eng.pool.check_invariants() == []
        assert eng.pool.blocks_in_use == 0   # all retired

    def test_continuous_admission_mid_batch(self, lm):
        """A request admitted while others are mid-decode gets the
        same tokens as it would alone — iteration-level scheduling
        must be invisible to the sequence."""
        from kungfu_tpu.serve.engine import DecodeEngine

        model, params = lm
        alone = _run_engine(
            DecodeEngine(model, params, max_batch=2, block_tokens=4,
                         max_len=32), {"x": [9, 8, 7]}, 6)["x"]
        eng = DecodeEngine(model, params, max_batch=3,
                           block_tokens=4, max_len=32)
        got = {"a": [eng.admit("a", [5, 7, 11, 13], 8)[0]]}
        for _ in range(3):                   # a is mid-decode...
            em, _ = eng.step()
            for s, (t, _d) in em.items():
                got.setdefault(s, []).append(t)
        got["x"] = [eng.admit("x", [9, 8, 7], 6)[0]]  # ...x joins
        for _ in range(20):
            em, _ = eng.step()
            for s, (t, _d) in em.items():
                got.setdefault(s, []).append(t)
            if not eng.live():
                break
        assert got["x"] == alone

    def test_batch_composition_bitwise_parity(self, lm):
        """The same sequence's decode logits are BITWISE identical
        whatever else shares the batch — rows are independent, so
        batch composition is purely a scheduling choice."""
        from kungfu_tpu.serve import paged
        from kungfu_tpu.serve.engine import DecodeEngine

        model, params = lm

        def logits_for(seqs, probe):
            eng = DecodeEngine(model, params, max_batch=4,
                               block_tokens=4, max_len=32)
            for s, p in seqs.items():
                eng.admit(s, p, 8)
            slot = eng._seqs[probe].slot
            order = eng.live()
            tables = eng.pool.batch_tables(
                order, eng.max_blocks,
                pad_rows=eng.max_batch - len(order))
            lengths = eng.pool.batch_lengths(
                order, pad_rows=eng.max_batch - len(order))
            tokens = np.zeros(eng.max_batch, np.int32)
            for i, s in enumerate(order):
                tokens[i] = eng._seqs[s].last_token
            out, _, _ = paged.decode_step(
                model.config, params, eng.pool_k, eng.pool_v,
                tables, lengths, tokens)
            return np.asarray(out)[order.index(probe)]

        pa, pb = [5, 7, 11, 13], [2, 3]
        solo = logits_for({"a": pa}, "a")
        shared = logits_for({"b": pb, "a": pa}, "a")
        assert np.array_equal(solo, shared)  # bitwise, not allclose

    def test_no_cross_request_leakage_after_eviction(self, lm):
        """A sequence admitted onto REUSED blocks (LIFO free list =
        the previous request's bytes still in them) produces bitwise
        the same tokens as on a fresh pool: masking, not zeroing, is
        the isolation mechanism, and it must be airtight."""
        from kungfu_tpu.serve.engine import DecodeEngine

        model, params = lm
        fresh = _run_engine(
            DecodeEngine(model, params, max_batch=2, block_tokens=4,
                         max_len=32), {"b": [2, 3]}, 8)["b"]
        eng = DecodeEngine(model, params, max_batch=2,
                           block_tokens=4, max_len=32,
                           num_blocks=4)                 # tight pool
        _run_engine(eng, {"a": [5, 7, 11, 13, 17, 19]}, 8)
        assert eng.pool.blocks_in_use == 0
        reused = _run_engine(eng, {"b": [2, 3]}, 8)["b"]
        assert reused == fresh

    def test_pool_pressure_preempts_youngest_and_resume_matches(self, lm):
        """When the pool runs dry mid-decode the youngest sequence is
        preempted (blocks freed, reported), and re-admitting it with
        prompt+generated resumes the exact token stream."""
        from kungfu_tpu.serve.engine import DecodeEngine

        model, params = lm
        ref = _run_engine(
            DecodeEngine(model, params, max_batch=2, block_tokens=2,
                         max_len=32), {"y": [2, 3]}, 10)["y"]
        # 6 blocks of 2 tokens: a alone grows to 4 blocks, then y
        # joins (strictly younger) and the next boundary crossing
        # finds the pool dry — y, fewest generated tokens, is the
        # cheapest redo and must be the victim
        eng = DecodeEngine(model, params, max_batch=2,
                           block_tokens=2, max_len=32, num_blocks=6)
        eng.admit("a", [5, 7, 11, 13], 12)
        for _ in range(3):
            eng.step()
        tok_y, _ = eng.admit("y", [2, 3], 10)
        got_y = [tok_y]
        preempted_seen = False
        for _ in range(40):
            emitted, preempted = eng.step()
            for s, (t, _d) in emitted.items():
                if s == "y":
                    got_y.append(t)
            if preempted:
                assert preempted == ["y"], preempted
                preempted_seen = True
                break
            if not eng.live():
                break
        assert preempted_seen, "tight pool never preempted"
        assert eng.pool.check_invariants() == []
        # resume: prompt + generated-so-far, remaining budget
        eng2 = DecodeEngine(model, params, max_batch=2,
                            block_tokens=2, max_len=32)
        tok, done = eng2.admit("y", [2, 3] + got_y, 10 - len(got_y))
        resumed = got_y + [tok]
        while not done and eng2.live():
            em, _ = eng2.step()
            for s, (t, done) in em.items():
                resumed.append(t)
        assert resumed == ref

    def test_admit_validation(self, lm):
        from kungfu_tpu.serve.engine import DecodeEngine

        model, params = lm
        eng = DecodeEngine(model, params, max_batch=1,
                           block_tokens=4, max_len=16)
        with pytest.raises(ValueError):
            eng.admit("a", [], 4)
        with pytest.raises(ValueError):
            eng.admit("a", [1] * 16, 4)      # prompt >= max_len
        with pytest.raises(ValueError):
            eng.admit("a", [1], 0)
        eng.admit("a", [1, 2], 4)
        assert eng.is_live("a") and not eng.is_live("b")
        with pytest.raises(KVPoolExhausted):
            eng.admit("b", [1], 4)           # no free slot
        with pytest.raises(ValueError):
            eng.admit("a", [1], 4)           # already live

    def test_kv_blocks_gauge_tracks_pool(self, lm):
        from kungfu_tpu.serve.engine import DecodeEngine
        from kungfu_tpu.trace import metrics

        model, params = lm
        eng = DecodeEngine(model, params, max_batch=2,
                           block_tokens=4, max_len=32)
        eng.admit("a", [1, 2, 3, 4, 5], 4)
        assert metrics.REGISTRY.read("kf_kv_blocks_in_use") == \
            eng.pool.blocks_in_use > 0

    def test_kernel_bitwise_parity_straddling_block_boundaries(self, lm):
        """The Pallas paged-decode kernel against the functional
        gather path, on the SAME pool state, at cache lengths bt-1,
        bt, bt+1 and 2*bt (every block-boundary straddle): the
        resident scheme is bitwise identical; the online-softmax
        stream scheme is allclose with equal argmax."""
        from kungfu_tpu.serve import paged
        from kungfu_tpu.serve.engine import DecodeEngine

        model, params = lm
        eng = DecodeEngine(model, params, max_batch=4,
                           block_tokens=4, max_len=32)
        prompts = {"a": [5, 7, 11], "b": [2, 3, 4, 6],
                   "c": [9, 8, 7, 6, 5], "d": [13] * 8}
        for s, p in prompts.items():
            eng.admit(s, p, 8)
        order = eng.live()
        tables = eng.pool.batch_tables(order, eng.max_blocks)
        lengths = eng.pool.batch_lengths(order)
        tokens = np.array([eng._seqs[s].last_token for s in order],
                          np.int32)
        outs = {}
        for kern in ("functional", "resident", "stream"):
            o, _, _ = paged.decode_step(
                model.config, params, eng.pool_k, eng.pool_v,
                tables, lengths, tokens, kernel=kern)
            outs[kern] = np.asarray(o)
        assert np.array_equal(outs["functional"], outs["resident"])
        np.testing.assert_allclose(outs["stream"], outs["functional"],
                                   rtol=1e-5, atol=1e-5)
        assert (outs["stream"].argmax(-1).tolist()
                == outs["functional"].argmax(-1).tolist())

    def test_plan_counts_padded_tiles(self):
        """The v5e compiler refuses the resident scheme at max_len
        2048 for GPT-2 heads (scoped VMEM): a [12, 64] bf16 head tile
        occupies [16, 128] there. The plan must not offer it."""
        import jax.numpy as jnp

        from kungfu_tpu.ops.paged_attn import paged_plan

        at = {n: paged_plan(n // 16, 16, 12, 64, dtype=jnp.bfloat16)
              for n in (1024, 2048)}
        assert at[1024]["scheme"] == "resident"
        assert at[2048]["scheme"] == "stream"
        assert at[2048]["resident_bytes"] > 16 * 2**20
        # an 8-byte item pads like a 4-byte one (no division by zero)
        assert (paged_plan(4, 16, 12, 64, dtype=jnp.float64)
                ["stream_bytes"]) > at[1024]["stream_bytes"]

    @pytest.mark.parametrize("knob", ["kernel", "auto"])
    def test_no_scheme_fits_is_never_silent(self, lm, monkeypatch, knob):
        """An over-budget plan raises when a kernel was asked for and
        warns under "auto" on a TPU — never a silent functional path."""
        import jax

        from kungfu_tpu.ops import paged_attn
        from kungfu_tpu.serve.engine import DecodeEngine

        model, params = lm
        monkeypatch.setattr(paged_attn, "_VMEM_BUDGET", 0)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        kw = dict(max_batch=2, block_tokens=4, max_len=32, kernel=knob)
        if knob == "kernel":
            with pytest.raises(ValueError, match="KF_SERVE_KERNEL=kernel"):
                DecodeEngine(model, params, **kw)
        else:
            with pytest.warns(UserWarning, match="functional gather"):
                assert DecodeEngine(model, params,
                                    **kw).kernel == "functional"

    def test_kernel_token_parity_end_to_end(self, lm):
        """Whole generations through the engine with the kernel
        schemes match the functional path token for token (growth
        crosses several block boundaries along the way)."""
        from kungfu_tpu.serve.engine import DecodeEngine

        model, params = lm
        prompts = {"a": [5, 7, 11], "b": [2, 3, 4, 6],
                   "c": [9, 8, 7, 6, 5]}
        ref = _run_engine(
            DecodeEngine(model, params, max_batch=3, block_tokens=4,
                         max_len=32), prompts, 6)
        for kern in ("resident", "stream"):
            eng = DecodeEngine(model, params, max_batch=3,
                               block_tokens=4, max_len=32,
                               kernel=kern)
            assert _run_engine(eng, prompts, 6) == ref, kern
            assert eng.pool.check_invariants() == []

    def test_chunked_prefill_token_parity(self, lm):
        """prefill_chunk splits long prompts across iterations
        (interleaved with decode); tokens must match whole-prefill
        admission exactly — and short prompts keep the immediate
        path, so the two admission styles coexist in one batch."""
        from kungfu_tpu.serve.engine import DecodeEngine

        model, params = lm
        prompts = {"a": [5, 7, 11, 13, 17, 19, 23, 29, 31],
                   "b": [2, 3], "c": [40, 41, 42, 43, 44, 45, 46]}
        ref = _run_engine(
            DecodeEngine(model, params, max_batch=4, block_tokens=4,
                         max_len=32), prompts, 5)
        eng = DecodeEngine(model, params, max_batch=4, block_tokens=4,
                           max_len=32, prefill_chunk=4)
        got = {s: [] for s in prompts}
        deferred = 0
        for s, p in prompts.items():
            tok, _done = eng.admit(s, p, 5)
            if tok is None:
                deferred += 1
            else:
                got[s].append(tok)
        assert deferred == 2                 # a and c exceed the chunk
        for _ in range(64):
            emitted, preempted = eng.step()
            assert not preempted
            for s, (tok, _d) in emitted.items():
                got[s].append(tok)
            if not eng.live():
                break
        assert got == ref
        assert eng.prefill_chunks >= 2
        assert eng.pool.check_invariants() == []
        assert eng.pool.blocks_in_use == 0

    def test_prefix_sharing_parity_and_block_collapse(self, lm):
        """Identical prompts admitted with share_prefix map the
        committed donor blocks instead of re-prefilling: blocks-in-use
        collapses, the divergent last-position write goes through
        copy-on-write, and every token still matches the unshared
        engine bitwise."""
        from kungfu_tpu.serve.engine import DecodeEngine

        model, params = lm
        common = [3, 1, 4, 1, 5, 9, 2, 6]    # exactly 2 full blocks
        prompts = {f"s{i}": list(common) for i in range(3)}
        ref = _run_engine(
            DecodeEngine(model, params, max_batch=3, block_tokens=4,
                         max_len=32), prompts, 5)
        eng = DecodeEngine(model, params, max_batch=3, block_tokens=4,
                           max_len=32, share_prefix=True)
        got = {}
        tok, _ = eng.admit("s0", prompts["s0"], 5)   # whole prefill
        got["s0"] = [tok]
        for s in ("s1", "s2"):
            tok, _ = eng.admit(s, prompts[s], 5)
            assert tok is None               # deferred: shared prefix
            assert eng.pool.shared_tokens(s) == len(common)
            got[s] = []
        # both sharers map the donor's 2 blocks: 2 owned blocks total,
        # not 6 — the collapse the prefix-heavy benchmark cell shows
        assert eng.pool.blocks_in_use == 2
        for _ in range(64):
            emitted, preempted = eng.step()
            assert not preempted
            for s, (tok, _d) in emitted.items():
                got[s].append(tok)
            if not eng.live():
                break
        assert got == ref
        assert eng.pool.check_invariants() == []
        assert eng.pool.blocks_in_use == 0   # index evicted on free


# -- the /serve front-end on a live config server -----------------------------


@pytest.fixture()
def serve_server():
    from kungfu_tpu.elastic.config_server import ConfigServer

    s = ConfigServer(port=0).start()
    yield s
    s.stop()


class TestServeFrontend:
    def test_submit_lease_append_result_roundtrip(self, serve_server):
        from kungfu_tpu.serve import frontend as fe

        url = serve_server.get_url
        rid = fe.submit(url, [1, 2, 3], 5)
        assert fe.stats(url)["queue_depth"] == 1
        (r,) = fe.lease(url, 4, "w0")
        assert r["id"] == rid and r["prompt"] == [1, 2, 3]
        assert fe.append(url, rid, 0, [10], False, "w0") == "ok"
        assert fe.append(url, rid, 1, [11], True, "w0") == "ok"
        out = fe.result(url, rid)
        assert out["state"] == "done" and out["tokens"] == [10, 11]
        assert fe.invariants(url) == []

    def test_admission_backpressure_is_429(self, serve_server,
                                           monkeypatch):
        import urllib.error
        import urllib.request

        from kungfu_tpu.serve.frontend import serve_url

        serve_server.serve_ledger.max_queue = 1
        body = json.dumps({"prompt": [1], "max_new_tokens": 1})
        target = serve_url(serve_server.get_url, "/submit")

        def post_raw():
            req = urllib.request.Request(
                target, data=body.encode(), method="POST",
                headers={"Content-Type": "application/json"})
            return urllib.request.urlopen(req, timeout=5).read()

        post_raw()
        with pytest.raises(urllib.error.HTTPError) as ei:
            post_raw()
        assert ei.value.code == 429          # transient: retriable

    def test_malformed_submit_is_400(self, serve_server):
        import urllib.error
        import urllib.request

        from kungfu_tpu.serve.frontend import serve_url

        req = urllib.request.Request(
            serve_url(serve_server.get_url, "/submit"),
            data=b'{"prompt": [], "max_new_tokens": 1}',
            method="POST",
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=5)
        assert ei.value.code == 400          # permanent: not retried

    def test_unknown_id_is_404(self, serve_server):
        import urllib.error

        from kungfu_tpu.peer import fetch_url
        from kungfu_tpu.retrying import NO_RETRY
        from kungfu_tpu.serve.frontend import serve_url

        with pytest.raises(urllib.error.HTTPError) as ei:
            fetch_url(serve_url(serve_server.get_url,
                                "/result?id=999"), retry=NO_RETRY)
        assert ei.value.code == 404

    def test_serve_routes_bypass_chaos_http_faults(self, serve_server):
        """Like /trace: a refuse_http fault schedule must not consume
        its request budget on (or refuse) serving traffic."""
        from kungfu_tpu import chaos
        from kungfu_tpu.serve import frontend as fe

        chaos.load({"faults": [{"type": "refuse_http", "count": 100,
                                "status": 503}]})
        try:
            rid = fe.submit(serve_server.get_url, [1], 1,
                            retry=None)
            assert fe.result(serve_server.get_url, rid)["state"] \
                == "queued"
        finally:
            chaos.load(None)
