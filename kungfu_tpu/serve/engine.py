"""The continuous-batching decode engine: Orca's iteration-level loop.

One `DecodeEngine` owns the model params, the paged KV pool and the
jitted decode step, and exposes exactly two scheduling verbs:

- ``admit(seq_id, prompt, max_new)`` — prefill a new request into a
  free batch slot (one batched causal forward through the MODEL's own
  prefill path fills the sequence's pool blocks) and emit its first
  token;
- ``step()`` — ONE decode iteration for every live slot, whatever
  mix of requests currently occupies them. New requests join the
  running batch between iterations (iteration-level scheduling,
  PAPERS.md Orca), finished requests retire and their blocks return
  to the pool immediately — no batch drains, no padding to the
  longest request.

When the pool runs dry mid-decode the engine PREEMPTS the youngest
sequence (fewest generated tokens — the cheapest redo) instead of
corrupting a live block: `step()` reports it and the caller returns
the request to the ledger, where its generated-so-far tokens are
already recorded and a later admission resumes it by re-prefilling
prompt + generated (docs/serving.md, "KV block lifecycle").

`build_lm` is the ONE model/params(+tp-sharding) setup both this
engine and `benchmarks/lm.py --decode` call, so the published
`gpt_decode_tokens_per_sec` row and the serving tier cannot drift
apart. Sampling is greedy (argmax) throughout — serving determinism
is what the parity tests pin.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from .. import trace
from .kv_cache import KVPoolExhausted, PagedKVPool, pool_capacity_blocks

SIZES = {
    # name -> (hidden, layers, heads, intermediate); the canonical
    # GPT size table (benchmarks/lm.py re-exports it)
    "tiny": (128, 2, 8, 256),
    "small": (768, 12, 12, 3072),   # GPT-2 124M
    "medium": (1024, 24, 16, 4096),  # GPT-2 350M
}


def build_lm(size: str, max_position: int, tp: int = 1, dtype=None,
             seed: int = 0, vocab_size: int = 50257):
    """Model + params (+ tp sharding) for decoding: the shared setup
    of `benchmarks.lm.measure_decode_rate` and `DecodeEngine`.

    Returns ``(model, params, mesh)`` — `mesh` is None at tp=1,
    otherwise the (1, tp) ("data", "model") mesh with the params
    Megatron-sharded per the `serve` rules table
    (`parallel.rules.gpt_serve_rules` — registered, so the
    shard-rule-coverage/mesh lint passes gate serving's plan like
    every other family's). Raises SystemExit with the same messages
    the benchmark always printed for impossible tp splits.
    """
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..models import GPTConfig, GPTLM

    if size not in SIZES:
        raise SystemExit(f"unknown size {size!r} (known: {sorted(SIZES)})")
    hidden, layers, heads, inter = SIZES[size]
    n = jax.device_count()
    if tp > n:
        raise SystemExit(f"--tp {tp} exceeds device count {n}")
    if heads % tp:
        raise SystemExit(
            f"--tp {tp} must divide num_heads {heads} of size={size}")
    cfg = GPTConfig(vocab_size=vocab_size, hidden_size=hidden,
                    num_layers=layers, num_heads=heads,
                    intermediate_size=inter,
                    max_position=max_position,
                    dtype=dtype if dtype is not None else jnp.bfloat16)
    model = GPTLM(cfg)
    probe = jnp.zeros((1, 1), jnp.int32)
    params = model.init(jax.random.PRNGKey(seed), probe)["params"]
    mesh = None
    if tp > 1:
        from jax.sharding import Mesh

        from ..parallel.rules import gpt_serve_rules, shard_params

        # decode's mesh is (1, tp) over the first tp devices — the
        # standard TPU serving layout (GSPMD propagates the Megatron
        # head sharding into the KV caches and inserts the ICI
        # collectives)
        mesh = Mesh(np.array(jax.devices()[:tp]).reshape(1, tp),
                    ("data", "model"))
        params = shard_params(jax.device_get(params), mesh,
                              gpt_serve_rules())
    return model, params, mesh


@dataclass
class _Seq:
    """One live sequence's engine-side state."""

    slot: int
    prompt_len: int
    max_new: int
    cache_len: int                    # tokens currently in pool blocks
    last_token: int                   # next decode input
    generated: List[int] = field(default_factory=list)
    # chunked-prefill state: `prompt` holds the full token list while
    # the sequence is still prefilling (None once decode-ready);
    # `prefill_pos` is the next position to prefill (starts past any
    # prefix-shared tokens)
    prompt: Optional[List[int]] = None
    prefill_pos: int = 0
    order: int = 0                    # admission order (FIFO prefill)
    # deferred prefills hold NO pool blocks until their first chunk
    # runs (`_prefill_step` admits lazily) — by then every
    # earlier-ordered prefill has committed, so a burst of identical
    # prompts admitted in one iteration still shares the first
    # arrival's blocks instead of each prefilling privately
    pending: bool = False


class DecodeEngine:
    """Iteration-level continuous batching over the paged KV pool."""

    def __init__(self, model, params, max_batch: int,
                 block_tokens: int, max_len: int,
                 num_blocks: int = 0, eos: Optional[int] = None,
                 kernel: str = "functional", prefill_chunk: int = 0,
                 share_prefix: bool = False):
        from . import paged

        cfg = model.config
        if max_len > cfg.max_position:
            raise ValueError(
                f"max_len {max_len} exceeds the model's max_position "
                f"{cfg.max_position}")
        if max_batch <= 0:
            raise ValueError(f"max_batch must be positive, got "
                             f"{max_batch}")
        self.model = model
        self.cfg = cfg
        self.params = params
        self.max_batch = int(max_batch)
        self.max_len = int(max_len)
        self.eos = eos
        self.max_blocks = paged.max_blocks_for(max_len, block_tokens)
        num_blocks = num_blocks or pool_capacity_blocks(
            max_batch, max_len, block_tokens)
        self.pool = PagedKVPool(num_blocks, block_tokens)
        self.pool_k, self.pool_v = paged.init_pool_tensors(
            cfg, num_blocks, block_tokens)
        # KF_SERVE_KERNEL resolution happens ONCE, here: "auto" means
        # the plan's pick on TPU and the functional path on CPU;
        # "kernel" forces the plan's pick (interpret mode off-TPU)
        self.kernel = self._resolve_kernel(kernel, block_tokens)
        self._decode = paged.make_decode_fn(cfg, kernel=self.kernel)
        self._prefill = paged.make_prefill_chunk_fn(cfg)
        self.prefill_chunk = int(prefill_chunk)
        self.share_prefix = bool(share_prefix)
        self._slots: List[Optional[object]] = [None] * self.max_batch
        self._seqs: Dict[object, _Seq] = {}
        self._admitted = 0
        self.steps = 0
        # wall-clock accounting for the per-np breakdown benchmark
        self.decode_s = 0.0
        self.prefill_s = 0.0
        self.prefill_chunks = 0

    def _resolve_kernel(self, knob: str, block_tokens: int) -> str:
        """Map the KF_SERVE_KERNEL knob to the decode_step kernel
        argument, consulting `paged_plan` so an over-budget shape is
        settled at construction (not at Mosaic compile time). A plan
        that fits neither scheme raises when a kernel was asked for
        ("kernel") and warns under "auto": the functional path is
        never taken in silence."""
        if knob == "functional":
            return "functional"
        import jax

        if knob == "auto" and jax.default_backend() != "tpu":
            return "functional"
        if knob not in ("auto", "kernel"):
            return knob  # explicit "resident"/"stream" (tests)
        from ..ops import paged_attn

        plan = paged_attn.paged_plan(
            self.max_blocks, block_tokens, self.cfg.num_heads,
            self.cfg.hidden_size // self.cfg.num_heads,
            dtype=self.cfg.dtype)
        if plan["scheme"] == "functional":
            why = (f"no paged-attention scheme fits VMEM at max_len "
                   f"{self.max_len}, block_tokens {block_tokens} "
                   f"(resident {plan['resident_bytes'] >> 20} MiB, "
                   f"stream {plan['stream_bytes'] >> 20} MiB)")
            if knob == "kernel":
                raise ValueError(f"KF_SERVE_KERNEL=kernel: {why}")
            warnings.warn(f"{why}; decoding with the functional gather")
        return plan["scheme"]

    def warm(self) -> None:
        """Compile every signature the serving loop can hit, BEFORE
        the first request: the decode step at its one fixed
        (max_batch, max_blocks) shape, the chunk-prefill buckets, and
        the whole-prefill length buckets. A replica that jits on its
        first real request stalls it for seconds — and on a shared
        host every OTHER replica's requests contend with that compile
        (the inverse-np scaling BENCH_r15 published was mostly
        laggard replicas compiling inside the measured window). All
        warm traffic lands in the scratch block (length/true_len 0 —
        masked out of every real row forever); wall time is NOT added
        to the prefill/decode accounting."""
        import numpy as np

        import jax
        import jax.numpy as jnp

        from . import paged

        bt = self.pool.block_tokens
        tables = self.pool.batch_tables([], self.max_blocks,
                                        pad_rows=self.max_batch)
        zeros = np.zeros(self.max_batch, np.int32)
        logits, self.pool_k, self.pool_v = self._decode(
            self.params, self.pool_k, self.pool_v, tables, zeros,
            zeros)
        jax.block_until_ready(logits)
        # chunk buckets: the configured chunk size plus the one-block
        # bucket (remainders and the recomputed tail of a fully
        # shared prompt both land there)
        chunk_buckets = {bt}
        if self.prefill_chunk:
            chunk_buckets.add(-(-self.prefill_chunk // bt) * bt)
        row = np.zeros(self.max_blocks, np.int32)
        for c in sorted(chunk_buckets):
            logits, self.pool_k, self.pool_v = self._prefill(
                self.params, self.pool_k, self.pool_v,
                jnp.asarray(row), 0,
                jnp.asarray(np.zeros(c, np.int32)), 0)
            jax.block_until_ready(logits)
        # whole-prefill buckets: with chunking on, prompts longer
        # than the chunk defer to the incremental path, so only the
        # buckets up to the chunk size can reach paged.prefill
        whole = (min(-(-self.prefill_chunk // bt), self.max_blocks)
                 if self.prefill_chunk else self.max_blocks)
        for nb in range(1, whole + 1):
            arr = jnp.zeros((1, nb * bt), jnp.int32)
            logits, ks, vs = paged.prefill(self.model, self.params,
                                           arr)
            self.pool_k, self.pool_v = paged.write_prefill(
                self.pool_k, self.pool_v, [0] * nb, ks[:, 0],
                vs[:, 0], bt)
            jax.block_until_ready(logits)

    # -- admission ----------------------------------------------------------

    @property
    def active(self) -> int:
        return len(self._seqs)

    def free_slots(self) -> int:
        return self.max_batch - len(self._seqs)

    def can_admit(self, prompt_len: int) -> bool:
        return (self.free_slots() > 0
                and prompt_len < self.max_len
                and self.pool.can_admit(prompt_len))

    def admit(self, seq_id, prompt: List[int],
              max_new: int) -> Tuple[Optional[int], bool]:
        """Admit `prompt` into a free slot. When neither prefix
        sharing nor chunking applies, the whole prompt prefills here
        and ``(first_token, done)`` returns as before. Otherwise the
        prefill is DEFERRED: the sequence enters the prefilling state,
        ``(None, False)`` returns immediately, and `step()` advances
        the prefill one chunk per iteration (interleaved with decode)
        until the first token is emitted through its `emitted` map.
        Raises KVPoolExhausted / ValueError when it cannot admit — the
        caller's admission queue keeps the request."""
        import time

        import numpy as np

        import jax.numpy as jnp

        from . import paged

        if seq_id in self._seqs:
            raise ValueError(f"sequence {seq_id!r} already live")
        if self.free_slots() <= 0:
            raise KVPoolExhausted("no free batch slot")
        t = len(prompt)
        if not 0 < t < self.max_len:
            raise ValueError(
                f"prompt length {t} outside (0, {self.max_len})")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        bt = self.pool.block_tokens
        # how much of the prompt COULD be skipped: committed donors in
        # the prefix index now, plus full-block prefixes of sequences
        # still prefilling — those run before this one (FIFO order)
        # and commit on completion, so deferring lets this sequence
        # share blocks that do not exist yet
        committed = inflight = 0
        if self.share_prefix:
            committed = self.pool.match_prefix(prompt)[1]
            for q in self._seqs.values():
                if q.prompt is None:
                    continue
                lim = min(q.prompt_len, t)
                m = 0
                while ((m + 1) * bt <= lim
                       and q.prompt[m * bt:(m + 1) * bt]
                       == prompt[m * bt:(m + 1) * bt]):
                    m += 1
                inflight = max(inflight, m * bt)
        potential = min(max(committed, inflight), t - 1)
        slot = self._slots.index(None)
        self._admitted += 1
        if potential > 0 or (self.prefill_chunk
                             and t - potential > self.prefill_chunk):
            # incremental path: step() owns the prefill from here
            seq = _Seq(slot=slot, prompt_len=t, max_new=int(max_new),
                       cache_len=t, last_token=int(prompt[-1]),
                       prompt=list(prompt), prefill_pos=0,
                       order=self._admitted, pending=True)
            if committed > 0 and committed >= inflight:
                # donors are ALREADY committed: map them now, so the
                # blocks-in-use collapse is visible at admit time and
                # pool pressure accounts the sharer immediately (a
                # failure here propagates with nothing registered)
                self.pool.admit(seq_id, t, prompt=prompt)
                seq.pending = False
                seq.prefill_pos = min(self.pool.shared_tokens(seq_id),
                                      t - 1)
            # otherwise the pool admission is LAZY (`pending`) so the
            # prefix match runs after the in-flight donors commit
            self._slots[slot] = seq_id
            self._seqs[seq_id] = seq
            return None, False
        table = self.pool.admit(
            seq_id, t, prompt=prompt if self.share_prefix else None)
        # pad the prompt to a block-sized bucket: one prefill compile
        # per bucket instead of per distinct length (causal masking
        # keeps every real position independent of the padding)
        padded = -(-t // bt) * bt
        arr = np.zeros((1, padded), np.int32)
        arr[0, :t] = prompt
        t0 = time.perf_counter()
        with trace.span("request.prefill", cat="serve", seq=str(seq_id),
                        prompt_len=t):
            logits, ks, vs = paged.prefill(self.model, self.params,
                                           jnp.asarray(arr))
            # the full padded prefix ships to the pool in ONE donated
            # scatter (padded tail masked by length, never visible)
            self.pool_k, self.pool_v = paged.write_prefill(
                self.pool_k, self.pool_v, table,
                ks[:, 0], vs[:, 0], bt)
            tok0 = int(jnp.argmax(logits[0, t - 1]))
        self.prefill_s += time.perf_counter() - t0
        if self.share_prefix:
            self.pool.commit_prefix(seq_id, prompt)
        seq = _Seq(slot=slot, prompt_len=t, max_new=int(max_new),
                   cache_len=t, last_token=tok0, generated=[tok0],
                   order=self._admitted)
        done = self._finished(seq)
        if done:
            self.pool.release(seq_id)
        else:
            self._slots[slot] = seq_id
            self._seqs[seq_id] = seq
        return tok0, done

    def _finished(self, seq: _Seq) -> bool:
        if len(seq.generated) >= seq.max_new:
            return True
        if self.eos is not None and seq.generated[-1] == self.eos:
            return True
        # hard cap: the pool reservation ends at max_len positions
        return seq.cache_len + 1 >= self.max_len

    # -- the iteration ------------------------------------------------------

    def _reserve(self, seq_id, attempt) -> Tuple[
            List[object], List[Tuple[int, int]]]:
        """Run `attempt` (an allocator call on behalf of `seq_id`),
        preempting the youngest OTHER live sequence (fewest generated
        tokens) on exhaustion until it succeeds; preempting `seq_id`
        itself is the last resort. Returns ``(preempted ids,
        (src, dst) pool-tensor copies the allocator requested)``."""
        preempted: List[object] = []
        while True:
            try:
                return preempted, attempt()
            except KVPoolExhausted:
                victims = sorted(
                    self._seqs,
                    key=lambda s: (s == seq_id,
                                   len(self._seqs[s].generated)))
                victim = victims[0]
                self._drop(victim)
                preempted.append(victim)
                if victim == seq_id:
                    return preempted, []

    def _make_room(self, seq_id) -> Tuple[List[object],
                                          List[Tuple[int, int]]]:
        """Extend `seq_id`'s table by one position (copy-on-write of
        a shared last block included)."""
        return self._reserve(
            seq_id,
            lambda: self.pool.grow(
                seq_id, self._seqs[seq_id].cache_len + 1))

    def _drop(self, seq_id) -> None:
        seq = self._seqs.pop(seq_id)
        self._slots[seq.slot] = None
        if not seq.pending:  # pending seqs hold no pool blocks yet
            self.pool.release(seq_id)

    def _prefill_step(self, seq_id, emitted: Dict[object,
                                                  Tuple[int, bool]],
                      preempted: List[object]) -> None:
        """Advance `seq_id`'s deferred prefill by one chunk. On the
        final chunk the first token is computed from the last real
        position's logits and reported through `emitted` exactly like
        a decode step's token."""
        import time

        import numpy as np

        import jax
        import jax.numpy as jnp

        from . import paged

        seq = self._seqs[seq_id]
        t = seq.prompt_len
        bt = self.pool.block_tokens
        if seq.pending:
            # lazy pool admission: every earlier-ordered prefill has
            # completed (and, with sharing, committed), so the prefix
            # match sees donors that did not exist at admit() time
            pre, _ = self._reserve(
                seq_id,
                lambda: self.pool.admit(
                    seq_id, t,
                    prompt=seq.prompt if self.share_prefix else None))
            preempted.extend(pre)
            if seq_id not in self._seqs:  # could not fit even alone
                return
            seq.pending = False
            # never share the FULL prompt: position t-1 must be
            # recomputed so the first token's logits exist (the
            # one-token chunk that recomputes it goes through
            # copy-on-write, so a shared donor block is never
            # overwritten)
            seq.prefill_pos = min(self.pool.shared_tokens(seq_id),
                                  t - 1)
        start = seq.prefill_pos
        real = t - start
        if self.prefill_chunk:
            real = min(real, self.prefill_chunk)
        # writes into shared/committed blocks (the divergence point,
        # or the recomputed last position of a fully-shared prompt)
        # swap in private copies first
        pre, copies = self._reserve(
            seq_id,
            lambda: self.pool.cow_for_write(seq_id, start, start + real))
        preempted.extend(pre)
        if seq_id not in self._seqs:  # lost its own blocks
            return
        if copies:
            self.pool_k, self.pool_v = paged.copy_blocks(
                self.pool_k, self.pool_v, copies)
        # chunks pad to a block multiple: one compile per chunk bucket
        # (pad positions scatter to the scratch block, masked off)
        c = -(-real // bt) * bt
        toks = np.zeros(c, np.int32)
        toks[:real] = seq.prompt[start:start + real]
        table = np.full(self.max_blocks, 0, np.int32)
        row = self.pool.table(seq_id)
        table[:len(row)] = row
        t0 = time.perf_counter()
        with trace.span("request.prefill_chunk", cat="serve",
                        seq=str(seq_id), start=start, tokens=real):
            logits, self.pool_k, self.pool_v = self._prefill(
                self.params, self.pool_k, self.pool_v,
                jnp.asarray(table), start, jnp.asarray(toks), t)
            logits = jax.block_until_ready(logits)
        self.prefill_s += time.perf_counter() - t0
        self.prefill_chunks += 1
        seq.prefill_pos = start + real
        if seq.prefill_pos < t:
            return
        tok0 = int(np.asarray(logits)[real - 1].argmax())
        if self.share_prefix:
            self.pool.commit_prefix(seq_id, seq.prompt)
        seq.prompt = None
        seq.generated = [tok0]
        seq.last_token = tok0
        seq.cache_len = t
        done = self._finished(seq)
        if done:
            self._drop(seq_id)
        emitted[seq_id] = (tok0, done)

    def step(self) -> Tuple[Dict[object, Tuple[int, bool]],
                            List[object]]:
        """One iteration over every live slot: at most ONE prefilling
        sequence advances by one chunk (admission order), then every
        decode-ready slot decodes — prefill is interleaved with
        decode instead of stalling it.

        Returns ``(emitted, preempted)``: `emitted` maps seq_id ->
        (token, done) for every sequence that emitted a token this
        iteration (a decode step's token, or a completed prefill's
        first token); `preempted` lists sequences evicted by pool
        pressure (their blocks are freed; re-admit to resume). No
        live slots -> both empty.
        """
        import time

        import numpy as np

        from . import paged

        if not self._seqs:
            return {}, []
        emitted: Dict[object, Tuple[int, bool]] = {}
        preempted: List[object] = []
        prefilling = sorted(
            (s for s, q in self._seqs.items() if q.prompt is not None),
            key=lambda s: self._seqs[s].order)
        if prefilling:
            self._prefill_step(prefilling[0], emitted, preempted)
        # capacity first: every decoding row's incoming token needs a
        # slot in its block table BEFORE the batched scatter runs —
        # and any copy-on-write the growth requests must land BEFORE
        # the scatter too (one batched copy, gathers read pre-copy
        # state so overlapping src/dst rows stay consistent)
        copies: List[Tuple[int, int]] = []
        for seq_id in [s for s in self._slots if s is not None]:
            if (seq_id in self._seqs and seq_id not in emitted
                    and self._seqs[seq_id].prompt is None):
                pre, cps = self._make_room(seq_id)
                preempted.extend(pre)
                copies.extend(cps)
        if copies:
            self.pool_k, self.pool_v = paged.copy_blocks(
                self.pool_k, self.pool_v, copies)
        live = [s for s in self._slots
                if s is not None and s in self._seqs
                and s not in emitted
                and self._seqs[s].prompt is None]
        self.steps += 1
        if not live:
            return emitted, preempted
        order = {s: self._seqs[s].slot for s in live}
        tokens = np.zeros(self.max_batch, np.int32)
        lengths = np.zeros(self.max_batch, np.int32)
        tables = self.pool.batch_tables([], self.max_blocks,
                                        pad_rows=self.max_batch)
        for s, slot in order.items():
            seq = self._seqs[s]
            tokens[slot] = seq.last_token
            lengths[slot] = seq.cache_len
            row = self.pool.table(s)
            tables[slot, :len(row)] = row
        t0 = time.perf_counter()
        with trace.span("serve.decode_step", cat="serve",
                        batch=len(live)):
            logits, self.pool_k, self.pool_v = self._decode(
                self.params, self.pool_k, self.pool_v, tables,
                lengths, tokens)
            toks = np.asarray(logits.argmax(axis=-1))
        self.decode_s += time.perf_counter() - t0
        for s, slot in order.items():
            seq = self._seqs[s]
            tok = int(toks[slot])
            seq.generated.append(tok)
            seq.last_token = tok
            seq.cache_len += 1
            done = self._finished(seq)
            if done:
                self._drop(s)
            emitted[s] = (tok, done)
        return emitted, preempted

    def drain(self, seq_id) -> None:
        """Release a live sequence without finishing it (eviction /
        shutdown: its blocks return to the pool; the ledger keeps the
        generated-so-far record)."""
        if seq_id in self._seqs:
            self._drop(seq_id)

    def live(self) -> List[object]:
        return [s for s in self._slots if s is not None]

    def prefilling(self) -> List[object]:
        """Live sequences still in the chunked-prefill state (they
        emit nothing until their last chunk — the worker heartbeats
        their leases)."""
        return [s for s, q in self._seqs.items()
                if q.prompt is not None]

    def is_live(self, seq_id) -> bool:
        return seq_id in self._seqs

    def generated(self, seq_id) -> List[int]:
        return list(self._seqs[seq_id].generated)
