"""GenerationServer — continuous-batching autoregressive serving over
the AOT executable stack.

The chat-style scenario: long-lived stateful requests share one
fixed-shape decode batch. A background decode thread runs ONE
pre-compiled executable per token for the WHOLE batch; new requests are
admitted into free slots of the in-flight batch between steps
(prefill + cache graft, one dispatch) and finished ones retire without
ever changing a shape — the executable set is closed over
(slot bucket, cache-length rung, prompt bucket) exactly like
`ParallelInference`'s bucket ladder is closed over batch shapes.

Steady-state contract (linted by scripts/check_fastpath.py and
regression-tested): past `warmup()`, the decode loop performs ZERO jit
traces and ZERO XLA compiles — superstep, admit, retire, and grow all
resolve from the in-memory executable tier — and the ONLY host sync is
the per-SUPERSTEP sampled-token-block fetch (`_fetch_tokens`); the
whole decode state (KV caches / recurrent carries, positions, active
mask, per-slot sampling knobs, rng keys) lives on device and is
DONATED through every dispatch, so steady state is one fixed-shape
dispatch per k tokens. The token block is a non-donated output whose
host copy starts asynchronously (`_start_fetch`) right after dispatch:
block n's journal append and stream delivery run while block n+1
computes, so the fetch overlaps compute instead of gating it.

Executables (per `FunctionStore`, two-tier: in-memory + on-disk
serialized — a restarted replica warms from disk):

- ``("superstep", C, k)`` — decode k tokens for all S slots at cache
  rung C as ONE `lax.scan` dispatch: each iteration embeds → writes the
  K/V row (or advances carries) → single-query attention → logits →
  fused per-slot sampling (greedy / temperature / top-k, all TRACED
  per-slot values: mixed sampling configs share one executable).
  Per-slot EOS/budget halt masks freeze finished slots mid-block
  (frozen iterations are computed-but-masked, emitted as -1, never
  delivered), so the block's semantics exactly equal k sequential
  steps while dispatches and host fetches per token drop by k.
  Admission / retirement / growth happen between supersteps, so EOS
  retirement may lag up to ~2k steps behind the terminal token (one
  block of halt lag + one block of async-fetch pipeline depth).
- ``("verify", C, d)`` — exact greedy drafting (optional, off by
  default): the host proposes up to d draft tokens (prompt-lookup
  n-gram over the request's own journal; during crash-replay, the
  journaled prefix itself), and one dispatch runs the q-block
  [current, draft...] through a multi-query decode attention
  (`flash_attention_decode_mq`), accepting exactly the prefix of
  drafts that match the model's own greedy argmax. Delivered streams
  are token-identical to vanilla greedy; non-greedy slots in the same
  batch advance exactly one sampled token per round (one rng split),
  keeping the sampled-stream bit-identity contract untouched.
- ``("admit", C, P)`` — prefill one prompt at prompt bucket P, graft
  its cache/carry rows into a slot, arm the slot's sampling config and
  rng key, sample the first token.
- ``("retire",)`` — clear a slot's position/active/token columns
  (cache rows need no clearing: the cache-validity mask hides them).
- ``("grow_to_<C'>", C)`` — pad the KV cache from rung C to C' when an
  admission needs more room than the current rung (never shrinks
  mid-flight; recurrent carry state is rung-independent).
- ``("advance_key_n",)`` — advance one rng key past n consumed
  sampling splits in a single dispatch (the crash-replay
  continuation-key derivation).
- ``("page_copy",)`` — copy one physical KV page pool→pool (paged
  servers only: the copy-on-write primitive behind prefix sharing).

Paged KV mode (decoder built with ``page_size``/``pool_pages``): the
cache is a fixed pool of physical pages instead of S contiguous rung
rows, and every executable additionally threads a host-built page
index — superstep/verify take the ``(S, rung // page_size)`` int32 page
table, admit takes the per-logical-page write-redirect row. The pool is
RUNG-INDEPENDENT, so ``grow`` degenerates to a host-side rung relabel
(no dispatch, no per-rung-pair executables); the rung only sets the
page-table width the dispatch reads through. Between dispatches the
host `PageAllocator` (generation/paging.py) maps prompt pages with
hash-of-prefix dedup (identical prefixes share read-only pages),
allocates write coverage for the next block, and copy-on-writes shared
pages before their first divergent write — each CoW is one pre-compiled
``("page_copy",)`` dispatch. Page bookkeeping is pure host numpy on the
existing dispatch boundaries: zero extra syncs, zero traces (linted).
Pool exhaustion raises the typed `PagePoolExhaustedError` — refused
pre-dispatch at admission (fails only that request), and mid-stream it
carries the RESOURCE_EXHAUSTED token so the OOM classifier routes it
through the degradation ladder, whose paged form gains an
evict-cold-pages level between shed-queued and shrink-rung.

Survivability (the serving twin of the PR 5/7 training guardian):

- **Crash-replay.** Every admitted request carries a host-side journal
  (`_SlotJournal`: admission id → rng key derivation; the prompt,
  sampling config, and delivered tokens already live on the request —
  the per-step journal append IS the existing sampled-token fetch, so
  it costs nothing extra). A decode-loop failure no longer fails the
  in-flight batch: the state is rebuilt from the warm executable set
  and every surviving request is RE-ADMITTED — by re-prefilling
  prompt+generated-prefix with the admission key advanced past the
  consumed splits when the prefix fits a prompt bucket, else by
  re-generating the prefix from the original admission state with
  delivery suppressed. Either way the continuation stream is
  bit-identical to an uninterrupted run, because per-slot keys make
  every stream a pure function of its admission state (chaos-tested).
- **Supervised restart.** A failed recovery no longer latches the
  server dead: a supervisor retries the rebuild+replay from the warm
  `FunctionStore` (zero live compiles) under a bounded `RetryPolicy`;
  only an exhausted budget — or sustained zero forward progress —
  latches the typed `ServerDeadError`, which is pushed to every open
  stream immediately so no consumer waits out its timeout.
- **Memory-pressure degradation ladder.** An OOM-classified failure
  (or a `monitoring/memory.py` high-water reading) degrades stepwise
  instead of killing serving: (1) refuse further cache growth, (2)
  also shed queued admissions, (3) shrink to a smaller pre-compiled
  rung — in-flight requests replay into it, requests that no longer
  fit fail with the typed `MemoryPressureError`. Pressure decays after
  a clean stretch of steps. Events count `dl4j.gen.degradations`;
  replays and restarts count `dl4j.gen.{replays,restarts}`.

Admission rides the same bounded-enqueue/shed semantics as
`ParallelInference` (`InferenceOverloadedError`, enqueue timeout).
Chaos fault sites: `generation.step`, `generation.admit`, `cache.grow`,
and (paged servers) `cache.page` (resilience/faults.py) fire inside the
loop at zero disabled-path cost.
"""
from __future__ import annotations

import collections
import itertools
import queue
import threading
import time
import weakref

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu import monitoring as _mon
from deeplearning4j_tpu.monitoring import events as _events
from deeplearning4j_tpu.monitoring import requests as _req
from deeplearning4j_tpu.generation.paging import PageAllocator
from deeplearning4j_tpu.generation.sampling import (GREEDY, method_id,
                                                    sample_step,
                                                    split_keys)
from deeplearning4j_tpu.resilience import faults as _faults
from deeplearning4j_tpu.resilience.errors import (InferenceOverloadedError,
                                                  InferenceTimeoutError,
                                                  MemoryPressureError,
                                                  PagePoolExhaustedError,
                                                  ReplayDivergedError,
                                                  ServerDeadError)
from deeplearning4j_tpu.resilience.policy import RetryPolicy
from deeplearning4j_tpu.util.crash_reporting import CrashReportingUtil

__all__ = ["GenerationRequest", "GenerationServer", "status"]

_SERVERS = weakref.WeakSet()

#: decode-state tuple layout (everything donated through each step)
_CACHE, _POS, _ACTIVE, _TOKENS, _RNG, _METHOD, _TEMP, _TOPK = range(8)


class GenerationRequest:
    """Handle for one submitted prompt: collects generated tokens,
    streams them (`stream()` / `on_token`), resolves via `result()`."""

    def __init__(self, prompt, max_new_tokens, eos_id, method,
                 temperature, top_k, on_token=None):
        self.prompt = prompt                  # np.int32 (plen,)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.method = method                  # sampling.GREEDY/SAMPLE
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.on_token = on_token
        self.tokens = []                      # generated token ids
        self.error = None
        self.finish_reason = None             # "eos" | "length" | "error"
        #: request-scoped tracing (monitoring/requests.py): None with
        #: monitoring off — every append below is one is-None branch
        self.trace = None
        self.trace_id = None
        #: the server's sequence number (`req` on every `serve.*` span
        #: of this request) and the clock reading `submit()` took, from
        #: which `serve.admit` reckons `queue_wait_us`
        self.seq = 0
        self.t_submit = None
        self._done = threading.Event()
        self._stream = queue.Queue()

    # -- server side ------------------------------------------------------
    def _push(self, tok):
        self.tokens.append(tok)
        self._stream.put(tok)
        if self.on_token is not None:
            try:
                self.on_token(tok)
            except Exception:  # noqa: BLE001 — a bad callback must not
                pass           # kill the shared decode loop

    def _finish(self, reason):
        self.finish_reason = reason
        if self.trace is not None:
            self.trace.event("retire", reason=reason,
                             tokens=len(self.tokens))
            self.trace.finish(reason)
        self._done.set()
        self._stream.put(None)

    def _fail(self, exc):
        self.error = exc
        if self.trace is not None:
            self.trace.event("failed", error=type(exc).__name__)
        self._finish("error")

    # -- client side ------------------------------------------------------
    def done(self):
        return self._done.is_set()

    def result(self, timeout=None):
        """Block until the request finished; returns the generated
        token ids — when generation stopped on `eos_id`, the EOS token
        is the last element (finish_reason tells which case hit)."""
        if not self._done.wait(timeout):
            raise TimeoutError("generation request still in flight")
        if self.error is not None:
            raise self.error
        return list(self.tokens)

    def stream(self, timeout=None):
        """Yield tokens as they are generated (ends at EOS/length).
        `timeout` bounds the wait per token (TimeoutError on expiry,
        matching result()). A server death pushes the terminal error
        sentinel immediately — consumers raise promptly, they never
        wait out the timeout on a dead decode loop."""
        while True:
            try:
                tok = self._stream.get(timeout=timeout)
            except queue.Empty:
                raise TimeoutError(
                    "generation stream produced no token within the "
                    "timeout") from None
            if tok is None:
                if self.error is not None:
                    raise self.error
                return
            yield tok


class _SlotJournal:
    """Host-side crash-replay journal for one admitted request.

    `admit_id` (the admission counter value) derives the slot's rng
    key; the prompt, sampling config, and delivered tokens live on the
    request itself — together they make the token stream a pure
    function of this record, which is exactly what `_replay_one` needs
    to continue an interrupted request bit-identically. While a
    re-generation replay is in flight, `expect` holds the
    already-delivered prefix and `replay_idx` the suppression cursor.
    `disp_pos` (paged servers) is the host upper bound of KV rows whose
    writes have been dispatched — the page allocator covers
    `[disp_pos, disp_pos + k)` before each block, so live writes always
    land on mapped private pages without ever syncing device `pos`."""

    __slots__ = ("req", "admit_id", "expect", "replay_idx", "disp_pos")

    def __init__(self, req, admit_id):
        self.req = req
        self.admit_id = admit_id
        self.expect = None
        self.replay_idx = 0
        self.disp_pos = 0


def _queued_req(item):
    """The GenerationRequest behind one admission-queue entry: adopted
    records (`adopt()`) ride the queue as their `_SlotJournal`, local
    submits as the bare request — drain/shed must fail either form."""
    return item.req if isinstance(item, _SlotJournal) else item


class _Block:
    """One in-flight sampled-token block: the device (k, S) output of a
    superstep/verify dispatch, the slot→journal map snapshotted at
    dispatch time (delivery must never hand a stale token to a slot
    re-admitted since), and the timing anchors for the per-token and
    fetch-overlap metrics. `proposed` is the per-slot draft-proposal
    count (drafting rounds only); `step` the dispatch's number, which
    its `serve.dispatch`, `serve.fetch` and `serve.deliver` spans
    share."""

    __slots__ = ("tokens", "recs", "k", "t0", "t_copy", "proposed",
                 "step")

    def __init__(self, tokens, recs, k, t0, t_copy, proposed=None,
                 step=0):
        self.tokens = tokens
        self.recs = recs
        self.k = k
        self.t0 = t0
        self.t_copy = t_copy
        self.proposed = proposed
        self.step = step    # dispatch number: its spans share it


def _ngram_propose(history, nd, n=3):
    """Prompt-lookup drafting: propose the `nd` tokens that followed
    the most recent PREVIOUS occurrence of the history's trailing
    n-gram (falling back to shorter grams down to a unigram). One
    vectorized sliding-window comparison per gram length — this runs
    on the decode hot path once per greedy slot per drafting round, so
    no per-position python loop. Wrong proposals cost nothing but the
    masked lanes of one verify dispatch; only exact greedy matches are
    ever delivered."""
    h = np.array(history, np.int32)
    t = len(h)
    for g in range(min(n, t - 1), 0, -1):
        gram = h[t - g:]
        # all candidate windows end before the trailing gram starts
        wins = np.lib.stride_tricks.sliding_window_view(h[:t - 1], g)
        hits = np.flatnonzero((wins == gram).all(axis=1))
        if len(hits):
            j = int(hits[-1])       # rightmost = freshest context wins
            tail = h[j + g:j + g + nd]
            if len(tail):
                return tail
    return h[:0]


class GenerationServer:
    """Continuous-batching KV-cache decode server over one model.

    `decoder`: a `generation.decode` adapter (BertDecoder /
    RecurrentDecoder) or a recurrent `MultiLayerNetwork` (wrapped
    automatically). `slots` is the decode batch bucket; `cache_lengths`
    the cache rungs (prompt_len + max_new_tokens must fit the top
    rung); `prompt_buckets` the prefill length ladder.

    Survivability knobs: `restart_policy` bounds supervised restarts
    after a failed recovery (default 3 attempts, short backoff);
    `max_consecutive_failures` bounds crash-recover churn with zero
    forward progress; `pressure_relief_steps` clean decode steps — or
    `pressure_relief_secs` of wall-clock quiet, whichever first —
    decay one memory-pressure level; `memory_high_water` (fraction of
    device memory, None disables) proactively refuses cache growth
    from the `monitoring/memory.py` telemetry (reported 'degraded'
    while it lasts)."""

    def __init__(self, decoder, slots=4, cache_lengths=(128,),
                 prompt_buckets=None, method="greedy", temperature=1.0,
                 top_k=0, eos_id=None, max_new_tokens=64, seed=0,
                 queue_limit=256, enqueue_timeout_ms=100.0,
                 exec_cache_dir=None, restart_policy=None,
                 max_consecutive_failures=8, pressure_relief_steps=256,
                 pressure_relief_secs=60.0, memory_high_water=0.92,
                 superstep=1, draft=0):
        from deeplearning4j_tpu.generation.decode import RecurrentDecoder
        if not hasattr(decoder, "init_cache"):
            decoder = RecurrentDecoder(decoder)
        self.decoder = decoder
        self.slots = int(slots)
        if self.slots < 1:
            raise ValueError("slots must be >= 1")
        self.superstep = int(superstep)
        if self.superstep < 1:
            raise ValueError("superstep must be >= 1")
        self.draft = int(draft)
        if self.draft < 0:
            raise ValueError("draft must be >= 0")
        if self.draft and self.superstep > 1:
            raise ValueError(
                "draft and superstep > 1 are alternative decode fast "
                "paths — a drafting round already amortizes the "
                "dispatch over up to draft+1 tokens; pick one")
        if self.draft and not getattr(decoder, "supports_draft", False):
            raise ValueError(
                f"{type(decoder).__name__} has no draft-verify forward "
                "(greedy drafting needs the multi-query KV-cache "
                "`verify` path — BertDecoder with kv_dtype='fp')")
        rungs = tuple(sorted({int(c) for c in cache_lengths}))
        if not rungs or rungs[0] < 2:
            raise ValueError(f"cache_lengths must be >= 2: {cache_lengths}")
        if not decoder.uses_cache_rungs:
            # carry state is O(1) in sequence length: one rung, which
            # only bounds prompt_len + max_new_tokens
            rungs = (rungs[-1],)
        if decoder.max_cache_len is not None \
                and rungs[-1] > decoder.max_cache_len:
            raise ValueError(
                f"top cache rung {rungs[-1]} exceeds the model's "
                f"maximum decodable length {decoder.max_cache_len}")
        self.cache_lengths = rungs
        #: paged-KV mode: decoder stores KV in a physical page pool and
        #: every dispatch reads through a host-built page table
        self.paged = bool(getattr(decoder, "paged", False))
        if self.paged:
            ps = int(decoder.page_size)
            bad = [c for c in rungs if c % ps]
            if bad:
                raise ValueError(
                    f"paged decode needs cache rungs divisible by the "
                    f"page size {ps}: {bad}")
            self._pages = PageAllocator(decoder.pool_pages, ps)
        else:
            self._pages = None
        if prompt_buckets is None:
            prompt_buckets, b = [], 8
            while b < rungs[-1]:
                prompt_buckets.append(b)
                b *= 2
            prompt_buckets.append(rungs[-1])
        self.prompt_buckets = tuple(sorted({int(p)
                                            for p in prompt_buckets}))
        if self.prompt_buckets[-1] > rungs[-1]:
            raise ValueError("prompt buckets cannot exceed the top "
                             "cache rung")
        self.default_method = method_id(method)
        self.default_temperature = float(temperature)
        self.default_top_k = int(top_k)
        self.default_eos_id = eos_id
        self.default_max_new_tokens = int(max_new_tokens)
        self.seed = int(seed)
        self.enqueue_timeout = float(enqueue_timeout_ms) / 1e3
        # a caller-supplied policy sets the budget/backoff knobs but is
        # NEVER mutated (it may be shared with other servers/trainers):
        # the supervisor runs a private clone whose classifier is the
        # server's own _restartable — restart classification (retry
        # transients AND shrinkable OOMs, refuse a dead latch) is the
        # server's call, not the policy's
        rp = restart_policy or RetryPolicy(
            max_attempts=3, initial_backoff=0.02, max_backoff=0.5)
        self.restart_policy = RetryPolicy(
            max_attempts=rp.max_attempts,
            initial_backoff=rp.initial_backoff,
            max_backoff=rp.max_backoff, multiplier=rp.multiplier,
            jitter=rp.jitter, deadline=rp.deadline, seed=self.seed,
            sleep=rp._sleep, clock=rp._clock,
            classifier=self._restartable)
        self.max_consecutive_failures = int(max_consecutive_failures)
        self.pressure_relief_steps = int(pressure_relief_steps)
        # wall-clock decay: a server whose remaining traffic is all
        # refused (or that idles) takes no decode steps, so step-count
        # relief alone would leave it degraded forever after one
        # transient OOM — elapsed quiet time relieves too
        self.pressure_relief_secs = (None if pressure_relief_secs is None
                                     else float(pressure_relief_secs))
        self.memory_high_water = (None if memory_high_water is None
                                  else float(memory_high_water))
        self.stats = {"tokens": 0, "steps": 0, "supersteps": 0,
                      "admissions": 0, "retirements": 0, "errors": 0,
                      "replays": 0, "restarts": 0, "degradations": 0,
                      "draft_accepts": 0, "draft_rejects": 0}
        self.token_fetches = 0       # host syncs: ONE per decode block
        #: counts the decoder keeps on the device (decode.py, "Device-side
        #: counters"): they ride every superstep's token block to the
        #: host and are cumulative in `stats`
        self._counter_names = tuple(getattr(decoder, "counter_names", ()))
        self.stats.update(dict.fromkeys(self._counter_names, 0))
        self._counts_seen = None     # the device's counts, last fetched
        self._queue = queue.Queue(maxsize=int(queue_limit))
        self._store = None           # FunctionStore, built at warmup
        self._exec_cache_dir = exec_cache_dir
        self._exes = {}              # (name, *) -> bare executable call
        self._margs = None           # non-donated model args
        self._state = None           # donated decode-state tuple
        self._rung = None
        self._slot_req = {}          # slot -> _SlotJournal
        self._inflight = None        # _Block dispatched, not delivered
        self._latencies = collections.deque(maxlen=512)  # per-token ms
        self._replaying = []         # journals awaiting re-admission
        self._free = list(range(self.slots))
        self._counter = 0            # admission counter (rng derivation)
        self._seq = itertools.count(1)   # request numbers (span `req`)
        self._step_seq = 0           # dispatch numbers (span `step`)
        # RLock: recovery replays deliveries (user on_token callbacks)
        # under the lock; a callback calling submit() must not deadlock
        self._lock = threading.RLock()
        self._work = threading.Event()
        self._shutdown = False
        self._dead = None            # typed ServerDeadError once latched
        self._pressure = 0           # ladder level (0..3; paged 0..4)
        self._page_counts = {"prefix_hits": 0, "evictions": 0}
        self._rung_cap = None        # growth cap while under pressure
        self._clean_steps = 0        # steps since the last OOM event
        self._pressure_ts = 0.0      # monotonic time of last escalation
        self._consecutive_failures = 0   # incidents without a delivery
        self._warm = False
        self._thread = None
        self._corr = "genserver-%x" % id(self)   # ops-event incident key
        _SERVERS.add(self)

    # -- warmup (the declared trace/compile boundary) ---------------------
    def warmup(self):
        """Build the whole closed executable set — superstep (or
        draft-verify) per rung, retire, admit per (rung, prompt
        bucket), grow per rung pair, the replay key-advance — through
        the two-tier FunctionStore (warm
        replica: deserialize, no XLA compile), initialize the device
        decode state at the smallest rung, and start the decode loop.
        Idempotent (and safe under concurrent first submits)."""
        with self._lock:
            return self._warmup_locked()

    def _warmup_locked(self):
        if self._warm:
            return {"compiled": 0, "from_disk": 0, "seconds": 0.0,
                    "executables": len(self._exes)}
        from deeplearning4j_tpu.runtime.executables import FunctionStore
        t0 = time.perf_counter()
        # slots is part of every executable's SHAPE but not of the
        # (name, rung, bucket) keys — it must be part of the store
        # identity or two servers over the same model with different
        # slot counts would share (wrong-shaped) disk entries
        store = FunctionStore(
            f"{self.decoder.fingerprint()}-s{self.slots}",
            directory=self._exec_cache_dir)
        if self.draft:
            store.register("verify", self._traced_verify(self.draft),
                           donate_argnums=self._donate_range())
        else:
            store.register("superstep",
                           self._traced_superstep(self.superstep),
                           donate_argnums=self._donate_range())
        store.register("admit", self._traced_admit,
                       donate_argnums=self._donate_range())
        store.register("retire", self._traced_retire,
                       donate_argnums=(0, 1, 2))
        if self.paged:
            store.register(
                "page_copy",
                lambda cache, src, dst: self.decoder.page_copy(
                    cache, src, dst),
                donate_argnums=(0,))
        store.register(
            "advance_key_n",
            lambda k, n: lax.fori_loop(
                0, n, lambda _, kk: split_keys(kk[None])[0][0], k))
        self._margs = tuple(self.decoder.model_args())
        sds = jax.ShapeDtypeStruct
        scalar_i = sds((), jnp.int32)
        scalar_f = sds((), jnp.float32)
        slot_i = sds((self.slots,), jnp.int32)
        for ci, rung in enumerate(self.cache_lengths):
            spec = self._state_spec(rung)
            margs_spec = jax.tree_util.tree_map(
                lambda l: sds(jnp.shape(l), jnp.result_type(l)),
                self._margs)
            # paged mode threads the page table through every decode
            # dispatch; its width is the rung's page count
            ptab = ((sds((self.slots,
                          rung // self.decoder.page_size), jnp.int32),)
                    if self.paged else ())
            if self.draft:
                key = ("verify", rung, self.draft)
                e = store.load_or_compile(
                    key, (*margs_spec, *spec, slot_i, slot_i,
                          sds((self.slots, self.draft), jnp.int32),
                          slot_i, *ptab))
            else:
                key = ("superstep", rung, self.superstep)
                e = store.load_or_compile(
                    key, (*margs_spec, *spec, slot_i, slot_i, *ptab))
            self._exes[key] = e.call
            for p in self.prompt_buckets:
                if p > rung:
                    continue
                wrow = ((sds((-(-p // self.decoder.page_size),),
                             jnp.int32),) if self.paged else ())
                key = ("admit", rung, p)
                e = store.load_or_compile(
                    key, (*margs_spec, *spec, scalar_i,
                          sds((p,), jnp.int32), scalar_i,
                          sds((2,), jnp.uint32), scalar_i, scalar_f,
                          scalar_i, *wrow))
                self._exes[key] = e.call
            if self.paged:
                # the pool is rung-independent: growth is a host-side
                # rung relabel, no grow executables exist
                continue
            for bigger in self.cache_lengths[ci + 1:]:
                name = f"grow_to_{bigger}"
                store.register(
                    name,
                    lambda cache, _to=bigger: self.decoder.grow(cache,
                                                                _to),
                    donate_argnums=(0,))
                key = (name, rung)
                e = store.load_or_compile(key, (spec[_CACHE],))
                self._exes[key] = e.call
        key = ("retire",)
        e = store.load_or_compile(
            key, (sds((self.slots,), jnp.int32),
                  sds((self.slots,), jnp.bool_),
                  sds((self.slots,), jnp.int32), scalar_i))
        self._exes[key] = e.call
        key = ("advance_key_n",)
        e = store.load_or_compile(key, (sds((2,), jnp.uint32),
                                        scalar_i))
        self._exes[key] = e.call
        if self.paged:
            key = ("page_copy",)
            e = store.load_or_compile(
                key, (self._state_spec(self.cache_lengths[0])[_CACHE],
                      scalar_i, scalar_i))
            self._exes[key] = e.call
        self._store = store
        self._rung = self.cache_lengths[0]
        self._state = self._init_state(self._rung)
        self._warm = True
        if self._thread is None:
            self._thread = threading.Thread(target=self._loop,
                                            daemon=True)
            self._thread.start()
        return {"compiled": store.stats["compiles"],
                "from_disk": store.stats["disk_hits"],
                "seconds": time.perf_counter() - t0,
                "executables": len(self._exes)}

    def _donate_range(self):
        n = len(tuple(self.decoder.model_args()))
        return tuple(range(n, n + 8))

    def _state_spec(self, rung):
        sds = jax.ShapeDtypeStruct
        s = self.slots
        cache = jax.eval_shape(
            lambda: self.decoder.init_cache(s, rung))
        return (cache, sds((s,), jnp.int32), sds((s,), jnp.bool_),
                sds((s,), jnp.int32), sds((s, 2), jnp.uint32),
                sds((s,), jnp.int32), sds((s,), jnp.float32),
                sds((s,), jnp.int32))

    def _init_state(self, rung):
        s = self.slots
        self._counts_seen = np.zeros((len(self._counter_names),), np.int64)
        return (self.decoder.init_cache(s, rung),
                jnp.zeros((s,), jnp.int32),
                jnp.zeros((s,), jnp.bool_),
                jnp.zeros((s,), jnp.int32),
                jnp.zeros((s, 2), jnp.uint32),
                jnp.zeros((s,), jnp.int32),
                jnp.ones((s,), jnp.float32),
                jnp.zeros((s,), jnp.int32))

    # -- traced bodies (pure; lowered once per signature at warmup) -------
    def _traced_superstep(self, k):
        """k decode steps as ONE lax.scan dispatch. Per-slot halt masks
        freeze a slot the moment it samples its EOS token or exhausts
        its budget — frozen iterations keep recomputing the held token
        at the held position (idempotent cache writes, masked -1
        output), so the block's semantics exactly equal k sequential
        steps with host-side retirement; retirement itself happens
        after delivery, up to k steps late. `eos` is -1 for slots with
        no EOS (sampled ids are always >= 0, so it never matches);
        `budget` is the per-slot count of tokens the block may still
        emit (see _superstep_args for the replay accounting)."""

        def superstep(*args):
            n = self.decoder.n_model_args
            margs = args[:n]
            if self.paged:
                (cache, pos, active, tokens, rng, method, temp, topk,
                 eos, budget, ptab) = args[n:]
            else:
                (cache, pos, active, tokens, rng, method, temp, topk,
                 eos, budget) = args[n:]
                ptab = None

            def body(carry, _):
                cache, pos, active, tokens, rng, budget = carry
                if ptab is None:
                    logits, cache = self.decoder.step(margs, cache,
                                                      tokens, pos)
                else:
                    logits, cache = self.decoder.step(margs, cache,
                                                      tokens, pos, ptab)
                sampled, rng = sample_step(logits, rng, method, temp,
                                           topk)
                out = jnp.where(active, sampled, -1)
                budget = budget - active.astype(jnp.int32)
                halt = (sampled == eos) | (budget <= 0)
                tokens = jnp.where(active, sampled, tokens)
                pos = jnp.where(active, pos + 1, pos)
                active = active & ~halt
                return (cache, pos, active, tokens, rng, budget), out

            (cache, pos, active, tokens, rng, _), outs = lax.scan(
                body, (cache, pos, active, tokens, rng, budget), None,
                length=k)
            if self._counter_names:
                # one more row a counter, under the k rows of tokens
                counts = self.decoder.counters(cache).astype(outs.dtype)
                outs = jnp.concatenate([outs, jnp.broadcast_to(
                    counts[:, None], (counts.shape[0], outs.shape[1]))])
            return (cache, pos, active, tokens, rng, method, temp,
                    topk, outs)                           # outs (k, S)

        return superstep

    def _traced_verify(self, ndraft):
        """One greedy-drafting round as ONE dispatch: the decoder's
        multi-query `verify` forward scores the q-block
        [current, draft...], and the acceptance rule delivers the
        longest prefix of draft tokens matching the model's own greedy
        argmax, plus the model's next token — so every delivered token
        IS the vanilla greedy token (exactness by construction), and a
        full match delivers ndraft+1 tokens for one dispatch. Non-
        greedy slots ride the same dispatch with a zero-length draft
        (host-enforced): they deliver exactly one sampled token per
        round with exactly one rng split — their streams stay
        bit-identical to the undrafted path. EOS/budget truncate the
        delivered prefix and freeze the slot like the superstep."""
        d = ndraft + 1

        def verify(*args):
            n = self.decoder.n_model_args
            margs = args[:n]
            if self.paged:
                (cache, pos, active, tokens, rng, method, temp, topk,
                 eos, budget, draft, dlen, ptab) = args[n:]
                logits, cache = self.decoder.verify(
                    margs, cache, tokens, pos, draft, ptab)  # (S, d, V)
            else:
                (cache, pos, active, tokens, rng, method, temp, topk,
                 eos, budget, draft, dlen) = args[n:]
                logits, cache = self.decoder.verify(
                    margs, cache, tokens, pos, draft)        # (S, d, V)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # position 0 samples with the slot's own config (ONE split
            # per round — greedy slots ignore the key, sampled slots
            # deliver exactly this one token)
            first, rng = sample_step(logits[:, 0], rng, method, temp,
                                     topk)
            cand = jnp.concatenate([first[:, None], greedy[:, 1:]],
                                   axis=1)                 # (S, d)
            # draft j consumed iff every draft token <= j matched the
            # model's prediction (prefix rule)
            ok = ((jnp.arange(ndraft)[None, :] < dlen[:, None])
                  & (cand[:, :ndraft] == draft))
            m = jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)
            js = jnp.arange(d)[None, :]
            deliver = (js <= m[:, None]) & (js < budget[:, None])
            # stop AFTER the first delivered EOS (it is itself emitted)
            is_eos = deliver & (cand == eos[:, None])
            before = jnp.cumsum(is_eos.astype(jnp.int32), axis=1) \
                - is_eos.astype(jnp.int32)
            deliver &= (before == 0) & active[:, None]
            out = jnp.where(deliver, cand, -1)             # (S, d)
            ndel = deliver.sum(axis=1).astype(jnp.int32)
            pos = pos + ndel
            budget = budget - ndel
            last = jnp.take_along_axis(
                cand, jnp.clip(ndel - 1, 0, d - 1)[:, None],
                axis=1)[:, 0]
            tokens = jnp.where(ndel > 0, last, tokens)
            active = active & ~(is_eos.any(axis=1) | (budget <= 0))
            return (cache, pos, active, tokens, rng, method, temp,
                    topk, out.T)                           # (d, S)

        return verify

    def _traced_admit(self, *args):
        n = self.decoder.n_model_args
        margs = args[:n]
        if self.paged:
            (cache, pos, active, tokens, rng, method, temp, topk,
             slot, prompt, plen, key, m, t, k, wrow) = args[n:]
            cache, logits = self.decoder.prefill(margs, cache, slot,
                                                 prompt, plen, wrow)
        else:
            (cache, pos, active, tokens, rng, method, temp, topk,
             slot, prompt, plen, key, m, t, k) = args[n:]
            cache, logits = self.decoder.prefill(margs, cache, slot,
                                                 prompt, plen)
        first, key2 = sample_step(logits[None], key[None], m[None],
                                  t[None], k[None])
        pos = pos.at[slot].set(plen)
        active = active.at[slot].set(True)
        tokens = tokens.at[slot].set(first[0])
        rng = rng.at[slot].set(key2[0])
        method = method.at[slot].set(m)
        temp = temp.at[slot].set(t)
        topk = topk.at[slot].set(k)
        return (cache, pos, active, tokens, rng, method, temp, topk,
                first[0])

    @staticmethod
    def _traced_retire(pos, active, tokens, slot):
        return (pos.at[slot].set(0),
                active.at[slot].set(False),
                tokens.at[slot].set(0))

    # -- client side ------------------------------------------------------
    def submit(self, prompt, max_new_tokens=None, eos_id="default",
               method=None, temperature=None, top_k=None, on_token=None,
               timeout_ms=None):
        """Queue one prompt for generation; returns a GenerationRequest
        immediately (tokens stream in as the decode loop reaches it).
        Admission is bounded: a full queue sheds with
        InferenceOverloadedError after the enqueue timeout; a dead
        server refuses with the latched ServerDeadError."""
        seq = next(self._seq)
        with _mon.span("serve.submit", req=seq):
            return self._submit(seq, prompt, max_new_tokens, eos_id,
                                method, temperature, top_k, on_token,
                                timeout_ms)

    def _submit(self, seq, prompt, max_new_tokens, eos_id, method,
                temperature, top_k, on_token, timeout_ms):
        from deeplearning4j_tpu.parallel.inference import bounded_enqueue
        if not self._warm:
            self.warmup()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        if prompt.size > self.prompt_buckets[-1]:
            raise ValueError(
                f"prompt length {prompt.size} exceeds the top prompt "
                f"bucket {self.prompt_buckets[-1]}")
        max_new = (self.default_max_new_tokens if max_new_tokens is None
                   else int(max_new_tokens))
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.size + max_new > self.cache_lengths[-1]:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new}) "
                f"exceeds the top cache rung {self.cache_lengths[-1]}")
        req = GenerationRequest(
            prompt, max_new,
            self.default_eos_id if eos_id == "default" else eos_id,
            self.default_method if method is None else method_id(method),
            (self.default_temperature if temperature is None
             else temperature),
            self.default_top_k if top_k is None else top_k,
            on_token=on_token)
        req.seq = seq
        req.t_submit = time.perf_counter()
        deadline = (None if timeout_ms is None
                    else time.monotonic() + float(timeout_ms) / 1e3)
        req.trace = _req.start("generation", meta={
            "prompt_len": int(prompt.size),
            "max_new_tokens": req.max_new_tokens,
            "method": req.method})
        if req.trace is not None:
            req.trace_id = req.trace.trace_id
            req.trace.event("enqueue", queued=self._queue.qsize())
        # liveness check + enqueue are ONE locked step: a request must
        # never land in the queue after shutdown()/_die() drained it
        # (nothing would ever fail or serve it — result() would hang)
        try:
            with self._lock:
                if self._shutdown:
                    raise RuntimeError("GenerationServer is shut down")
                if self._dead is not None:
                    raise self._dead
                bounded_enqueue(self._queue, req, deadline,
                                self.enqueue_timeout, what="generation")
        except BaseException as e:
            if req.trace is not None:
                # classify the rejection so a ring full of dead-server
                # refusals never reads as load shedding: only the
                # bounded-queue overload is a "shed"
                if isinstance(e, InferenceOverloadedError):
                    status = "shed"
                elif isinstance(e, InferenceTimeoutError):
                    status = "timeout"
                else:
                    status = "rejected"
                req.trace.event(status, error=type(e).__name__)
                req.trace.finish(status)
            if _mon.enabled():
                _events.emit(
                    "generation", _events.SERVER_REFUSED,
                    attrs={"error": type(e).__name__,
                           "request": getattr(req, "trace_id", None)},
                    correlation_id=self._corr)
            raise
        self._work.set()
        return req

    def generate(self, prompt, timeout=None, **kw):
        """Blocking convenience: submit + result."""
        return self.submit(prompt, **kw).result(timeout=timeout)

    def adopt(self, req, admit_id, timeout_ms=None):
        """Admit a pre-built request under an EXPLICIT admission id —
        the fleet-router hook behind cross-replica failover. A stream
        is a pure function of (server seed, admit_id, prompt, sampling
        config), so a router that keeps replica seeds aligned and
        assigns fleet-wide admission ids gets streams independent of
        WHICH replica serves them. `req.tokens` may already hold the
        delivered prefix of a request whose replica died mid-stream:
        the record then re-enters through the existing crash-replay
        machinery (prefix re-prefill, or re-generation with delivery
        suppressed), so the continuation is bit-identical to an
        uninterrupted run and nothing is ever re-delivered."""
        from deeplearning4j_tpu.parallel.inference import bounded_enqueue
        if not self._warm:
            self.warmup()
        plen = int(req.prompt.size)
        if plen < 1:
            raise ValueError("prompt must hold at least one token")
        if plen > self.prompt_buckets[-1]:
            raise ValueError(
                f"prompt length {plen} exceeds the top prompt "
                f"bucket {self.prompt_buckets[-1]}")
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if plen + req.max_new_tokens > self.cache_lengths[-1]:
            raise ValueError(
                f"prompt ({plen}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds the top cache rung "
                f"{self.cache_lengths[-1]}")
        rec = _SlotJournal(req, int(admit_id))
        if not req.seq:     # built by a router, not by submit()
            req.seq = next(self._seq)
        req.t_submit = time.perf_counter()
        deadline = (None if timeout_ms is None
                    else time.monotonic() + float(timeout_ms) / 1e3)
        # same locked liveness check + bounded enqueue as submit(): the
        # record must never land in a queue shutdown()/_die() drained
        with self._lock:
            if self._shutdown:
                raise RuntimeError("GenerationServer is shut down")
            if self._dead is not None:
                raise self._dead
            bounded_enqueue(self._queue, rec, deadline,
                            self.enqueue_timeout, what="generation")
        self._work.set()
        return req

    # -- decode loop ------------------------------------------------------
    def _loop(self):
        while not self._shutdown:
            try:
                self._admit_pending()
                if self._slot_req:
                    self._dispatch_block()
                elif self._inflight is not None:
                    # every occupant retired, but the pipelined tail
                    # block is still in flight: drain it (its live
                    # slots were all frozen — rows of -1 — but the
                    # fetch/step accounting must balance)
                    blk, self._inflight = self._inflight, None
                    self._deliver_block(blk)
                else:
                    if self._pressure:
                        # an idle server takes no steps and may see no
                        # growth attempts: wall-clock relief must fire
                        # from here or /health stays degraded forever
                        self._maybe_relieve_by_time()
                    with _mon.span("serve.idle"):
                        woken = self._work.wait(timeout=0.05)
                    if not woken:
                        continue
                    self._work.clear()
            except Exception as e:  # noqa: BLE001 — replay, stay up
                if not self._survive(e):
                    return

    def _admit_pending(self):
        """Admit queued requests into free slots of the in-flight batch
        — one prefill dispatch each, no shape changes (a longer request
        may first GROW the cache to a pre-compiled bigger rung).

        Failure containment: a degradation-ladder refusal
        (`MemoryPressureError`) is raised BEFORE any dispatch, so it
        fails only the triggering request and admission continues. Any
        later failure happens after the request was journaled and after
        a donating dispatch may have poisoned `self._state` (real on
        TPU; CPU ignores donation) — it propagates so `_survive`
        rebuilds the state and REPLAYS every journaled request,
        including the one whose admission crashed. (Size/shape
        validation already happened at submit()/adopt().)"""
        while self._free:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                return
            # adopted records (fleet failover / explicit-id admission)
            # ride the queue AS their journal; local submits are bare
            # requests that get their journal in _admit_one
            rec = item if isinstance(item, _SlotJournal) else None
            req = rec.req if rec is not None else item
            try:
                if rec is None:
                    self._admit_one(req)
                else:
                    self._admit_adopted(rec)
            except MemoryPressureError as e:
                req._fail(e)      # pre-dispatch refusal: state intact
                continue
            except Exception as e:  # noqa: BLE001 — see docstring
                if not any(r.req is req
                           for r in self._slot_req.values()):
                    # failed before the journal was registered: nothing
                    # will replay it — fail it so no caller hangs
                    req._fail(e)
                raise

    def _admit_one(self, req):
        """Fresh admission: assign the next admission id (the rng-key
        derivation the journal replays) and dispatch."""
        self._counter += 1
        self._admit_fresh(_SlotJournal(req, self._counter))

    def _admit_adopted(self, rec):
        """Admit a router-journaled record (`adopt()`): one with no
        delivered prefix admits exactly like a local submission, just
        under its explicit id; one carrying a delivered prefix is a
        mid-stream failover and re-enters through `_replay_one` — the
        same journal-replay path an in-process crash uses — so the
        continuation stays bit-identical and exactly-once. A record
        whose prefix already carries the terminal token only lost its
        retirement to the dead replica: finish it, never generate past
        EOS / max_new_tokens."""
        req = rec.req
        if req.done():
            return
        reason = self._finished_reason(req)
        if reason is not None:
            req._finish(reason)
            return
        if req.tokens:
            self._replay_one(rec)
        else:
            self._admit_fresh(rec)

    def _admit_fresh(self, rec):
        """Dispatch one journaled first-time admission and count it."""
        req = rec.req
        t0 = time.perf_counter()
        self._admit_rec(rec, req.prompt, self._admit_key(rec.admit_id))
        prefill_ms = (time.perf_counter() - t0) * 1e3
        self.stats["admissions"] += 1
        self.stats["tokens"] += 1     # the prefill's first sampled token
        if _mon.enabled():
            reg = _mon.get_registry()
            reg.counter(_mon.GEN_ADMISSIONS,
                        help="sequences admitted into the decode "
                             "batch").inc()
            reg.counter(_mon.GEN_TOKENS,
                        help="tokens generated (all slots)").inc()
            reg.histogram(_mon.GEN_PREFILL_MS,
                          help="prompt prefill + cache-graft wall "
                               "time").observe(prefill_ms,
                                               trace_id=req.trace_id)
            reg.gauge(_mon.GEN_ACTIVE_SLOTS,
                      help="occupied decode slots").set(
                len(self._slot_req))

    def _admit_rec(self, rec, prompt, key):
        """Admission dispatch shared by fresh admissions and
        crash-replay re-admissions: gate growth through the degradation
        ladder, JOURNAL the record before the first donating dispatch
        (a post-donation crash re-admits it from the journal), grow if
        needed, prefill, and deliver the first sampled token (delivery
        is suppressed while the record replays an already-delivered
        prefix)."""
        req = rec.req
        plen = int(prompt.size)
        pbucket = next(p for p in self.prompt_buckets if p >= plen)
        # how long the request sat in the queue: from submit()'s stamp
        # (a replayed record is admitted again: its wait is since then)
        waited = (-1 if req.t_submit is None else
                  int((time.perf_counter() - req.t_submit) * 1e6))
        with _mon.span("serve.admit", req=req.seq, prompt_len=plen,
                       bucket=pbucket, queue_wait_us=waited):
            self._admit_in_slot(rec, prompt, key, plen, pbucket)

    def _admit_in_slot(self, rec, prompt, key, plen, pbucket):
        req = rec.req
        needed = int(req.prompt.size) + req.max_new_tokens
        rung = self._rung
        if needed > rung or pbucket > rung:
            rung = self._rung_for(needed, pbucket)
            self._check_growth(rung)    # raises MemoryPressureError
        if self._pages is not None and _faults.ACTIVE is not None:
            # fired BEFORE the slot pop: an injected admission-time
            # pool fault (MemoryPressureError-classified) is contained
            # to the request without leaking the slot
            _faults.ACTIVE.fire(_faults.CACHE_PAGE)
        slot = self._free.pop()
        wrow = None
        if self._pages is not None:
            try:
                wrow = self._pages.admit_slot(slot, prompt, pbucket)
            except PagePoolExhaustedError:
                # PRE-dispatch refusal (allocations rolled back): the
                # slot goes back untouched and only this request fails
                self._free.append(slot)
                if _mon.enabled():
                    _events.emit(
                        "generation", _events.PAGES_EXHAUSTED,
                        attrs={"request": getattr(req, "trace_id", None)},
                        correlation_id=self._corr)
                raise
            rec.disp_pos = plen
        self._slot_req[slot] = rec
        if rung != self._rung:
            if _faults.ACTIVE is not None:
                _faults.ACTIVE.fire(_faults.CACHE_GROW)
            if req.trace is not None:
                req.trace.event("grow", to_rung=rung)
            if _mon.enabled():
                _events.emit("generation", _events.CACHE_GROWN,
                             attrs={"to_rung": rung},
                             correlation_id=self._corr)
            if self._pages is not None:
                # the pool is rung-independent: growth just widens the
                # page table the next dispatches read through
                self._rung = rung
            else:
                call = self._exes[(f"grow_to_{rung}", self._rung)]
                cache = call(self._state[_CACHE])
                self._state = (cache,) + self._state[1:]
                self._rung = rung
        if req.trace is not None:
            req.trace.event("admit", slot=slot, rung=rung,
                            bucket=pbucket, admit_id=rec.admit_id)
        padded = np.zeros((pbucket,), np.int32)
        padded[:plen] = prompt
        if _faults.ACTIVE is not None:
            _faults.ACTIVE.fire(_faults.GENERATION_ADMIT)
        call = self._exes[("admit", rung, pbucket)]
        extra = () if wrow is None else (wrow,)
        out = call(*self._margs, *self._state, np.int32(slot), padded,
                   np.int32(plen), key, np.int32(req.method),
                   np.float32(req.temperature), np.int32(req.top_k),
                   *extra)
        self._state = tuple(out[:8])
        if self._pages is not None:
            self._emit_page_metrics()
        first = int(self._fetch_tokens(out[8], req=req.seq))
        self._deliver(slot, rec, first)

    def _admit_key(self, admit_id):
        """Per-request admission rng key: a pure function of
        (server seed, admission id) — the identity crash-replay re-derives."""
        return np.random.default_rng(
            (self.seed, admit_id)).integers(0, 2 ** 32, size=2,
                                            dtype=np.uint32)

    def _rung_for(self, needed, pbucket):
        """Smallest pre-compiled cache rung admitting a request that
        needs `needed` rows and prefills at prompt bucket `pbucket`."""
        return next(c for c in self.cache_lengths
                    if c >= needed and c >= pbucket)

    def _superstep_args(self):
        """Per-dispatch EOS/budget columns: pure functions of the host
        journal at dispatch time. A replay-suppressed slot's budget
        includes its undelivered journaled prefix (the device must
        regenerate it before the live continuation). With a block
        already in flight, its undelivered tokens are not yet counted,
        so the budget may over-allow by up to one block — delivery
        clamps exactly at max_new/EOS, so overshoot is
        computed-but-dropped, never delivered."""
        eos = np.full((self.slots,), -1, np.int32)
        budget = np.zeros((self.slots,), np.int32)
        for slot, rec in self._slot_req.items():
            req = rec.req
            if req.eos_id is not None:
                eos[slot] = req.eos_id
            left = req.max_new_tokens - len(req.tokens)
            if rec.expect is not None:
                left += len(rec.expect) - rec.replay_idx
            budget[slot] = max(left, 0)
        return eos, budget

    def _propose_drafts(self):
        """Host-side draft proposal (pure numpy over the request
        journal — no device work, no syncs): a replaying slot proposes
        its journaled prefix (a guaranteed-exact draft); a live GREEDY
        slot proposes the prompt-lookup n-gram continuation of its own
        history; non-greedy slots propose nothing (their sampled
        streams must consume exactly one rng split per token)."""
        nd = self.draft
        draft = np.zeros((self.slots, nd), np.int32)
        dlen = np.zeros((self.slots,), np.int32)
        for slot, rec in self._slot_req.items():
            req = rec.req
            if req.method != GREEDY:
                continue
            if rec.expect is not None:
                tail = rec.expect[rec.replay_idx:rec.replay_idx + nd]
            else:
                tail = _ngram_propose(
                    np.concatenate([req.prompt,
                                    np.array(req.tokens, np.int32)]),
                    nd)
            if len(tail):
                draft[slot, :len(tail)] = tail
                dlen[slot] = len(tail)
        return draft, dlen

    def _page_args(self, k):
        """Paged-mode page prep for one decode block (host work on the
        dispatch boundary — zero syncs): guarantee every occupied slot
        owns writable pages for its next `k` KV rows — allocating fresh
        pages and copy-on-writing shared ones (each CoW is one tiny
        pre-compiled `("page_copy",)` dispatch) — then materialize the
        page table at the current rung width. Coverage is clipped to
        the request's total row need; a frozen lane's held-position
        rewrite past that lands on the null page by construction
        (unmapped table entries are 0)."""
        if _faults.ACTIVE is not None:
            _faults.ACTIVE.fire(_faults.CACHE_PAGE)
        copy = self._exes[("page_copy",)]
        for slot, rec in self._slot_req.items():
            req = rec.req
            needed = int(req.prompt.size) + req.max_new_tokens
            hi = min(rec.disp_pos + k, needed)
            if hi <= rec.disp_pos:
                continue
            for src, dst in self._pages.ensure_range(slot, rec.disp_pos,
                                                     hi - 1):
                cache = copy(self._state[_CACHE], np.int32(src),
                             np.int32(dst))
                self._state = (cache,) + self._state[1:]
            rec.disp_pos = hi
        self._emit_page_metrics()
        return self._pages.build_table(
            self.slots, self._rung // self.decoder.page_size)

    def _emit_page_metrics(self):
        """Page-pool observability (enabled-guarded, rides the dispatch
        boundary): occupancy/sharing gauges plus eviction and
        prefix-hit counters incremented by delta from the allocator's
        monotonic stats."""
        if not _mon.enabled():
            return
        reg = _mon.get_registry()
        occ = self._pages.occupancy()
        reg.gauge(_mon.GEN_PAGES_ACTIVE,
                  help="physical KV pages holding live or cold-resident "
                       "content").set(occ["pages_active"])
        reg.gauge(_mon.GEN_PAGES_SHARED,
                  help="shared (prefix-dedup) pages referenced by >= 1 "
                       "live slot").set(occ["pages_shared"])
        st = self._pages.stats
        for metric, key, hlp in (
                (_mon.GEN_PAGE_EVICTIONS, "evictions",
                 "cold shared KV pages evicted (LRU / ladder)"),
                (_mon.GEN_PREFIX_HITS, "prefix_hits",
                 "admissions that reused >= 1 shared prefix page")):
            delta = st[key] - self._page_counts[key]
            if delta:
                reg.counter(metric, help=hlp).inc(delta)
                self._page_counts[key] = st[key]

    def _dispatch_block(self):
        """Dispatch the next decode block (superstep scan or drafting
        verify round) for the whole batch, start the ASYNC host copy of
        its sampled-token output, then deliver the PREVIOUS block while
        this one computes — the journal append and stream delivery run
        behind compute instead of gating it."""
        self._step_seq += 1
        k = self.draft + 1 if self.draft else self.superstep
        with _mon.span("serve.dispatch", step=self._step_seq, k=k,
                       active=len(self._slot_req)):
            prev = self._dispatch(k)
        if prev is not None:
            self._deliver_block(prev)

    def _dispatch(self, k):
        """The dispatch itself; returns the block that was in flight
        before it, for `_dispatch_block` to deliver."""
        t0 = time.perf_counter()
        if _faults.ACTIVE is not None:
            # multi-token block dispatches (superstep scans AND
            # drafting verify rounds) fire the superstep site; the
            # k=1 per-token path keeps the original step site so
            # existing chaos schedules keep their call numbering
            _faults.ACTIVE.fire(_faults.GENERATION_SUPERSTEP
                                if self.superstep > 1 or self.draft
                                else _faults.GENERATION_STEP)
        eos, budget = self._superstep_args()
        ptab = (() if self._pages is None else (self._page_args(k),))
        if self.draft:
            draft, dlen = self._propose_drafts()
            call = self._exes[("verify", self._rung, self.draft)]
            out = call(*self._margs, *self._state, eos, budget, draft,
                       dlen, *ptab)
            proposed = dlen
        else:
            call = self._exes[("superstep", self._rung,
                               self.superstep)]
            out = call(*self._margs, *self._state, eos, budget, *ptab)
            proposed = None
        self._state = tuple(out[:8])
        block = self._start_fetch(out[8])
        prev, self._inflight = self._inflight, _Block(
            block, dict(self._slot_req), k, t0, time.perf_counter(),
            proposed, self._step_seq)
        return prev

    def _deliver_block(self, blk):
        """Materialize one sampled-token block (THE host sync) and
        deliver it step-major: -1 marks a frozen/empty lane; a slot
        retired or re-admitted since the block's dispatch is skipped
        (its journal snapshot no longer owns the slot)."""
        with _mon.span("serve.deliver", step=blk.step) as sp:
            sp.set_metadata(tokens=self._deliver_tokens(blk))

    def _deliver_tokens(self, blk):
        """`_deliver_block`'s work; returns the live tokens delivered."""
        overlap_ms = (time.perf_counter() - blk.t_copy) * 1e3
        toks = self._fetch_tokens(blk.tokens, step=blk.step)  # (k, S)
        if self._counter_names and blk.proposed is None:
            toks = self._take_counters(toks, blk.k)   # superstep blocks
        dt_ms = (time.perf_counter() - blk.t0) * 1e3
        # request timelines: one "block" event per still-owned slot —
        # appended HERE, on the existing fetch boundary (toks is host
        # data already), BEFORE delivery so a retirement this block
        # lands after its final block event. Zero new syncs.
        ex_tid = None
        for slot, rec in blk.recs.items():
            if self._slot_req.get(slot) is not rec:
                continue
            if ex_tid is None and rec.expect is None:
                ex_tid = rec.req.trace_id
            tr = rec.req.trace
            if tr is not None:
                tr.event("block", k=blk.k,
                         tokens=int((toks[:, slot] >= 0).sum()),
                         wall_ms=round(dt_ms, 3),
                         overlap_ms=round(overlap_ms, 3))
        live = 0
        ndel = np.zeros((toks.shape[1],), np.int32)
        for row in toks:
            for slot, rec in blk.recs.items():
                tok = int(row[slot])
                if tok < 0 or self._slot_req.get(slot) is not rec:
                    continue
                if rec.expect is None:
                    live += 1
                ndel[slot] += 1
                self._deliver(slot, rec, tok)
        self.stats["steps"] += 1
        self.stats["tokens"] += live
        # realized block depth: a superstep block truly executed k scan
        # iterations, but a drafting round is ONE dispatch whose token
        # yield is whatever was accepted — dividing its wall by the
        # MAXIMUM deliverable (draft+1) would overstate per-token
        # latency quality by up to (draft+1)x on miss-heavy workloads
        k_real = (blk.k if blk.proposed is None
                  else max(1, int(ndel.max(initial=0))))
        self._latencies.append(dt_ms / k_real)
        accepts = rejects = 0
        if blk.proposed is not None:
            # count only tokens that actually reached delivery (ndel):
            # lanes of slots retired/re-admitted since dispatch were
            # skipped above and must not inflate the acceptance rate
            accepts = int(np.minimum(np.maximum(ndel - 1, 0),
                                     blk.proposed).sum())
            rejects = int(blk.proposed.sum()) - accepts
            self.stats["draft_accepts"] += accepts
            self.stats["draft_rejects"] += rejects
        multi = self.superstep > 1 or self.draft > 0
        if multi:
            self.stats["supersteps"] += 1
        if self._pressure:
            self._clean_steps += k_real
            if self._clean_steps >= self.pressure_relief_steps:
                self._relieve_pressure()
        if _mon.enabled():
            reg = _mon.get_registry()
            reg.counter(_mon.GEN_TOKENS,
                        help="tokens generated (all slots)").inc(live)
            reg.histogram(_mon.GEN_PER_TOKEN_MS,
                          help="decode wall time per token (block "
                               "wall / realized block depth)").observe(
                dt_ms / k_real, trace_id=ex_tid)
            reg.histogram(_mon.GEN_TOKENS_PER_DISPATCH,
                          help="live tokens delivered per decode "
                               "dispatch").observe(live)
            reg.histogram(_mon.GEN_FETCH_OVERLAP_MS,
                          help="window the async token fetch had to "
                               "overlap the next dispatch").observe(
                overlap_ms)
            if multi:
                reg.counter(_mon.GEN_SUPERSTEPS,
                            help="multi-token decode-block dispatches "
                                 "(superstep scans / draft-verify "
                                 "rounds)").inc()
            if blk.proposed is not None:
                reg.counter(_mon.GEN_DRAFT_ACCEPTS,
                            help="draft tokens accepted (delivered "
                                 "beyond the per-round baseline "
                                 "token)").inc(accepts)
                reg.counter(_mon.GEN_DRAFT_REJECTS,
                            help="draft tokens proposed but not "
                                 "delivered (mismatch or EOS/budget "
                                 "truncation)").inc(rejects)
        return live

    def _take_counters(self, block, k):
        """Split a fetched superstep block into its k rows of tokens and
        the decoder's counters under them (host data already: no sync).
        The device counts in wrapping int32 since its state was built;
        `stats` gets what was added since the last block."""
        seen = block[k:, 0].astype(np.int64)
        for name, add in zip(self._counter_names,
                             (seen - self._counts_seen) & 0xFFFFFFFF):
            self.stats[name] += int(add)
        self._counts_seen = seen
        return block[:k]

    def _start_fetch(self, arr):
        """Start the NON-BLOCKING device→host copy of a sampled-token
        block (part of the declared fetch boundary): the copy runs
        while the next block computes; `_fetch_tokens` later
        materializes an already-landed buffer instead of stalling the
        loop on the round-trip."""
        try:
            arr.copy_to_host_async()
        except AttributeError:      # backend without async copy:
            pass                    # _fetch_tokens blocks as before
        return arr

    def _fetch_tokens(self, arr, **ids):
        """THE per-superstep host sync: materialize the sampled-token
        block. The journal append rides this same boundary — `_deliver`
        stores the fetched tokens on the request's host-side list, so
        crash-replay costs zero extra syncs. `ids` name whose fetch it
        is on the `serve.fetch` span: a superstep's `step`, an
        admission's `req`."""
        self.token_fetches += 1
        with _mon.span("serve.fetch", **ids):
            return np.asarray(arr)

    def _deliver(self, slot, rec, tok):
        req = rec.req
        if rec.expect is not None:
            # crash-replay suppression: this token was delivered before
            # the crash — verify the re-generated stream matches the
            # journal and hand delivery back to the live path once the
            # prefix is exhausted
            if tok != rec.expect[rec.replay_idx]:
                req.error = ReplayDivergedError(
                    f"replayed token {tok} != journaled "
                    f"{rec.expect[rec.replay_idx]} at position "
                    f"{rec.replay_idx}")
                rec.expect = None
                self._retire_slot(slot, "error")
                return
            rec.replay_idx += 1
            if rec.replay_idx >= len(rec.expect):
                rec.expect = None
            return
        self._consecutive_failures = 0      # forward progress
        req._push(tok)
        reason = self._finished_reason(req)
        if reason is not None:
            self._retire_slot(slot, reason)

    def _retire_slot(self, slot, reason):
        """Per-sequence retirement: clear the slot's device columns
        (one tiny pre-compiled dispatch) and free it for admission."""
        call = self._exes[("retire",)]
        pos, active, tokens = call(self._state[_POS],
                                   self._state[_ACTIVE],
                                   self._state[_TOKENS], np.int32(slot))
        self._state = (self._state[_CACHE], pos, active, tokens,
                       *self._state[_RNG:])
        rec = self._slot_req.pop(slot)
        self._free.append(slot)
        if self._pages is not None:
            # private pages free; shared prefix pages stay resident
            # cold for the next identical prompt (evictable currency)
            self._pages.release_slot(slot)
        self.stats["retirements"] += 1
        try:
            if _mon.enabled():
                reg = _mon.get_registry()
                reg.counter(_mon.GEN_RETIREMENTS,
                            help="sequences retired (EOS or "
                                 "length)").inc()
                reg.gauge(_mon.GEN_ACTIVE_SLOTS,
                          help="occupied decode slots").set(
                    len(self._slot_req))
        finally:
            # once popped from the journal, the request MUST finish —
            # a failure above would otherwise leave it unreplayable
            # and its consumer hung forever
            rec.req._finish(reason)

    # -- survivability: crash-replay, supervision, degradation -----------
    def _survive(self, exc):
        """Decode-loop failure: crash-replay recovery first (journal →
        rebuild → re-admit), then supervised restarts under the
        RetryPolicy budget. OOM-classified failures escalate the
        memory-pressure ladder before the rebuild. Returns False when
        the server latched dead (the loop must exit)."""
        self.stats["errors"] += 1
        self._consecutive_failures += 1
        if self._consecutive_failures > self.max_consecutive_failures:
            self._die(exc, reason=(
                f"no forward progress after "
                f"{self._consecutive_failures} consecutive "
                f"decode-loop failures"))
            return False
        if _mon.enabled():
            _events.emit(
                "generation", _events.SERVER_DISRUPTED,
                attrs={"error": type(exc).__name__,
                       "consecutive": self._consecutive_failures},
                correlation_id=self._corr)
        if CrashReportingUtil.is_oom(exc):
            self._note_memory_pressure(exc)
        try:
            self._recover(exc)
            if _mon.enabled():
                _events.emit("generation", _events.SERVER_RECOVERED,
                             attrs={"via": "replay"},
                             correlation_id=self._corr)
            return True
        except Exception as e2:  # noqa: BLE001 — supervisor takes over
            ok = self._supervised_restart(e2)
            if ok and _mon.enabled():
                _events.emit("generation", _events.SERVER_RECOVERED,
                             attrs={"via": "restart"},
                             correlation_id=self._corr)
            return ok

    def _recover(self, exc=None):
        """Crash-replay recovery: every in-flight journal moves to the
        replay-pending set (the donated device state is presumed
        poisoned mid-dispatch), the decode state is rebuilt at the
        smallest rung from the warm executable set, and each surviving
        request is re-admitted with its continuation bit-identical to
        an uninterrupted run. Raises when the rebuild/replay itself
        fails — the supervisor retries; pending journals survive the
        retry because re-admission is idempotent from the journal."""
        with self._lock:
            if self._shutdown or self._dead is not None:
                return
            # the pipelined block (if any) died with the state: its
            # undelivered tokens were never journaled, so replay
            # regenerates exactly them
            self._inflight = None
            for rec in self._slot_req.values():
                if rec not in self._replaying:
                    self._replaying.append(rec)
            self._slot_req.clear()
            self._free = list(range(self.slots))
            self._replaying.sort(key=lambda r: r.admit_id)
            self._rung = self.cache_lengths[0]
            self._state = self._init_state(self._rung)
            if self._pages is not None:
                # pool contents died with the state: the allocator
                # forgets everything and the ordered re-admissions
                # rebuild table + prefix registry from the journal
                self._pages.reset()
            while self._replaying:
                rec = self._replaying[0]
                if rec.req.done():
                    self._replaying.pop(0)
                    continue
                reason = self._finished_reason(rec.req)
                if reason is not None:
                    # the final token was already delivered and only
                    # the RETIREMENT was lost to the crash: finish the
                    # request instead of replaying it — a replay would
                    # generate past EOS / max_new_tokens
                    rec.req._finish(reason)
                    self._replaying.pop(0)
                    continue
                try:
                    self._replay_one(rec)
                except MemoryPressureError as e:
                    # pre-dispatch refusal (no longer fits the capped
                    # rung): fail this request, keep replaying the rest
                    rec.req._fail(e)
                    self._replaying.pop(0)
                    continue
                self._replaying.pop(0)

    def _replay_one(self, rec):
        """Re-admit one journaled request. Preferred path: re-prefill
        prompt+generated-prefix in ONE dispatch, with the admission key
        advanced past the consumed sampling splits — the next sampled
        token continues the stream exactly (decode-exactness makes the
        prefill logits equal the uninterrupted step's). When the prefix
        outgrows the prompt-bucket ladder, fall back to re-generating
        it from the original admission state with delivery suppressed —
        per-slot keys make both paths bit-identical continuations."""
        req = rec.req
        g = len(req.tokens)
        plen = int(req.prompt.size)
        needed = plen + req.max_new_tokens
        use_prefix = g and plen + g <= self.prompt_buckets[-1]
        if use_prefix:
            # the longer prefix bucket must not force a bigger cache
            # rung than the request itself needs — a crash must never
            # inflate memory (or trip the pressure cap) versus the
            # uninterrupted run; otherwise re-generate instead
            pb_prefix = next(p for p in self.prompt_buckets
                             if p >= plen + g)
            pb_orig = next(p for p in self.prompt_buckets
                           if p >= plen)
            use_prefix = (self._rung_for(needed, pb_prefix)
                          == self._rung_for(needed, pb_orig))
        if req.trace is not None:
            req.trace.event("replay",
                            mode="prefix" if use_prefix
                            else "regenerate", delivered=g)
        if use_prefix:
            prefix = np.concatenate(
                [req.prompt, np.asarray(req.tokens, np.int32)])
            key = self._advance_key(self._admit_key(rec.admit_id), g)
            rec.expect = None
            rec.replay_idx = 0
            self._admit_rec(rec, prefix, key)
            live_first = True       # the prefill sampled a NEW token
        else:
            rec.expect = list(req.tokens) or None
            live_first = rec.expect is None   # g == 0: first-ever token
            rec.replay_idx = 0
            self._admit_rec(rec, req.prompt,
                            self._admit_key(rec.admit_id))
        self.stats["replays"] += 1
        if live_first:
            self.stats["tokens"] += 1
        if _mon.enabled():
            reg = _mon.get_registry()
            reg.counter(_mon.GEN_REPLAYS,
                        help="in-flight requests re-admitted by "
                             "crash-replay").inc()
            _events.emit(
                "generation", _events.SERVER_REPLAY,
                attrs={"request": getattr(req, "trace_id", None),
                       "mode": "prefix" if use_prefix else "regenerate",
                       "delivered": g},
                correlation_id=self._corr)
            if live_first:
                reg.counter(_mon.GEN_TOKENS,
                            help="tokens generated (all slots)").inc()
            reg.gauge(_mon.GEN_ACTIVE_SLOTS,
                      help="occupied decode slots").set(
                len(self._slot_req))

    @staticmethod
    def _finished_reason(req):
        """The finish reason a delivered-but-unretired request should
        get ("eos" / "length"), or None while it still needs tokens —
        the guard that keeps crash-replay from continuing a stream
        whose terminal token already reached the consumer."""
        if req.tokens and req.eos_id is not None \
                and req.tokens[-1] == req.eos_id:
            return "eos"
        if len(req.tokens) >= req.max_new_tokens:
            return "length"
        return None

    def _advance_key(self, key, n):
        """Advance an admission key past `n` consumed sampling splits —
        the replay-prefill continuation key. ONE dispatch of the
        pre-compiled `("advance_key_n",)` executable (n is a traced
        scalar), so replay performs zero live compiles and O(1)
        dispatches however long the delivered prefix."""
        return self._exes[("advance_key_n",)](key, np.int32(n))

    def _supervised_restart(self, exc):
        """Recovery failed: retry the rebuild+replay from the warm
        FunctionStore under the bounded RetryPolicy. The typed
        ServerDeadError latch only engages once the budget is
        exhausted (or the failure is classified unrestartable)."""

        def on_retry(attempt, e):
            self._count_restart()
            if CrashReportingUtil.is_oom(e):
                self._note_memory_pressure(e)

        self._count_restart()
        try:
            self.restart_policy.call(self._recover, on_retry=on_retry,
                                     label="generation-server restart")
            return True
        except Exception as final:  # noqa: BLE001 — budget exhausted
            self._die(final, reason="supervised restart budget "
                                    "exhausted")
            return False

    def _restartable(self, exc):
        """Restart classifier: anything is worth a bounded restart
        except a latched death, or an OOM once the degradation ladder
        has no smaller rung left to shrink into (another allocation
        attempt at the same size cannot help)."""
        if isinstance(exc, ServerDeadError):
            return False
        if CrashReportingUtil.is_oom(exc):
            if self._pressure < (4 if self._pages is not None else 3):
                return True
            cap = self._rung_cap or self.cache_lengths[-1]
            return any(c < cap for c in self.cache_lengths)
        return True

    def _count_restart(self):
        self.stats["restarts"] += 1
        if _mon.enabled():
            _mon.get_registry().counter(
                _mon.GEN_RESTARTS,
                help="supervised decode-loop restarts from the warm "
                     "FunctionStore").inc()
            _events.emit("generation", _events.SERVER_RESTARTED,
                         attrs={"restarts": self.stats["restarts"]},
                         correlation_id=self._corr)

    # -- memory-pressure degradation ladder -------------------------------
    def _note_memory_pressure(self, exc):
        """Escalate the ladder one level: 1 = refuse cache growth past
        the current rung, 2 = also shed every queued admission, 3 =
        shrink the cap one pre-compiled rung (in-flight requests replay
        into it; ones that no longer fit fail typed). Paged servers get
        an extra level between shed and shrink — 3 = evict every cold
        (refcount-zero) shared prefix page, reclaiming pool headroom
        before giving up rung capacity; shrink moves to 4. Keeps a
        `monitoring/memory.py` telemetry reading for OOM forensics."""
        self._clean_steps = 0
        self._pressure_ts = time.monotonic()
        if self._pressure == 0 or self._rung_cap is None:
            self._rung_cap = self._rung
        if self._pages is not None:
            ladder = ("refuse_growth", "shed_queue", "evict_pages",
                      "shrink")
        else:
            ladder = ("refuse_growth", "shed_queue", "shrink")
        self._pressure = min(len(ladder), self._pressure + 1)
        action = ladder[self._pressure - 1]
        if _mon.enabled():
            _events.emit(
                "generation", _events.PRESSURE_ESCALATED,
                attrs={"level": self._pressure, "action": action,
                       "error": type(exc).__name__},
                correlation_id=self._corr)
        if self._pressure >= 2:
            self._shed_queue(exc)
        if self._pages is not None and self._pressure >= 3:
            evicted = self._pages.evict_cold()
            if _mon.enabled():
                _events.emit("generation", _events.PAGES_EVICTED,
                             attrs={"evicted": evicted},
                             correlation_id=self._corr)
        if self._pressure >= len(ladder):
            smaller = [c for c in self.cache_lengths
                       if c < self._rung_cap]
            if smaller:
                self._rung_cap = smaller[-1]
                if _mon.enabled():
                    _events.emit("generation", _events.CACHE_SHRUNK,
                                 attrs={"cap": self._rung_cap},
                                 correlation_id=self._corr)
            else:
                # no smaller pre-compiled rung: the ladder is out of
                # moves — say so instead of reporting a phantom shrink
                action = "at_floor"
        self._count_degradation(action)
        if _mon.enabled():
            try:
                from deeplearning4j_tpu.monitoring import memory as _mem
                _mem.sample()
            except Exception:  # noqa: BLE001 — telemetry best-effort
                pass

    def _relieve_pressure(self):
        """A clean stretch of decode steps — or of wall-clock quiet —
        decays one pressure level; back at level 0 the growth cap
        lifts entirely."""
        self._clean_steps = 0
        self._pressure_ts = time.monotonic()
        self._pressure = max(0, self._pressure - 1)
        if self._pressure == 0:
            self._rung_cap = None
        if _mon.enabled():
            _events.emit("generation", _events.PRESSURE_RELIEVED,
                         attrs={"level": self._pressure},
                         correlation_id=self._corr,
                         resolves=self._pressure == 0)

    def _maybe_relieve_by_time(self):
        """Wall-clock decay: re-evaluated on every growth attempt, so
        pressure lifts even when the remaining traffic is all refused
        (no decode steps run, the step-count relief never fires)."""
        if self._pressure and self.pressure_relief_secs is not None \
                and (time.monotonic() - self._pressure_ts
                     >= self.pressure_relief_secs):
            self._relieve_pressure()

    def _check_growth(self, target):
        """Degradation-ladder gate on cache growth — PRE-dispatch, so a
        refusal is contained to the triggering request. Refuses past
        the pressure cap, and proactively when the live device-memory
        telemetry is already past the high-water mark (which also
        reports the server 'degraded' on /health while it lasts)."""
        self._maybe_relieve_by_time()
        if self._rung_cap is not None and target > self._rung_cap:
            self._count_degradation("refuse_growth")
            raise MemoryPressureError(
                f"cache growth to rung {target} refused: the "
                f"memory-pressure ladder caps the cache at rung "
                f"{self._rung_cap} (pressure level {self._pressure})")
        if self.memory_high_water is not None:
            from deeplearning4j_tpu.monitoring import memory as _mem
            for stats in _mem.device_memory_stats().values():
                if not stats:
                    continue
                used = stats.get("bytes_in_use")
                limit = stats.get("bytes_limit")
                if used and limit \
                        and used / limit > self.memory_high_water:
                    # telemetry-driven refusals are a degradation too:
                    # /health must say 'degraded' while the replica is
                    # systematically refusing growth, not 'ok'. No cap
                    # is set — growth resumes the moment the telemetry
                    # clears, and the pressure level decays on its own
                    self._pressure = max(self._pressure, 1)
                    self._pressure_ts = time.monotonic()
                    self._clean_steps = 0   # fresh pressure evidence
                    self._count_degradation("refuse_growth")
                    raise MemoryPressureError(
                        f"cache growth to rung {target} refused: "
                        f"device memory at {used / limit:.0%} of limit "
                        f"exceeds the {self.memory_high_water:.0%} "
                        f"high-water mark")

    def _shed_queue(self, cause):
        """Ladder level 2: fail every queued (not-yet-admitted) request
        typed instead of admitting into a memory-starved batch."""
        shed = 0
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            err = MemoryPressureError(
                "queued admission shed under memory pressure")
            err.__cause__ = cause
            _queued_req(item)._fail(err)
            shed += 1
        if shed and _mon.enabled():
            _events.emit("generation", _events.SERVER_SHED,
                         attrs={"shed": shed},
                         correlation_id=self._corr)
        return shed

    def _count_degradation(self, action):
        self.stats["degradations"] += 1
        if _mon.enabled():
            _mon.get_registry().counter(
                _mon.GEN_DEGRADATIONS, labels={"action": action},
                help="memory-pressure degradation-ladder events").inc()

    def _fail_open_requests(self, err):
        """Push the terminal error sentinel to every in-flight and
        replay-pending request (caller holds the lock; already-finished
        requests keep their results) and clear both collections."""
        for rec in list(self._slot_req.values()):
            if not rec.req.done():
                rec.req._fail(err)
        self._slot_req.clear()
        for rec in self._replaying:
            if not rec.req.done():
                rec.req._fail(err)
        self._replaying.clear()

    def _drain_queue(self, err):
        while True:
            try:
                _queued_req(self._queue.get_nowait())._fail(err)
            except queue.Empty:
                return

    def _die(self, cause, reason="decode loop died"):
        """Terminal: latch the typed ServerDeadError, refuse future
        submits, and push the error sentinel to EVERY open request —
        in-flight, replay-pending, and queued — immediately, so no
        stream consumer waits out its timeout on a dead server."""
        err = ServerDeadError(f"GenerationServer {reason}: {cause!r}")
        err.__cause__ = cause
        if _mon.enabled():
            _events.emit("generation", _events.SERVER_DEAD,
                         attrs={"reason": reason,
                                "error": type(cause).__name__},
                         correlation_id=self._corr)
        with self._lock:
            self._dead = err
            self._fail_open_requests(err)
        self._drain_queue(err)

    # -- lifecycle / status ----------------------------------------------
    def shutdown(self):
        """Idempotent: stops the decode loop; in-flight, replay-pending,
        and queued requests fail with a RuntimeError."""
        with self._lock:
            if self._shutdown:
                return
            self._shutdown = True
        self._work.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        err = RuntimeError("GenerationServer shut down")
        # any submit racing this drain either saw _shutdown under the
        # lock (raised) or enqueued before we took it above — so after
        # this drain the queue stays empty forever
        with self._lock:
            self._fail_open_requests(err)
            self._drain_queue(err)

    def __enter__(self):
        self.warmup()
        return self

    def __exit__(self, *exc):
        self.shutdown()

    def serving_state(self):
        """Compact survivability view for `GET /health`
        (resilience.health_snapshot): dead → the replica must be
        replaced; degraded → serving under the memory-pressure ladder;
        serving/cold otherwise."""
        if self._shutdown:
            # deliberate shutdown wins over an earlier death: the
            # operator already acted, /health must not keep paging
            state = "shutdown"
        elif self._dead is not None:
            state = "dead"
        elif self._pressure:
            state = "degraded"
        else:
            state = "serving" if self._warm else "cold"
        out = {"state": state, "pressure": self._pressure,
               "rung_cap": self._rung_cap,
               "active_slots": len(self._slot_req),
               "replays": self.stats["replays"],
               "restarts": self.stats["restarts"],
               "degradations": self.stats["degradations"]}
        if self._pages is not None:
            # page-pool occupancy + dedup/CoW/eviction counters: the
            # capacity signal for paged replicas on /health and
            # /generation (status() spreads this dict)
            out["page_pool"] = {**self._pages.occupancy(),
                                **self._pages.stats}
        return out

    def _latency_percentiles(self):
        """Per-token latency p50/p99 (ms) over the recent decode
        blocks' block-wall/block-steps samples — endpoint-served even
        with monitoring disabled (the host-side ring costs one float
        append per block)."""
        if not self._latencies:
            return {"per_token_p50_ms": None, "per_token_p99_ms": None}
        p50, p99 = np.percentile(list(self._latencies), [50, 99])
        return {"per_token_p50_ms": round(float(p50), 3),
                "per_token_p99_ms": round(float(p99), 3)}

    def status(self):
        dispatches = self.stats["steps"] + self.stats["admissions"]
        return {
            "decoder": type(self.decoder).__name__,
            "slots": self.slots,
            "cache_lengths": list(self.cache_lengths),
            "rung": self._rung,
            "prompt_buckets": list(self.prompt_buckets),
            "superstep": self.superstep,
            "draft": self.draft,
            "paged": self.paged,
            "active_slots": len(self._slot_req),
            "queued": self._queue.qsize(),
            "warm": self._warm,
            "executables": len(self._exes),
            "token_fetches": self.token_fetches,
            "tokens_per_dispatch": round(
                self.stats["tokens"] / dispatches, 3) if dispatches
            else None,
            "host_syncs_per_token": round(
                self.token_fetches / self.stats["tokens"], 3)
            if self.stats["tokens"] else None,
            **self._latency_percentiles(),
            **self.serving_state(),
            **self.stats,
            "store": (None if self._store is None
                      else self._store.status()),
        }


def status():
    """Aggregate generation status for every live server
    (`GET /generation` on the UIServer)."""
    return {"servers": [s.status() for s in list(_SERVERS)]}
