"""Serving chaos harness: seeded fault injection against the whole
serving stack (GenerationServer crash-replay + supervised restart +
memory-pressure ladder, ParallelInference AOT breaker, executable-store
load faults, coordination barrier faults).

The invariants every scenario asserts:
- no request hangs forever — every accepted request resolves or fails
  with a TYPED error within its timeout;
- completed token streams are BIT-IDENTICAL to a fault-free run
  (per-slot rng keys make streams pure functions of admission state,
  so crash-replay re-admission continues them exactly);
- recovery performs ZERO live compiles — everything resolves from the
  warm FunctionStore;
- a dead server pushes its typed error to every open stream
  immediately (blocked consumers raise promptly, they never wait out
  their timeout).

Fault sites driven here (scripts/check_fault_coverage.py asserts every
faults.py site is exercised by some test): GENERATION_STEP,
GENERATION_ADMIT, CACHE_GROW, CACHE_PAGE, SERVING_DISPATCH,
EXECUTABLES_LOAD, INFERENCE_FORWARD, COMM_BARRIER, COMM_ALLREDUCE.
"""
import json
import random
import threading
import time

import numpy as np
import pytest

import jax

from deeplearning4j_tpu import monitoring as mon
from deeplearning4j_tpu.generation import BertDecoder, GenerationServer
from deeplearning4j_tpu.models.bert import bert_tiny, init_bert_params
from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                   NeuralNetConfiguration, OutputLayer,
                                   Sgd)
from deeplearning4j_tpu.nn.conf.recurrent import LSTM, RnnOutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.parallel.inference import (InferenceMode,
                                                   ParallelInference)
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.resilience.errors import (InjectedFault,
                                                  MemoryPressureError,
                                                  PagePoolExhaustedError,
                                                  ServerDeadError)
from deeplearning4j_tpu.resilience.policy import (CircuitBreaker,
                                                  RetryPolicy)

V = 16   # tiny char vocab (the LSTM decode path is BIT-exact, so the
#          stream-equality assertions below are exact, not approximate)


@pytest.fixture(autouse=True)
def _clean_harness():
    faults.clear_plan()
    yield
    faults.clear_plan()
    mon.disable()


#: module-scoped on-disk executable cache (suite diet): every server
#: in this file shares one FunctionStore disk tier, so only the FIRST
#: build of each (model, slots, knobs) shape pays XLA compiles — the
#: dozen-plus later warmups deserialize in a fraction of the time
_CACHE = {"dir": None}


@pytest.fixture(scope="module", autouse=True)
def _exec_cache(tmp_path_factory):
    _CACHE["dir"] = str(tmp_path_factory.mktemp("chaos-exec"))
    yield
    _CACHE["dir"] = None


def _lstm_net(seed=3, hidden=16):
    return MultiLayerNetwork(
        (NeuralNetConfiguration.Builder().seed(seed).updater(Adam(1e-2))
         .weightInit("xavier").list()
         .layer(LSTM(nOut=hidden, activation="tanh"))
         .layer(RnnOutputLayer(lossFunction="mcxent", nOut=V,
                               activation="softmax"))
         .setInputType(InputType.recurrent(V)).build())).init()


@pytest.fixture(scope="module")
def net():
    return _lstm_net()


def _dense_net():
    return MultiLayerNetwork(
        (NeuralNetConfiguration.Builder()
         .seed(3).updater(Sgd(0.1)).activation("tanh")
         .list()
         .layer(DenseLayer.Builder().nOut(8).build())
         .layer(OutputLayer.Builder("mcxent").nOut(3)
                .activation("softmax").build())
         .setInputType(InputType.feedForward(5))
         .build())).init()


@pytest.fixture(scope="module")
def dense_net():
    return _dense_net()


@pytest.fixture(scope="module")
def bert():
    cfg = bert_tiny()
    return cfg, init_bert_params(cfg, jax.random.PRNGKey(1))


def _bert_server(bert, **kw):
    """KV-cache (rung-growing) server: the LSTM decoder collapses cache
    rungs, so every growth / memory-pressure scenario runs on the
    BertDecoder path."""
    cfg, params = bert
    kw.setdefault("slots", 2)
    kw.setdefault("cache_lengths", [16, 32])
    kw.setdefault("prompt_buckets", [8])
    kw.setdefault("method", "greedy")
    kw.setdefault("seed", 11)
    kw.setdefault("exec_cache_dir", _CACHE["dir"])
    srv = GenerationServer(BertDecoder(cfg, params), **kw)
    srv.warmup()
    return srv


def _bert_paged_server(bert, **kw):
    """_bert_server on the paged KV pool — every chaos invariant must
    also hold when recovery rebuilds a page table + prefix registry
    from the journal, not just a contiguous cache."""
    cfg, params = bert
    dec_kw = dict(page_size=8, pool_pages=kw.pop("pool_pages", 40))
    kw.setdefault("slots", 2)
    kw.setdefault("cache_lengths", [16, 32])
    kw.setdefault("prompt_buckets", [8])
    kw.setdefault("method", "greedy")
    kw.setdefault("seed", 11)
    kw.setdefault("exec_cache_dir", _CACHE["dir"])
    srv = GenerationServer(BertDecoder(cfg, params, **dec_kw), **kw)
    srv.warmup()
    return srv


#: the 4-request soak workload: mixed prompt lengths, budgets, and
#: sampling configs (temperature/top-k requests prove the rng stream
#: survives replay, not just greedy argmax)
_WORKLOAD = [
    dict(prompt=[1, 4, 2], max_new_tokens=8),
    dict(prompt=[5, 6], max_new_tokens=8, method="temperature",
         temperature=0.8),
    dict(prompt=[7, 3, 2, 1, 4, 6], max_new_tokens=12, method="top_k",
         temperature=0.9, top_k=3),
    dict(prompt=[2, 2, 5], max_new_tokens=6),
]


def _server(net, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("cache_lengths", [48])
    kw.setdefault("prompt_buckets", [8, 16])
    kw.setdefault("method", "greedy")
    kw.setdefault("seed", 11)
    kw.setdefault("exec_cache_dir", _CACHE["dir"])
    srv = GenerationServer(net, **kw)
    srv.warmup()
    return srv


def _run_workload(srv, workload=_WORKLOAD, timeout=60):
    """Submit the workload, consume every request through a streaming
    consumer THREAD (the production shape), return the token lists."""
    reqs = [srv.submit(**dict(w)) for w in workload]
    out = [None] * len(reqs)
    errs = [None] * len(reqs)

    def consume(i, req):
        try:
            out[i] = list(req.stream(timeout=timeout))
        except Exception as e:  # noqa: BLE001 — asserted by callers
            errs[i] = e

    threads = [threading.Thread(target=consume, args=(i, r))
               for i, r in enumerate(reqs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout + 10)
        assert not t.is_alive(), "stream consumer hung"
    return reqs, out, errs


# ===================== crash-replay: the headline soak =================
@pytest.fixture(scope="module")
def want_streams(net):
    """Fault-free baseline streams of the 4-request soak workload —
    computed ONCE and shared by every per-token bit-identity scenario
    (suite diet: one baseline server+run instead of one per test)."""
    srv = _server(net)
    try:
        _, want, errs = _run_workload(srv)
        assert errs == [None] * 4
        return want
    finally:
        srv.shutdown()


def test_chaos_decode_kill_streams_bit_identical(net, want_streams):
    """ACCEPTANCE: kill the decode loop at a seeded random step with 4
    concurrent streaming requests — surviving requests replay, every
    stream completes BIT-identical to the fault-free run, and
    `dl4j.gen.replays` counts the re-admissions."""
    want = want_streams
    kill_step = random.Random(20260804).randint(3, 9)
    srv = _server(net)
    try:
        mon.enable()
        replays0 = mon.get_registry().counter(mon.GEN_REPLAYS).value
        plan = faults.FaultPlan(seed=5).fail_at(faults.GENERATION_STEP,
                                                kill_step)
        with plan:
            _, got, errs = _run_workload(srv)
        assert plan.fired.get(faults.GENERATION_STEP) == 1
        assert errs == [None] * 4
        assert got == want, \
            "replayed streams must bit-match the fault-free run"
        assert srv.stats["replays"] >= 1
        assert mon.get_registry().counter(mon.GEN_REPLAYS).value \
            - replays0 == srv.stats["replays"]
        assert srv.stats["errors"] == 1
        # the server is healthy again: a fresh request serves normally
        assert len(srv.generate([3, 1], max_new_tokens=3,
                                timeout=60)) == 3
    finally:
        srv.shutdown()


def test_chaos_double_kill_and_admission_faults(net, want_streams):
    """An admission fault plus two decode-step kills in one run: the
    journal replays through all of them and the completed streams
    still bit-match the fault-free run."""
    want = want_streams
    srv = _server(net)
    try:
        plan = (faults.FaultPlan(seed=9)
                .fail_at(faults.GENERATION_ADMIT, 2)
                .fail_at(faults.GENERATION_STEP, 4)
                .fail_at(faults.GENERATION_STEP, 11))
        with plan:
            _, got, errs = _run_workload(srv)
        assert errs == [None] * 4
        assert got == want
        assert srv.stats["replays"] >= 2
        assert srv.stats["errors"] >= 2
    finally:
        srv.shutdown()


def test_chaos_kill_mid_superstep_streams_bit_identical(net):
    """ACCEPTANCE (superstep × crash-replay): kill the decode loop
    mid-SUPERSTEP (k=8 — up to 32 in-flight undelivered tokens across
    4 concurrent streams die with the block) at two seeded points; the
    journal replays every survivor, the completed streams bit-match
    the fault-free k=8 run, and recovery performs zero live
    compiles."""
    baseline = _server(net, superstep=8)
    try:
        _, want, errs = _run_workload(baseline)
        assert errs == [None] * 4
    finally:
        baseline.shutdown()

    srv = _server(net, superstep=8)
    try:
        compiles0 = srv._store.stats["compiles"]
        plan = (faults.FaultPlan(seed=17)
                .fail_at(faults.GENERATION_SUPERSTEP, 2)
                .fail_at(faults.GENERATION_SUPERSTEP, 4))
        with plan:
            _, got, errs = _run_workload(srv)
        assert plan.fired.get(faults.GENERATION_SUPERSTEP) == 2
        assert errs == [None] * 4
        assert got == want, \
            "superstep replays must bit-match the fault-free run"
        assert srv.stats["replays"] >= 1
        assert srv.stats["errors"] >= 2
        assert srv._store.stats["compiles"] == compiles0, \
            "superstep crash-replay must not compile"
        # the whole batch still amortizes: one fetch per BLOCK
        assert srv.stats["supersteps"] > 0
    finally:
        srv.shutdown()


def test_chaos_killed_request_timeline_full_lifecycle(net):
    """ISSUE 15 acceptance (chaos × request tracing): a decode kill
    mid-stream at superstep k=8 leaves every request with a finished
    timeline showing the FULL lifecycle — enqueue → admit → superstep
    blocks → replay → re-admit → more blocks → retire — served over
    `GET /requests/<id>`, while the delivered streams stay bit-identical
    to the fault-free run. Zero added host syncs on the decode path is
    proven by the fastpath sync lint (test_fastpath_lint walks the
    timeline appends inside the _deliver_block/_fetch_tokens
    boundary)."""
    import urllib.request
    from deeplearning4j_tpu.monitoring import requests as reqmod
    from deeplearning4j_tpu.ui.server import UIServer

    baseline = _server(net, superstep=8)
    try:
        _, want, errs = _run_workload(baseline)
        assert errs == [None] * 4
    finally:
        baseline.shutdown()

    srv = _server(net, superstep=8)
    try:
        mon.enable()
        reqmod.log().clear()
        plan = faults.FaultPlan(seed=17).fail_at(
            faults.GENERATION_SUPERSTEP, 2)
        with plan:
            reqs, got, errs = _run_workload(srv)
        assert plan.fired.get(faults.GENERATION_SUPERSTEP) == 1
        assert errs == [None] * 4
        assert got == want, \
            "replayed streams must bit-match the fault-free run"
        assert srv.stats["replays"] >= 1

        replayed = 0
        for req, toks in zip(reqs, got):
            assert req.trace_id is not None
            tl = reqmod.log().get(req.trace_id)
            assert tl is not None and tl.status == req.finish_reason
            names = [e["event"] for e in tl.events]
            # every request: enqueue → admit → ≥1 block → retire (last)
            assert names[0] == "enqueue"
            assert "admit" in names and names[-1] == "retire"
            assert names.count("block") >= 1
            retire = next(e for e in tl.events
                          if e["event"] == "retire")
            assert retire["tokens"] == len(toks)
            if "replay" in names:
                replayed += 1
                i_replay = names.index("replay")
                # the replay is followed by a RE-admission and blocks
                # resume after it (a request killed before its first
                # delivered block legitimately has no block before)
                assert "admit" in names[i_replay:]
                i_readmit = i_replay + names[i_replay:].index("admit")
                assert "block" in names[i_readmit:]
        assert replayed >= 1, "the kill must replay at least one stream"

        # the acceptance surface: GET /requests/<id> serves the same
        # lifecycle, and the per-token p99 exemplars link into the run
        server = UIServer.getInstance()
        server.start(port=0)
        try:
            base = f"http://127.0.0.1:{server.port}"
            doc = json.loads(urllib.request.urlopen(
                base + f"/requests/{reqs[0].trace_id}",
                timeout=10).read().decode())
            served = [e["event"] for e in doc["events"]]
            assert served[0] == "enqueue" and served[-1] == "retire"
            listing = json.loads(urllib.request.urlopen(
                base + "/requests", timeout=10).read().decode())
            ids = {t.trace_id for t in reqs}
            assert listing["exemplars"].get(mon.GEN_PER_TOKEN_MS), \
                "per-token p99 exemplars must be served"
            # this run's trace ids sit in the exemplar window (earlier
            # tests in the module may own the top-valued slots)
            window = mon.get_registry().get(
                mon.GEN_PER_TOKEN_MS).exemplars(top=64)
            assert ids & {e["trace_id"] for e in window}
        finally:
            server.stop()
    finally:
        srv.shutdown()
        reqmod.log().clear()


def test_submit_rejection_status_not_mislabeled_as_shed(net):
    """A shut-down (or dead) server's submit refusal must land in the
    request ring as 'rejected', never as 'shed' — an operator reading
    /requests during an incident must be able to tell dead-server
    refusals from genuine overload shedding."""
    from deeplearning4j_tpu.monitoring import requests as reqmod
    srv = _server(net)
    try:
        mon.enable()
        reqmod.log().clear()
        srv.shutdown()
        with pytest.raises(RuntimeError):
            srv.submit(prompt=[1, 2], max_new_tokens=2)
        rec = reqmod.log().snapshot()["recent"][-1]
        assert rec["status"] == "rejected"
        assert rec["events"][-1]["event"] == "rejected"
        assert rec["events"][-1]["error"] == "RuntimeError"
    finally:
        srv.shutdown()
        reqmod.log().clear()


def test_supervised_restart_from_warm_store_zero_compiles(net):
    """ACCEPTANCE: a recovery failure (the replay admission itself
    faults) triggers a supervised restart that rebuilds from the warm
    FunctionStore — zero live compiles, streams still bit-identical.
    slots=1 serializes admission numbering, so admission 1 is the
    fresh request and admission 2 is deterministically THE replay."""
    workload = [dict(prompt=[1, 4, 2], max_new_tokens=16,
                     method="temperature", temperature=0.8)]
    baseline = _server(net, slots=1)
    try:
        _, want, _ = _run_workload(baseline, workload)
    finally:
        baseline.shutdown()

    srv = _server(net, slots=1)
    try:
        compiles0 = srv._store.stats["compiles"]
        traces0 = srv._store.trace_calls
        plan = (faults.FaultPlan(seed=1)
                .fail_at(faults.GENERATION_STEP, 2)
                .fail_at(faults.GENERATION_ADMIT, 2))
        with plan:
            _, got, errs = _run_workload(srv, workload)
        assert errs == [None]
        assert got == want
        assert srv.stats["restarts"] >= 1
        assert srv.stats["replays"] >= 1
        assert srv._store.stats["compiles"] == compiles0, \
            "supervised restart must not compile anything"
        assert srv._store.trace_calls == traces0
    finally:
        srv.shutdown()


# ===================== death: typed, prompt, bounded ==================
def test_restart_budget_exhaustion_latches_typed_dead(net):
    """Every admission faults: recovery can never succeed, so the
    bounded RetryPolicy exhausts and the server latches the typed
    ServerDeadError — in-flight requests fail typed, submit refuses,
    `GET /health` reports serving_dead."""
    srv = _server(net, slots=2, restart_policy=RetryPolicy(
        max_attempts=2, initial_backoff=0.005, max_backoff=0.01))
    try:
        plan = faults.FaultPlan(seed=2).every(faults.GENERATION_ADMIT, 1)
        with plan:
            req = srv.submit([1, 2, 3], max_new_tokens=4)
            with pytest.raises(ServerDeadError):
                req.result(timeout=30)
        assert srv.stats["restarts"] >= 1
        with pytest.raises(ServerDeadError):
            srv.submit([1, 2], max_new_tokens=4)
        assert srv.serving_state()["state"] == "dead"
        from deeplearning4j_tpu.resilience import health_snapshot
        snap = health_snapshot()
        assert snap["status"] == "serving_dead"
        assert any(s["state"] == "dead" for s in snap["serving"])
    finally:
        srv.shutdown()
    # deliberate shutdown demotes the dead report: /health stops paging
    assert srv.serving_state()["state"] == "shutdown"


def test_dead_server_unblocks_stream_consumers_promptly(net):
    """Satellite: the dead transition must push the terminal error
    sentinel to every OPEN stream iterator immediately — a blocked
    consumer thread raises typed well before its own timeout."""
    # a short prompt-bucket ladder forces the re-generation replay path
    # (no prefill progress), so an every-step fault makes zero forward
    # progress and trips the no-progress guard
    srv = _server(net, slots=1, prompt_buckets=[4], cache_lengths=[16],
                  max_consecutive_failures=3,
                  restart_policy=RetryPolicy(max_attempts=2,
                                             initial_backoff=0.005))
    state = {}

    def consume(req):
        t0 = time.monotonic()
        try:
            for _ in req.stream(timeout=120):
                pass
        except Exception as e:  # noqa: BLE001 — asserted below
            state["err"] = e
        state["elapsed"] = time.monotonic() - t0

    try:
        plan = faults.FaultPlan(seed=3).every(faults.GENERATION_STEP, 1)
        with plan:
            req = srv.submit([1, 2, 3], max_new_tokens=8)
            t = threading.Thread(target=consume, args=(req,))
            t.start()
            t.join(timeout=60)
            assert not t.is_alive(), "consumer never unblocked"
        assert isinstance(state["err"], ServerDeadError)
        assert state["elapsed"] < 30, \
            "consumer must raise promptly, not wait out its timeout"
        assert req.finish_reason == "error"
    finally:
        srv.shutdown()


# ===================== memory-pressure degradation ladder =============
def _oom(site, call_n):
    return RuntimeError(
        f"RESOURCE_EXHAUSTED: out of memory (injected at {site} "
        f"call {call_n})")


@pytest.mark.slow   # suite diet (ISSUE 18): ~11 s — level 1 alone is a
# strict sub-walk of test_pressure_ladder_sheds_queue_then_shrinks
# (refuse-growth cap, typed failure, fitting requests still serve);
# the CACHE_GROW site + "degraded" serving_state stay tier-1 via
# test_pressure_decays_while_idle and
# test_pressure_decays_by_wall_clock_without_steps
def test_pressure_level1_refuses_growth_keeps_serving(bert):
    """An OOM during cache growth escalates to level 1: the grown-past
    request fails typed, in-flight requests replay at the capped rung,
    and fresh requests that fit keep serving."""
    baseline = _bert_server(bert)
    try:
        want = baseline.generate([1, 4, 2], max_new_tokens=8,
                                 timeout=60)          # fits rung 16
    finally:
        baseline.shutdown()

    srv = _bert_server(bert)
    try:
        plan = faults.FaultPlan(seed=4).fail_at(faults.CACHE_GROW, 1,
                                                exc=_oom)
        with plan:
            a = srv.submit([1, 4, 2], max_new_tokens=8)      # fits 16
            b = srv.submit([5, 6, 7, 8, 9, 10, 11],
                           max_new_tokens=20)                # needs 32
            assert a.result(timeout=60) == want
            with pytest.raises(MemoryPressureError):
                b.result(timeout=60)
        assert srv._pressure == 1
        assert srv._rung_cap == 16
        assert srv.stats["degradations"] >= 1
        assert srv.serving_state()["state"] == "degraded"
        # growth is now refused pre-dispatch: fails typed, no crash
        errors0 = srv.stats["errors"]
        with pytest.raises(MemoryPressureError):
            srv.generate([5, 6, 7, 8, 9, 10, 11], max_new_tokens=20,
                         timeout=60)
        assert srv.stats["errors"] == errors0
        # requests inside the cap still serve
        assert srv.generate([1, 4, 2], max_new_tokens=8,
                            timeout=60) == want
    finally:
        srv.shutdown()


def test_pressure_ladder_sheds_queue_then_shrinks(bert):
    """Repeated OOM incidents walk the whole ladder: level 2 sheds the
    queued admissions typed; level 3 shrinks the cap one pre-compiled
    rung — the in-flight request that no longer fits fails typed, and
    a fitting request still serves at the shrunken rung. slots=1
    serializes everything, so the step numbering is deterministic."""
    srv = _bert_server(bert, slots=1)
    try:
        plan = (faults.FaultPlan(seed=6)
                .fail_at(faults.GENERATION_STEP, 2, exc=_oom)
                .fail_at(faults.GENERATION_STEP, 4, exc=_oom)
                .fail_at(faults.GENERATION_STEP, 6, exc=_oom))
        with plan:
            # big occupies THE slot (grown to rung 32); the others
            # queue behind it and are still queued at every incident
            big = srv.submit([5, 6, 7, 8, 9, 10, 11],
                             max_new_tokens=20)              # needs 32
            q1 = srv.submit([1, 2], max_new_tokens=4)
            q2 = srv.submit([3, 4], max_new_tokens=4)
            # OOM 1 -> refuse growth (cap 32); OOM 2 -> shed the queue;
            # OOM 3 -> shrink the cap to 16: big no longer fits
            with pytest.raises(MemoryPressureError):
                big.result(timeout=60)
            with pytest.raises(MemoryPressureError):
                q1.result(timeout=60)
            with pytest.raises(MemoryPressureError):
                q2.result(timeout=60)
        assert srv._pressure == 3
        assert srv._rung_cap == 16          # shrunk below the 32 rung
        assert srv.stats["degradations"] >= 3
        # the server still serves requests that fit the shrunken rung
        assert len(srv.generate([1, 2], max_new_tokens=4,
                                timeout=60)) == 4
        assert srv._rung == 16
    finally:
        srv.shutdown()


def test_pressure_decays_after_clean_stretch(bert):
    # the relief window must outlast the FIRST request's post-fault
    # steps (~5) and land inside the second request's (~7 more)
    srv = _bert_server(bert, slots=1, pressure_relief_steps=10)
    try:
        plan = faults.FaultPlan(seed=7).fail_at(faults.GENERATION_STEP,
                                                2, exc=_oom)
        with plan:
            srv.generate([1, 2], max_new_tokens=8, timeout=60)
        assert srv._pressure == 1
        # a clean stretch of decode steps relieves the pressure and
        # lifts the growth cap
        srv.generate([1, 2], max_new_tokens=8, timeout=60)
        assert srv._pressure == 0
        assert srv._rung_cap is None
        assert srv.generate([5, 6, 7, 8, 9, 10, 11], max_new_tokens=20,
                            timeout=60)   # growth works again
        assert srv._rung == 32
    finally:
        srv.shutdown()


# ===================== paged KV pool chaos ============================
@pytest.mark.slow   # suite diet (ISSUE 19): ~20 s — a second full
# dense-vs-paged superstep compile set just to cross replay × paging;
# fast-lane twins: replay bit-identity via
# test_chaos_decode_kill_streams_bit_identical, paged pool recovery
# under chaos via test_chaos_paged_ladder_evicts_cold_pages_before_shrink,
# and paged-read bit-identity via
# test_paged.py::test_paged_streams_bit_identical_mixed_sampling
def test_chaos_page_fault_replay_bit_identical(bert):
    """ACCEPTANCE (paged): a `cache.page` fault (corrupt page index /
    failed pool bookkeeping) mid-stream crashes the loop; recovery
    resets the pool, rebuilds the page table + prefix registry from the
    journal, and every completed stream is BIT-identical to the
    fault-free SLOT-CONTIGUOUS run — superstep k=2 so the kill lands
    inside a multi-token block."""
    dense = _bert_server(bert, superstep=2)
    try:
        _, want, errs = _run_workload(dense)
        assert errs == [None] * 4
    finally:
        dense.shutdown()

    srv = _bert_paged_server(bert, superstep=2)
    try:
        # call 6 is past the first admissions' fires: it lands on a
        # steady-state block's page walk, pool already populated
        plan = faults.FaultPlan(seed=9).fail_at(faults.CACHE_PAGE, 6)
        with plan:
            _, got, errs = _run_workload(srv)
        assert plan.fired.get(faults.CACHE_PAGE) == 1
        assert errs == [None] * 4
        assert got == want, \
            "paged replay must bit-match the dense fault-free run"
        assert srv.stats["replays"] >= 1
        # the rebuilt pool is consistent: a fresh request serves
        assert len(srv.generate([3, 1], max_new_tokens=3,
                                timeout=60)) == 3
    finally:
        srv.shutdown()


def _pool_oom(site, call_n):
    return PagePoolExhaustedError(
        f"kv page pool exhausted (injected at {site} call {call_n})")


def test_chaos_paged_ladder_evicts_cold_pages_before_shrink(bert):
    """The paged ladder has FOUR rungs: repeated pool-exhaustion OOMs
    walk refuse-growth → shed-queue → EVICT-COLD-PAGES → shrink. The
    third incident reclaims resident refcount-zero prefix pages and
    leaves rung capacity untouched; only the fourth gives up the rung.
    slots=1 serializes everything, so step numbering is deterministic."""
    srv = _bert_paged_server(bert, slots=1)
    try:
        mon.enable()
        count = lambda a: mon.get_registry().counter(  # noqa: E731
            mon.GEN_DEGRADATIONS, labels={"action": a}).value
        # (the registry is the process's: another file's degradations on
        # this worker stay in it, so count from here)
        base = {a: count(a) for a in ("refuse_growth", "shed_queue",
                                      "evict_pages", "shrink")}
        deg = lambda a: count(a) - base[a]  # noqa: E731
        # incidents 1+2 hit a request that grew (relabeled) to rung 32;
        # it replays through both and completes
        plan = (faults.FaultPlan(seed=8)
                .fail_at(faults.GENERATION_STEP, 2, exc=_pool_oom)
                .fail_at(faults.GENERATION_STEP, 4, exc=_pool_oom))
        with plan:
            big = srv.submit([5, 6, 7, 8, 9, 10, 11],
                             max_new_tokens=20)              # needs 32
            assert len(big.result(timeout=60)) == 20
        assert srv._pressure == 2
        assert srv._rung_cap == 32          # capped, nothing shrunk
        assert deg("refuse_growth") == 1 and deg("shed_queue") == 1
        # the retired request left its prompt pages resident COLD —
        # exactly the headroom level 3 reclaims
        assert srv.serving_state()["page_pool"]["pages_cold"] > 0
        ev0 = srv._pages.stats["evictions"]

        # incident 3: evict_pages — pool headroom, NOT rung capacity
        plan = faults.FaultPlan(seed=9).fail_at(
            faults.GENERATION_STEP, 1, exc=_pool_oom)
        with plan:
            assert len(srv.generate([1, 2], max_new_tokens=4,
                                    timeout=60)) == 4
        assert srv._pressure == 3
        assert srv._rung_cap == 32          # still no shrink
        assert deg("evict_pages") == 1 and deg("shrink") == 0
        assert srv._pages.stats["evictions"] > ev0

        # incident 4: out of pool moves — NOW the cap shrinks to 16
        plan = faults.FaultPlan(seed=10).fail_at(
            faults.GENERATION_STEP, 1, exc=_pool_oom)
        with plan:
            big2 = srv.submit([5, 6, 7, 8, 9, 10, 11],
                              max_new_tokens=20)
            with pytest.raises(MemoryPressureError):
                big2.result(timeout=60)
        assert srv._pressure == 4
        assert srv._rung_cap == 16
        assert deg("shrink") == 1
        # the server still serves requests that fit the shrunken rung
        assert len(srv.generate([1, 2], max_new_tokens=4,
                                timeout=60)) == 4
    finally:
        srv.shutdown()


def test_crash_during_retirement_never_overshoots_the_stream(net):
    """If the crash lands AFTER a request's terminal token was
    delivered but BEFORE its retirement completed, recovery must
    finish the request — replaying it would generate past EOS /
    max_new_tokens and fork the delivered stream."""
    srv = _server(net, slots=1)
    try:
        want = srv.generate([1, 4, 2], max_new_tokens=4, timeout=60)
        orig = srv._exes[("retire",)]
        fired = []

        def flaky_retire(*a):
            if not fired:
                fired.append(True)
                raise RuntimeError("injected retire crash")
            return orig(*a)

        srv._exes[("retire",)] = flaky_retire
        r = srv.submit([1, 4, 2], max_new_tokens=4)
        assert r.result(timeout=60) == want
        assert len(r.tokens) == 4               # never a 5th token
        assert r.finish_reason == "length"
        assert srv.stats["errors"] == 1
        # and the server serves on
        assert srv.generate([1, 4, 2], max_new_tokens=4,
                            timeout=60) == want
    finally:
        srv.shutdown()


def test_pressure_decays_while_idle(bert):
    """A transient OOM on a server that then goes IDLE (no steps, no
    growth attempts) must still decay: the decode loop's idle tick
    drives the wall-clock relief, so /health stops reporting degraded."""
    srv = _bert_server(bert, slots=1, pressure_relief_secs=0.05)
    try:
        with faults.FaultPlan(seed=8).fail_at(faults.CACHE_GROW, 1,
                                              exc=_oom):
            with pytest.raises(MemoryPressureError):
                srv.generate([5, 6, 7, 8, 9, 10, 11],
                             max_new_tokens=20, timeout=60)
        assert srv._pressure == 1
        deadline = time.monotonic() + 10
        while srv.serving_state()["state"] != "serving":
            assert time.monotonic() < deadline, \
                "idle server never relieved its pressure"
            time.sleep(0.02)
        assert srv._pressure == 0
    finally:
        srv.shutdown()


def test_pressure_decays_by_wall_clock_without_steps(bert):
    """A transient OOM must not degrade the replica forever when the
    remaining traffic is all refused (no decode steps ever run, so
    step-count relief alone would never fire): elapsed quiet time
    relieves the pressure on the next growth attempt."""
    srv = _bert_server(bert, slots=1, pressure_relief_secs=0.05)
    try:
        with faults.FaultPlan(seed=8).fail_at(faults.CACHE_GROW, 1,
                                              exc=_oom):
            with pytest.raises(MemoryPressureError):
                srv.generate([5, 6, 7, 8, 9, 10, 11],
                             max_new_tokens=20, timeout=60)
        assert srv._pressure == 1
        time.sleep(0.1)
        # no steps ran since the OOM — the growth attempt itself
        # relieves the decayed pressure and succeeds
        assert len(srv.generate([5, 6, 7, 8, 9, 10, 11],
                                max_new_tokens=20, timeout=60)) == 20
        assert srv._pressure == 0
        assert srv._rung == 32
    finally:
        srv.shutdown()


def test_memory_telemetry_high_water_refuses_growth(bert, monkeypatch):
    """The ladder is driven by monitoring/memory.py telemetry too: a
    device already past the high-water mark refuses growth proactively
    (typed, pre-dispatch) without waiting for the OOM."""
    from deeplearning4j_tpu.monitoring import memory as memmod
    srv = _bert_server(bert, slots=1, memory_high_water=0.9)
    try:
        srv.generate([1, 2], max_new_tokens=4, timeout=60)  # rung 16
        monkeypatch.setattr(
            memmod, "device_memory_stats",
            lambda: {"dev0": {"bytes_in_use": 95, "bytes_limit": 100}})
        with pytest.raises(MemoryPressureError, match="high-water"):
            srv.generate([5, 6, 7, 8, 9, 10, 11], max_new_tokens=20,
                         timeout=60)
        assert srv.stats["errors"] == 0     # refusal, not a crash
        # a telemetry-refusing replica is observably degraded, not ok
        assert srv.serving_state()["state"] == "degraded"
        monkeypatch.setattr(
            memmod, "device_memory_stats",
            lambda: {"dev0": {"bytes_in_use": 10, "bytes_limit": 100}})
        assert srv.generate([5, 6, 7, 8, 9, 10, 11], max_new_tokens=20,
                            timeout=60)
    finally:
        srv.shutdown()


# ===================== ParallelInference AOT breaker ==================
def test_aot_fallback_breaker_reprobes_and_recovers(dense_net):
    """Satellite regression: one `dl4j.serving.aot_fallbacks` event
    opens the breaker (legacy serving during cooldown) — it must NOT
    disable AOT for the instance's lifetime: after cooldown the
    half-open probe restores the zero-trace steady state."""
    clock = {"t": 0.0}
    breaker = CircuitBreaker(failure_threshold=1, cooldown=5.0,
                             clock=lambda: clock["t"],
                             name="inference.aot")
    pi = (ParallelInference.Builder(dense_net)
          .inferenceMode(InferenceMode.BATCHED)
          .bucketLadder([1, 2, 4]).aotBreaker(breaker).build())
    try:
        pi.warmup()
        mon.enable()
        fb0 = mon.get_registry().counter(mon.SERVING_AOT_FALLBACKS).value
        rng = np.random.default_rng(8)
        x = rng.standard_normal((2, 5)).astype(np.float32)
        want = dense_net.output(x).numpy()
        plan = faults.FaultPlan(seed=0).fail_at(faults.SERVING_DISPATCH,
                                                1)
        with plan:
            got = pi.output(x)      # AOT faults -> served legacy
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
        assert breaker.state == CircuitBreaker.OPEN
        assert pi._ladder is not None       # NOT permanently reverted
        assert pi._aot_error is not None
        assert mon.get_registry().counter(
            mon.SERVING_AOT_FALLBACKS).value - fb0 == 1
        # during cooldown: legacy serving, still correct, no AOT tries
        np.testing.assert_allclose(pi.output(x), want, atol=1e-5,
                                   rtol=1e-5)
        # past cooldown the half-open probe re-takes the AOT path and
        # closes the breaker: zero-trace steady state again
        clock["t"] = 6.0
        traces = pi._store.trace_calls
        compiles = pi._store.stats["compiles"]
        for _ in range(3):
            np.testing.assert_allclose(pi.output(x), want, atol=1e-5,
                                       rtol=1e-5)
        # record_success lands just after result delivery on the
        # collector thread: give it a beat before asserting
        for _ in range(200):
            if breaker.state == CircuitBreaker.CLOSED:
                break
            time.sleep(0.01)
        assert breaker.state == CircuitBreaker.CLOSED
        assert pi._store.trace_calls == traces
        assert pi._store.stats["compiles"] == compiles
        assert mon.get_registry().counter(
            mon.SERVING_AOT_FALLBACKS).value - fb0 == 1   # no re-trips
    finally:
        pi.shutdown()


def test_inference_forward_fault_fails_typed_and_recovers(dense_net):
    """`inference.forward` chaos: the faulted request fails typed, the
    collector survives, and the next request serves normally."""
    pi = (ParallelInference.Builder(dense_net)
          .inferenceMode(InferenceMode.BATCHED).build())
    try:
        x = np.zeros((2, 5), np.float32)
        plan = faults.FaultPlan(seed=0).fail_at(
            faults.INFERENCE_FORWARD, 1)
        with plan:
            with pytest.raises(InjectedFault):
                pi.output(x, timeout_ms=10000)
        out = pi.output(x, timeout_ms=10000)
        assert out.shape == (2, 3)
    finally:
        pi.shutdown()


# ===================== executable-store load faults ===================
def test_executables_load_fault_hits_miss_path_only(dense_net):
    """`executables.load` chaos: a fault on the store miss path
    surfaces typed (warmup-time problem), clears with the plan, and the
    warmed in-memory tier never revisits the site."""
    from deeplearning4j_tpu.runtime.executables import ExecutableStore
    store = ExecutableStore(dense_net, directory=None)
    sig = (((2, 5), "float32"),)
    with faults.FaultPlan(seed=0).fail_at(faults.EXECUTABLES_LOAD, 1):
        with pytest.raises(InjectedFault):
            store.load_or_compile(sig)
    entry = store.load_or_compile(sig)
    assert entry is not None
    # steady state (memory tier) never reaches the fault site
    with faults.FaultPlan(seed=0).every(faults.EXECUTABLES_LOAD, 1):
        assert store.lookup(sig) is entry
        assert store.load_or_compile(sig) is entry


# ===================== coordination-layer sites =======================
def test_comm_barrier_fault_breaks_fence_typed():
    from deeplearning4j_tpu.parallel.coordination import (LocalKV,
                                                          PeerCoordinator)
    c = PeerCoordinator(client=LocalKV(), process_id=0, num_processes=1,
                        sync_every=1, peer_timeout=1.0)
    with faults.FaultPlan(seed=0).fail_at(faults.COMM_BARRIER, 1):
        with pytest.raises(InjectedFault):
            c.barrier("fence", timeout=0.5)
    c.barrier("fence2", timeout=5.0)    # single-process: passes clean


def test_comm_allreduce_fault_fires_before_dispatch():
    from deeplearning4j_tpu.parallel.multihost import MultiHostTrainer
    t = MultiHostTrainer.__new__(MultiHostTrainer)   # hook-level probe
    t.compress = True
    t._explicit = True     # the explicit-exchange flag the hook checks
    with faults.FaultPlan(seed=0).fail_at(faults.COMM_ALLREDUCE, 1):
        with pytest.raises(InjectedFault):
            t.fit_batch(None, None, None, None)
