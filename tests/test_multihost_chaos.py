"""Preemption-tolerant multi-host training: chaos + containment tests.

Three tiers:
- fast single-process tests of the coordination plane (two
  `PeerCoordinator`s sharing one `LocalKV`, driven from two threads —
  every agreement/containment path without subprocess spawn cost);
- single-process-backend runner tests over the 8 virtual devices
  (preemption drain + bit-identical resume, coordinated rollback);
- REAL two-process chaos (subprocess workers over jax.distributed +
  gloo): the headline `host.preempt`-injected drain with bit-identical
  resume, the killed-peer `PeerLostError` containment, and (slow) a
  real `kill -TERM` mid-run.
"""
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.parallel import coordination as coord_mod
from deeplearning4j_tpu.parallel.coordination import (LocalKV,
                                                      PeerCoordinator)
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.resilience.errors import (DistributedInitError,
                                                  PeerDesyncError,
                                                  PeerLostError,
                                                  PreemptionSignal)

_WORKER = os.path.join(os.path.dirname(__file__),
                       "multihost_chaos_worker.py")


def _free_port():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture(autouse=True)
def _clean_switches():
    yield
    coord_mod.clear_coordinator()
    faults.clear_plan()
    faults.PROCESS_ID = None
    from deeplearning4j_tpu.resilience import guardian as _g
    _g.clear_guardian()


# ===================== LocalKV / coordination plane =====================
def test_localkv_kv_and_barrier_semantics():
    kv = LocalKV()
    kv.key_value_set("a/b", "1")
    with pytest.raises(RuntimeError):
        kv.key_value_set("a/b", "2")            # write-once by default
    kv.key_value_set("a/b", "2", allow_overwrite=True)
    assert kv.blocking_key_value_get("a/b", 100) == "2"
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        kv.blocking_key_value_get("missing", 150)
    assert 0.1 < time.monotonic() - t0 < 2.0
    assert kv.key_value_dir_get("a/") == [("a/b", "2")]
    # barrier: second arrival releases both
    done = []

    def arrive():
        kv.wait_at_barrier("bar", 2000, expected=2)
        done.append(1)

    t = threading.Thread(target=arrive)
    t.start()
    kv.wait_at_barrier("bar", 2000, expected=2)
    t.join(timeout=2)
    assert len(done) == 1
    with pytest.raises(TimeoutError):
        kv.wait_at_barrier("bar2", 100, expected=2)


def _pair(tmp_path, sync_every=2, peer_timeout=2.0):
    kv = LocalKV()
    return [PeerCoordinator(sync_every=sync_every,
                            peer_timeout=peer_timeout,
                            client=kv, process_id=i, num_processes=2,
                            dump_dir=str(tmp_path)) for i in (0, 1)]


def test_preemption_agreement_two_coordinators(tmp_path):
    """Worker 1 requests preemption mid-window; BOTH coordinators reach
    the drain decision at the SAME sync round/step."""
    c0, c1 = _pair(tmp_path)
    c0.driver_attached = c1.driver_attached = True
    decisions = {}

    def run(c, preempt_at):
        for step in range(6):
            if step == preempt_at:
                c.request_preemption("test")
            c.on_step()
            d = c.take_decision()
            if d is not None:
                decisions[c.process_id] = (d, c.step)
                return

    t0 = threading.Thread(target=run, args=(c0, None))
    t1 = threading.Thread(target=run, args=(c1, 1))
    t0.start(); t1.start()
    t0.join(timeout=10); t1.join(timeout=10)
    # flag raised before step 2's sync → both agree at step 2
    assert decisions == {0: ("preempt", 2), 1: ("preempt", 2)}
    assert c0.preempted and c1.preempted


def test_undriven_preemption_raises_signal(tmp_path):
    """Without a driving runner nothing could consume the decision —
    the sync point unwinds the loop directly."""
    c0, c1 = _pair(tmp_path)
    errs = {}

    def run(c):
        c.request_preemption("test")
        try:
            c.on_step(); c.on_step()
        except PreemptionSignal as e:
            errs[c.process_id] = e

    ts = [threading.Thread(target=run, args=(c,)) for c in (c0, c1)]
    [t.start() for t in ts]
    [t.join(timeout=10) for t in ts]
    assert set(errs) == {0, 1}
    assert errs[0].step == errs[1].step == 2


def test_peer_lost_is_bounded_and_dumps(tmp_path):
    """A peer that never reaches the sync point surfaces as
    PeerLostError within ~peer_timeout, with a forensics report
    containing the peer table — never an indefinite hang."""
    c0, _ = _pair(tmp_path, peer_timeout=1.0)
    t0 = time.monotonic()
    with pytest.raises(PeerLostError) as ei:
        c0.on_step(); c0.on_step()       # sync at step 2; peer silent
    elapsed = time.monotonic() - t0
    assert elapsed < 10.0                 # bounded (timeout 1 s + slack)
    assert ei.value.report_path and os.path.exists(ei.value.report_path)
    text = open(ei.value.report_path).read()
    assert "Peer table" in text and "PEER LOST" in text


def test_step_desync_detected(tmp_path):
    """A peer on a different step number is a PeerDesyncError — the
    lockstep contract is broken, continuing would corrupt the model."""
    c0, _ = _pair(tmp_path)
    # forge worker 1's round-0 heartbeat with a diverged step count
    c0._client.key_value_set(
        "dl4j/hb/0/1", json.dumps({"step": 99, "t": time.time(),
                                   "preempt": False}))
    with pytest.raises(PeerDesyncError):
        c0.on_step(); c0.on_step()


def test_monitor_detects_silent_peer(tmp_path):
    """The monitor thread declares a peer lost when its liveness key
    goes stale; the next on_step raises instead of entering another
    collective."""
    c0, c1 = _pair(tmp_path, peer_timeout=0.5)
    m0 = c0.start_monitor(poll_interval=0.1)
    m1 = c1.start_monitor(poll_interval=0.1)
    time.sleep(0.3)                       # both alive: no trip
    assert not c0._lost
    c1.stop_monitor()                     # peer 1 goes silent
    deadline = time.monotonic() + 5
    while not c0._lost and time.monotonic() < deadline:
        time.sleep(0.05)
    assert 1 in c0._lost
    with pytest.raises(PeerLostError):
        c0.on_step()
    c0.stop_monitor()
    assert m0 is not None and m1 is not None


def test_barrier_timeout_is_peer_lost(tmp_path):
    c0, _ = _pair(tmp_path)
    with pytest.raises(PeerLostError):
        c0.barrier("fence", timeout=0.2)


def test_bound_coordinator_ignores_auxiliary_trainers(tmp_path):
    """A coordinator bound to the runner's trainer must not count a
    host-local auxiliary fit's steps — that would desync the lockstep
    step-agreement check across hosts."""
    c0, _ = _pair(tmp_path, sync_every=100)
    main, aux = object(), object()
    c0.bind(main)
    c0.on_step(aux)
    c0.on_step()          # while bound, source-less is ignored too —
    #                       ANY extra count desyncs cross-host agreement
    assert c0.step == 0
    c0.on_step(main)
    assert c0.step == 1
    c0.bind(None)
    c0.on_step(aux)
    c0.on_step()
    assert c0.step == 3                   # unbound: everything counts


# ===================== process-aware fault seeds ========================
def test_faultplan_seed_is_process_aware():
    """Same plan seed, different process id → a DIFFERENT (but
    per-worker deterministic) probability schedule; process 0 keeps the
    legacy schedule (seed ^ 0 == seed)."""
    def schedule(seed, pid):
        plan = faults.FaultPlan(seed=seed, process_id=pid)
        plan.probability("site", 0.3)
        fired = []
        for i in range(40):
            try:
                plan.fire("site")
                fired.append(0)
            except Exception:  # noqa: BLE001
                fired.append(1)
        return fired

    s0a, s0b = schedule(7, 0), schedule(7, 0)
    s1a, s1b = schedule(7, 1), schedule(7, 1)
    assert s0a == s0b and s1a == s1b      # deterministic per worker
    assert s0a != s1a                      # but unique across workers
    # deterministic rules are count-based and unaffected by the seed
    p = faults.FaultPlan(seed=7, process_id=3).fail_at("s", 2)
    p.fire("s")
    with pytest.raises(Exception):
        p.fire("s")


def test_faultplan_process_id_resolution(monkeypatch):
    monkeypatch.setenv("DL4J_PROCESS_ID", "5")
    assert faults.resolve_process_id() == 5
    faults.PROCESS_ID = 2                 # bootstrap registration wins
    assert faults.resolve_process_id() == 2
    assert faults.resolve_process_id(9) == 9
    faults.PROCESS_ID = None
    monkeypatch.delenv("DL4J_PROCESS_ID")
    assert faults.resolve_process_id() == 0


# ===================== hardened bootstrap ===============================
def test_bootstrap_noop_without_coordinator(monkeypatch):
    from deeplearning4j_tpu.parallel import multihost
    for k in ("DL4J_COORDINATOR", "JAX_COORDINATOR_ADDRESS"):
        monkeypatch.delenv(k, raising=False)
    assert multihost.initialize() is False


def test_bootstrap_retries_then_typed_error(monkeypatch):
    """A coordinator that never comes up is retried with backoff, then
    surfaces as DistributedInitError — typed, bounded, loud."""
    import jax

    from deeplearning4j_tpu.parallel import multihost
    from deeplearning4j_tpu.resilience.policy import RetryPolicy
    calls = []

    def fake_init(**kw):
        calls.append(kw)
        raise RuntimeError("UNAVAILABLE: failed to connect to all "
                           "addresses")

    monkeypatch.setattr(jax.distributed, "initialize", fake_init)
    monkeypatch.setattr(jax.distributed, "shutdown", lambda: None)
    policy = RetryPolicy(max_attempts=3, initial_backoff=0.01,
                         max_backoff=0.02, deadline=10)
    with pytest.raises(DistributedInitError) as ei:
        multihost.initialize("localhost:1", 2, 1, connect_deadline=10,
                             retry_policy=policy)
    assert len(calls) == 3                # retried to the budget
    assert "could not join" in str(ei.value)


def test_bootstrap_nonretryable_fails_fast(monkeypatch):
    import jax

    from deeplearning4j_tpu.parallel import multihost
    calls = []

    def fake_init(**kw):
        calls.append(kw)
        raise RuntimeError("INVALID_ARGUMENT: bad process id")

    monkeypatch.setattr(jax.distributed, "initialize", fake_init)
    monkeypatch.setattr(jax.distributed, "shutdown", lambda: None)
    with pytest.raises(DistributedInitError):
        multihost.initialize("localhost:1", 2, 1, connect_deadline=10)
    assert len(calls) == 1                # not classified transient


def test_bootstrap_env_config(monkeypatch):
    """DL4J_* env vars drive the config; a successful init registers
    the process id with the fault harness."""
    import jax

    from deeplearning4j_tpu.parallel import multihost

    class FakeClient:
        def wait_at_barrier(self, *a, **k):
            pass

        def key_value_set(self, *a, **k):
            pass

        def blocking_key_value_get(self, key, t):
            return str(jax.local_device_count())

    seen = {}

    def fake_init(**kw):
        seen.update(kw)

    monkeypatch.setenv("DL4J_COORDINATOR", "localhost:12345")
    monkeypatch.setenv("DL4J_NUM_PROCESSES", "1")
    monkeypatch.setenv("DL4J_PROCESS_ID", "0")
    monkeypatch.setattr(jax.distributed, "initialize", fake_init)
    monkeypatch.setattr(coord_mod, "_distributed_client",
                        lambda: seen and FakeClient() or None)
    # no REAL distributed client exists in this process: enabling gloo
    # here would poison later backend creation
    monkeypatch.setattr(multihost, "_enable_cpu_collectives",
                        lambda: False)
    try:
        assert multihost.initialize() is True
        assert seen["coordinator_address"] == "localhost:12345"
        assert seen["num_processes"] == 1
        assert faults.PROCESS_ID == 0
    finally:
        faults.PROCESS_ID = None


# ===================== coordinated guardian =============================
def test_coordinated_guardian_folds_verdicts(tmp_path):
    """Each host publishes its flush window; both fold to the SAME
    (AND of ok, max of gnorm) — so a NaN on ONE host skips the update
    on EVERY host and both climb the same ladder rung."""
    from deeplearning4j_tpu.parallel.multihost import CoordinatedGuardian
    c0, c1 = _pair(tmp_path, sync_every=2, peer_timeout=5.0)
    g0 = CoordinatedGuardian(c0, check_every=2, warmup_steps=100)
    g1 = CoordinatedGuardian(c1, check_every=2, warmup_steps=100)
    results = {}

    def run(g, gnorms_oks):
        for gn, ok in gnorms_oks:
            g.on_step(None, np.float32(gn), np.asarray(ok))
        results[g.coordinator.process_id] = (g.skipped, g._bad_streak)

    # host 0 saw healthy steps; host 1's step 2 was NaN
    t0 = threading.Thread(target=run,
                          args=(g0, [(1.0, True), (1.0, True)]))
    t1 = threading.Thread(target=run,
                          args=(g1, [(1.0, True), (float("nan"), False)]))
    t0.start(); t1.start()
    t0.join(timeout=10); t1.join(timeout=10)
    # both guardians agree: one skipped update, one live bad streak
    assert results[0] == results[1] == (1, 1)


def test_coordinated_guardian_desync_window(tmp_path):
    from deeplearning4j_tpu.parallel.multihost import CoordinatedGuardian
    c0, c1 = _pair(tmp_path, sync_every=2, peer_timeout=2.0)
    g0 = CoordinatedGuardian(c0, check_every=2, warmup_steps=100)
    errs = {}
    # peer publishes a WRONG-LENGTH window for flush 0
    c0._client.key_value_set(
        "dl4j/gv/0/1", json.dumps({"g": [1.0], "ok": [True]}))

    def run():
        try:
            g0.on_step(None, np.float32(1.0), np.asarray(True))
            g0.on_step(None, np.float32(1.0), np.asarray(True))
        except PeerDesyncError as e:
            errs["e"] = e

    t = threading.Thread(target=run)
    t.start(); t.join(timeout=10)
    assert "e" in errs
    assert c1 is not None


# ===================== health / metrics surface =========================
def test_health_snapshot_has_peer_table(tmp_path):
    from deeplearning4j_tpu import resilience
    c0, c1 = _pair(tmp_path, sync_every=1, peer_timeout=5.0)
    c0.install()
    try:
        done = threading.Event()

        def peer():
            c1.on_step()
            done.set()

        t = threading.Thread(target=peer)
        t.start()
        c0.on_step()
        done.wait(timeout=5)
        snap = resilience.health_snapshot()
        dist = snap["distributed"]
        assert dist["process_id"] == 0 and dist["num_processes"] == 2
        assert set(dist["peers"]) == {"0", "1"}
        assert snap["status"] == "ok"
        c0.request_preemption("test")
        assert resilience.health_snapshot()["status"] == "degraded"
    finally:
        c0.uninstall()


# ===================== single-process runner ============================
TOTAL, SYNC, SAVE = 12, 2, 4


def _make_runner(tmp_path, ckpt_name, preempt_at=None, guardian=False,
                 compress=True, accum=1, buckets=None):
    import jax

    from deeplearning4j_tpu.nn.updaters import Sgd
    from deeplearning4j_tpu.parallel.multihost import (CoordinatedGuardian,
                                                       MultiHostRunner,
                                                       MultiHostTrainer)

    def loss_fn(params, batch, rng_key):
        import jax.numpy as jnp
        h = jnp.tanh(batch["x"] @ params["W1"])
        return jnp.mean(batch.get("scale", 1.0)) * jnp.mean(h * h)

    coordinator = PeerCoordinator(sync_every=SYNC, peer_timeout=5.0,
                                  client=LocalKV(), process_id=0,
                                  num_processes=1,
                                  dump_dir=str(tmp_path))
    trainer = MultiHostTrainer(loss_fn, Sgd(0.3), compress=compress,
                               accumulation=accum, buckets=buckets,
                               compression_kw={"initial_threshold": 1e-4})
    g = None
    if guardian:
        g = CoordinatedGuardian(coordinator, check_every=SYNC,
                                warmup_steps=100, max_skips=1,
                                max_lr_retries=0, max_rollbacks=2)
    runner = MultiHostRunner(trainer, str(tmp_path / ckpt_name),
                             coordinator, save_every=SAVE, guardian=g,
                             rng_seed=3, monitor=False, sigterm=False)
    if preempt_at is not None:
        plan = faults.FaultPlan(seed=0)
        plan.fail_at(faults.HOST_PREEMPT, preempt_at,
                     exc=lambda site, n: PreemptionSignal(f"inj@{n}"))
        plan.install()
    return runner


def _batch(trainer, step, nan=False):
    from deeplearning4j_tpu.parallel.multihost import global_batch
    r = np.random.default_rng(100 + step)
    g = trainer.accumulation
    if g > 1:
        # super-batch (G, B, ...): a NaN poisons ONE microbatch only —
        # the accumulated verdict must still catch it
        xs = r.standard_normal((g, 8, 6)).astype(np.float32)
        scale = np.ones((g, 8, 1), np.float32)
        if nan:
            scale[1] = np.nan
        return global_batch(trainer.mesh, {"x": xs, "scale": scale},
                            accumulation=g)
    xs = r.standard_normal((8, 6)).astype(np.float32)
    return global_batch(trainer.mesh,
                        {"x": xs,
                         "scale": np.full((8, 1),
                                          np.nan if nan else 1.0,
                                          np.float32)})


def _init_params():
    r = np.random.default_rng(0)
    return {"W1": (r.standard_normal((6, 5)) * 0.5).astype(np.float32)}


def _drive(runner, total=TOTAL, nan_steps=()):
    params, opt_state = runner.resume_or_init(_init_params())
    while runner.step < total:
        b = _batch(runner.trainer, runner.step,
                   nan=runner.step in nan_steps)
        params, opt_state, loss = runner.fit_batch(params, opt_state, b)
    return params, opt_state


def _digest(params):
    import hashlib
    h = hashlib.md5()
    for k in sorted(params):
        h.update(np.asarray(params[k]).tobytes())
    return h.hexdigest()


def test_runner_preemption_bit_identical_single_process(tmp_path):
    """host.preempt injected mid-run → coordinated drain + verified
    checkpoint + PreemptionSignal; a fresh runner resumes and the final
    params are BIT-identical to a never-preempted run."""
    # clean reference
    runner = _make_runner(tmp_path, "ck_clean")
    params, opt = _drive(runner)
    runner.finalize(params, opt)
    ref = _digest(params)

    # preempted run: fire at sync call 2 → coordinator step 4
    runner = _make_runner(tmp_path, "ck_pre", preempt_at=2)
    with pytest.raises(PreemptionSignal):
        _drive(runner)
    faults.clear_plan()
    drained_step = runner.step
    runner.close()
    assert 0 < drained_step < TOTAL

    # resume in a fresh runner (fresh coordinator, fresh jit caches)
    runner = _make_runner(tmp_path, "ck_pre")
    params2, opt2 = _drive(runner)
    assert runner.resumed_step == drained_step
    runner.finalize(params2, opt2)
    assert _digest(params2) == ref        # bit-identical


def test_runner_resume_restores_encoder_residual(tmp_path):
    """The per-bucket threshold-encoding residual rides the checkpoint:
    after a drain + resume the encoder state is restored bit-exactly
    (the property that makes the compressed trainer's resume exact).
    Buckets are keyed "0".."N-1" since the bucketed exchange (ISSUE
    14); the one-leaf model here planners into a single bucket."""
    runner = _make_runner(tmp_path, "ck_res", preempt_at=2)
    with pytest.raises(PreemptionSignal):
        _drive(runner)
    faults.clear_plan()
    runner.close()
    runner = _make_runner(tmp_path, "ck_res")
    params, opt_state = runner.resume_or_init(_init_params())
    res = opt_state["encoder"]["residual"]["0"]
    assert np.abs(np.asarray(res)).sum() > 0   # accumulated, restored
    runner.close()


def _tree_digest(tree):
    import hashlib

    import jax
    h = hashlib.md5()
    for leaf in jax.tree_util.tree_leaves(jax.device_get(tree)):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def test_runner_preemption_bit_identical_with_accumulation(tmp_path):
    """ISSUE 14 chaos acceptance: kill/resume mid-run with in-step
    accumulation (G=4) + bucketed encoded exchange stays bit-identical
    — params AND the per-bucket encoder state (residuals + adaptive
    thresholds) of the resumed run equal a never-preempted run's."""
    runner = _make_runner(tmp_path, "ck_acc_clean", accum=4, buckets=2)
    params, opt = _drive(runner)
    ref_p, ref_enc = _tree_digest(params), _tree_digest(opt["encoder"])
    runner.finalize(params, opt)

    runner = _make_runner(tmp_path, "ck_acc_pre", preempt_at=2, accum=4,
                          buckets=2)
    with pytest.raises(PreemptionSignal):
        _drive(runner)
    faults.clear_plan()
    drained_step = runner.step
    runner.close()
    assert 0 < drained_step < TOTAL

    runner = _make_runner(tmp_path, "ck_acc_pre", accum=4, buckets=2)
    params2, opt2 = _drive(runner)
    assert runner.resumed_step == drained_step
    assert _tree_digest(params2) == ref_p            # bit-identical
    assert _tree_digest(opt2["encoder"]) == ref_enc  # per-bucket state
    runner.finalize(params2, opt2)


def test_runner_rollback_with_nan_microbatch_under_accumulation(
        tmp_path):
    """Guardian × accumulation chaos: a NaN in one MICROBATCH of the
    super-batch fails the accumulated verdict (update refused on
    device), the window exhausts the skip rung, and the coordinated
    rollback lands on a verified generation — training ends finite."""
    runner = _make_runner(tmp_path, "ck_acc_roll", guardian=True,
                          accum=4, buckets=2)
    params, opt = _drive(runner, total=TOTAL, nan_steps=(5, 6, 7, 8))
    g = runner.guardian
    assert g.skipped >= 2                 # device refused the NaN steps
    assert g.rollbacks >= 1               # ladder reached rollback
    assert np.isfinite(np.asarray(params["W1"])).all()
    runner.finalize(params, opt)


def test_runner_rollback_lands_on_verified_generation(tmp_path):
    """A NaN window exhausts the skip rung → the guardian requests
    ROLLBACK → the runner restores the newest verified generation and
    training continues finite."""
    runner = _make_runner(tmp_path, "ck_roll", guardian=True)
    params, opt = _drive(runner, total=TOTAL,
                         nan_steps=(5, 6, 7, 8))
    g = runner.guardian
    assert g.skipped >= 2                 # device refused the NaN steps
    assert g.rollbacks >= 1               # ladder reached the rollback rung
    assert g.last_restored_step is not None
    assert np.isfinite(np.asarray(params["W1"])).all()
    runner.finalize(params, opt)


def test_compressed_trainer_trains_and_reports_stats(tmp_path):
    """The compressed dp-over-DCN step optimizes, and the wire
    telemetry (nnz / threshold / residual) materializes at sync
    cadence."""
    runner = _make_runner(tmp_path, "ck_stats")
    params, opt_state = runner.resume_or_init(_init_params())
    losses = []
    while runner.step < 8:
        b = _batch(runner.trainer, 0)     # fixed batch: loss must drop
        params, opt_state, loss = runner.fit_batch(params, opt_state, b)
        losses.append(float(np.asarray(loss)))
    assert losses[-1] < losses[0]         # made progress through encoding
    stats = runner.trainer.encoder_stats(opt_state)
    assert stats["nnz"] >= 0 and stats["threshold"] > 0
    assert np.isfinite(stats["residual_norm"])
    runner.finalize(params, opt_state)


# ===================== REAL two-process chaos ===========================
def _spawn_pair(tmp_path, ckpt_dir, mode, tag):
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    outs = [str(tmp_path / f"{tag}_w{i}.json") for i in (0, 1)]
    procs = [subprocess.Popen(
        [sys.executable, _WORKER, str(i), str(port), outs[i],
         str(ckpt_dir), mode],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for i in (0, 1)]
    return procs, outs


def _wait_pair(procs, timeout=300):
    logs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        logs.append(out)
    return logs


def _load(outs):
    return [json.load(open(o)) for o in outs]


@pytest.mark.slow   # suite diet (ISSUE 13): ~17 s two-process soak —
# preemption bit-identity stays tier-1 via the single-process runner
# test, and two-process coordination via test_two_process_peer_loss_*
def test_two_process_preemption_bit_identical(tmp_path):
    """THE chaos headline: host.preempt injected at a sync round on
    worker 1 → both workers agree, drain into a verified checkpoint,
    exit cleanly; the restarted two-process run resumes and ends with
    params BIT-identical to a run that never saw the preemption."""
    # clean reference run
    procs, outs = _spawn_pair(tmp_path, tmp_path / "ckA", "clean", "a")
    logs = _wait_pair(procs)
    for i, p in enumerate(procs):
        assert p.returncode == 0, f"worker {i}:\n{logs[i][-3000:]}"
    clean = _load(outs)
    assert clean[0]["done"] and clean[1]["done"]
    assert clean[0]["checksum"] == clean[1]["checksum"]

    # preempted run: injected at host.preempt call 2 (step 8)
    procs, outs = _spawn_pair(tmp_path, tmp_path / "ckB",
                              "preempt@2", "b")
    logs = _wait_pair(procs)
    for i, p in enumerate(procs):
        assert p.returncode == 0, f"worker {i}:\n{logs[i][-3000:]}"
    pre = _load(outs)
    assert pre[0].get("preempted") and pre[1].get("preempted")
    assert pre[0]["step"] == pre[1]["step"] == 8

    # restart: must resume at the drained step and finish bit-identical
    procs, outs = _spawn_pair(tmp_path, tmp_path / "ckB", "clean", "c")
    logs = _wait_pair(procs)
    for i, p in enumerate(procs):
        assert p.returncode == 0, f"worker {i}:\n{logs[i][-3000:]}"
    res = _load(outs)
    assert res[0]["resumed_at"] == 8 and res[1]["resumed_at"] == 8
    assert res[0]["done"] and res[1]["done"]
    assert res[0]["checksum"] == clean[0]["checksum"]
    assert res[1]["checksum"] == clean[1]["checksum"]
    # loss trajectories line up exactly from the resume point
    np.testing.assert_array_equal(np.asarray(res[0]["losses"]),
                                  np.asarray(clean[0]["losses"][8:]))


@pytest.mark.slow   # real two-process soak; sparse-wire bit-identity
# stays tier-1 via test_wire_format.py::test_sparse_trainer_bit_identical
def test_two_process_sparse_wire_matches_dense(tmp_path):
    """The sparse ragged wire over a REAL cross-process allgather
    (jax.distributed, 2 workers): a full soak on the sparse wire must
    land on the SAME trained params as the dense exchange — the format
    changes the bytes on the wire, never the training trajectory. The
    workers also report the wire ledger: every worker ships
    (capacity + header) int32 slots per bucket, nothing dense-sized."""
    procs, outs = _spawn_pair(tmp_path, tmp_path / "ckWd", "clean", "wd")
    logs = _wait_pair(procs)
    for i, p in enumerate(procs):
        assert p.returncode == 0, f"dense worker {i}:\n{logs[i][-3000:]}"
    dense = _load(outs)
    assert dense[0]["done"] and dense[1]["done"]

    procs, outs = _spawn_pair(tmp_path, tmp_path / "ckWs", "sparse", "ws")
    logs = _wait_pair(procs)
    for i, p in enumerate(procs):
        assert p.returncode == 0, f"sparse worker {i}:\n{logs[i][-3000:]}"
    sparse = _load(outs)
    assert sparse[0]["done"] and sparse[1]["done"]
    # both workers of the sparse world agree exactly (replicated params)
    assert sparse[0]["checksum"] == sparse[1]["checksum"]
    # the sparse trajectory matches the dense one (float-reduction
    # distance: the cross-process collective is allgather+chain instead
    # of the backend's allreduce)
    np.testing.assert_allclose(np.asarray(sparse[0]["losses"]),
                               np.asarray(dense[0]["losses"]),
                               rtol=0, atol=1e-6)
    for k in dense[0]["params"]:
        np.testing.assert_allclose(
            np.asarray(sparse[0]["params"][k], np.float32),
            np.asarray(dense[0]["params"][k], np.float32),
            rtol=0, atol=1e-6, err_msg=f"param {k} diverged on the wire")
    # wire ledger: the reported bytes are exactly the ragged format's
    # (capacity + header) slots per worker per bucket
    ws = sparse[0]["wire_stats"]
    assert ws["wire_bytes"] == sum(ws["bucket_wire_bytes"])
    for cap, b in zip(ws["wire_capacity"], ws["bucket_wire_bytes"]):
        # 8 dp shards (4 devices × 2 processes), WIRE_HEADER=2 slots
        assert b == (cap + 2) * 4 * 8


@pytest.mark.slow   # suite diet (ISSUE 14): ~13 s two-process soak —
# peer-loss containment stays tier-1 via the in-process
# test_peer_lost_is_bounded_and_dumps + test_monitor_detects_silent_peer,
# and real two-process jax.distributed execution via
# test_multihost.py::test_two_process_sharded_trainer
def test_two_process_peer_loss_bounded(tmp_path):
    """A hard-killed peer (os._exit inside sync round 2) surfaces on
    the survivor as PeerLostError + a peer-table dump within the
    configured timeout — no indefinite collective hang."""
    procs, outs = _spawn_pair(tmp_path, tmp_path / "ckD", "die@2", "d")
    t0 = time.monotonic()
    logs = _wait_pair(procs, timeout=180)
    elapsed = time.monotonic() - t0
    assert procs[1].returncode == 23, logs[1][-2000:]   # the kill
    assert procs[0].returncode == 0, logs[0][-3000:]    # clean surfacing
    survivor = json.load(open(outs[0]))
    assert survivor.get("peer_lost"), survivor
    assert survivor["report_exists"], survivor
    # bounded: worker startup+jit dominates; detection itself is the
    # 8 s peer timeout, so the whole run must finish well under the
    # no-containment alternative (an indefinite hang → 180 s kill)
    assert elapsed < 150


@pytest.mark.slow
def test_two_process_real_sigterm_bit_identical(tmp_path):
    """Satellite soak: a REAL kill -TERM lands on worker 1 mid-run; the
    SIGTERM handler requests the drain, both workers checkpoint and
    exit 0, and the restarted run ends bit-identical to a clean one."""
    procs, outs = _spawn_pair(tmp_path, tmp_path / "ckS", "clean", "s")
    logs = _wait_pair(procs)
    clean = _load(outs)
    assert clean[0]["done"]

    procs, outs = _spawn_pair(tmp_path, tmp_path / "ckT", "sigterm", "t")
    # watch worker 1's stdout for progress, then deliver the signal
    killed = False
    for line in procs[1].stdout:
        if "step 5" in line:
            procs[1].send_signal(signal.SIGTERM)
            killed = True
            break
    assert killed, "worker 1 never reached step 5"
    out1 = procs[1].stdout.read()
    out0, _ = procs[0].communicate(timeout=300)
    procs[1].wait(timeout=60)
    assert procs[0].returncode == 0, out0[-3000:]
    assert procs[1].returncode == 0, out1[-3000:]
    pre = _load(outs)
    assert pre[0].get("preempted") and pre[1].get("preempted")
    assert pre[0]["step"] == pre[1]["step"]

    procs, outs = _spawn_pair(tmp_path, tmp_path / "ckT", "clean", "u")
    logs = _wait_pair(procs)
    for i, p in enumerate(procs):
        assert p.returncode == 0, f"worker {i}:\n{logs[i][-3000:]}"
    res = _load(outs)
    assert res[0]["resumed_at"] == pre[0]["step"]
    assert res[0]["checksum"] == clean[0]["checksum"]
