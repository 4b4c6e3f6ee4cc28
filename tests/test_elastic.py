"""True elastic multi-host (ISSUE 17): mid-run JOIN / LEAVE / REPLACE
over the coordination KV, with the per-worker encoder stacks re-stacked
for the new dp width at every re-form.

Tier-1 layers:
- `restack_encoder` numerics (shrink conserves residual mass, grow
  tiles thresholds and zero-fills residuals);
- `ElasticMembership` protocol on the KV (announce → heartbeat-union
  agreement → leader commit → roster epoch / admission ticket /
  departed-host reap; typed failures leave the old roster
  authoritative);
- the elastic `MultiHostRunner` flows, driven by ONE real runner
  (pid 0) against synthetic peers pumping bare `PeerCoordinator`s on
  the shared LocalKV: join widens the mesh at a sync boundary, a
  graceful leave shrinks it and reaps the leaver's KV state, a silent
  peer triggers REPLACEMENT (restore newest verified, step rewinds
  < save_every, the replayed step is bit-equal), and `join_cluster`
  warm-starts a real joiner from the drain checkpoint with the
  members' counters adopted;
- the `host.join` fault site (faults.HOST_JOIN): an injected failure in
  the admission window — on either side — abandons the announcements
  and raises the typed error with the roster untouched.

The slow tier drives the same flows across REAL process boundaries
(harness-owned TCP KV + independent jax instances — see kv_server.py):
kill a worker mid-run, watch the survivor re-form and keep training,
restart the worker through `join_cluster`, and land within float
distance of a fixed-membership reference.
"""
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from deeplearning4j_tpu.nn.updaters import Sgd
from deeplearning4j_tpu.parallel.membership import (JOIN_PREFIX,
                                                    ElasticMembership,
                                                    restack_encoder)
from deeplearning4j_tpu.parallel.multihost import (LocalKV,
                                                   MultiHostRunner,
                                                   MultiHostTrainer,
                                                   PeerCoordinator,
                                                   global_batch)
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.resilience.errors import MembershipChangeError

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


def _loss_fn(params, batch, rng_key):
    h = jnp.tanh(batch["x"] @ params["W1"])
    return jnp.mean(h * h)


def _init_params():
    r = np.random.default_rng(0)
    return {"W1": (r.standard_normal((6, 5)) * 0.5).astype(np.float32)}


def _mesh_factory(members):
    return Mesh(np.array(jax.devices()[:4 * len(members)]), ("dp",))


def _trainer(mesh, **kw):
    kw.setdefault("compress", True)
    kw.setdefault("compression_kw", {"initial_threshold": 1e-4})
    return MultiHostTrainer(_loss_fn, Sgd(0.3), mesh=mesh, **kw)


def _batch(trainer, step):
    r = np.random.default_rng(100 + step)
    return global_batch(trainer.mesh,
                        {"x": r.standard_normal((8, 6)).astype(np.float32)})


def _coord(kv, pid, tmp, peer_timeout=6.0):
    return PeerCoordinator(sync_every=2, peer_timeout=peer_timeout,
                           client=kv, process_id=pid, num_processes=1,
                           dump_dir=tmp)


# ===================== restack_encoder numerics =========================
def _enc(n, buckets=2, elems=7, seed=0):
    r = np.random.default_rng(seed)
    return {"residual": {str(b): r.standard_normal(
                (n, elems)).astype(np.float32) for b in range(buckets)},
            "threshold": np.linspace(1e-4, 8e-4, n).astype(np.float32),
            "nnz": np.arange(n, dtype=np.int32)}


def test_restack_encoder_shrink_conserves_residual_mass():
    enc = _enc(8)
    out = restack_encoder(enc, 4)
    for b in ("0", "1"):
        assert out["residual"][b].shape == (4, 7)
        # fold i -> i % new_n: departed workers' un-sent mass survives
        np.testing.assert_allclose(
            out["residual"][b].sum(axis=0), enc["residual"][b].sum(axis=0),
            rtol=1e-6)
        np.testing.assert_array_equal(
            out["residual"][b][1],
            enc["residual"][b][1] + enc["residual"][b][5])
    np.testing.assert_array_equal(out["threshold"], enc["threshold"][:4])
    assert (out["nnz"] == 0).all() and out["nnz"].shape == (4,)


def test_restack_encoder_grow_tiles_thresholds_zero_residual():
    enc = _enc(4)
    out = restack_encoder(enc, 8)
    for b in ("0", "1"):
        np.testing.assert_array_equal(out["residual"][b][:4],
                                      enc["residual"][b])
        assert (out["residual"][b][4:] == 0).all()
    # a joiner starts from a peer's ADAPTED threshold, not the default
    np.testing.assert_array_equal(out["threshold"][4:], enc["threshold"])
    assert (out["nnz"] == 0).all()
    assert restack_encoder(enc, 4) is enc          # same width: no-op
    with pytest.raises(ValueError, match="width 0"):
        restack_encoder(enc, 0)


# ===================== membership protocol on the KV ====================
def test_membership_join_commit_admits_and_clears():
    kv, tmp = LocalKV(), tempfile.mkdtemp()
    c0, c1 = _coord(kv, 0, tmp), _coord(kv, 1, tmp)
    m0 = ElasticMembership(c0, members=[0])
    m1 = ElasticMembership(c1, members=[1])
    m1.announce_join()
    assert m0.pending() == ([1], [])
    info = {"step": 4, "cstep": 4, "rounds": 2, "save_seq": 1, "dp": 4}
    assert m0.commit([1], [], info=info) == [0, 1]
    assert m0.epoch == 1 and c0.members == [0, 1]
    # announcement cleared, roster epoch + ticket written with the info
    assert not kv.key_value_dir_get(c0._key(JOIN_PREFIX))
    roster = json.loads(kv.blocking_key_value_get(
        c0._key("em/roster/1"), 1000))
    assert roster["members"] == [0, 1]
    ticket = m1.await_admission(timeout=1.0)
    assert m1.members == [0, 1] and ticket["dp"] == 4 \
        and ticket["cstep"] == 4


def test_membership_leave_commit_reaps_departed_state():
    kv, tmp = LocalKV(), tempfile.mkdtemp()
    c0 = _coord(kv, 0, tmp)
    m0 = ElasticMembership(c0, members=[0, 1])
    for k in ("metrics/1", "steps/1", "alive/1", "hb/7/1"):
        kv.key_value_set(c0._key(k), "x")
    m0.announce_leave(pid=1)
    assert m0.pending() == ([], [1])
    assert m0.commit([], [1]) == [0]
    live = {k for k, _ in kv.key_value_dir_get(c0._key(""))}
    for k in ("metrics/1", "steps/1", "alive/1", "hb/7/1", "em/leave/1"):
        assert c0._key(k) not in live, f"{k} must be reaped"
    with pytest.raises(MembershipChangeError, match="zero members"):
        m0.commit([], [0])


def test_membership_admission_timeout_and_abandon():
    kv, tmp = LocalKV(), tempfile.mkdtemp()
    c1 = _coord(kv, 1, tmp)
    m1 = ElasticMembership(c1, members=[1])
    m1.announce_join()
    with pytest.raises(MembershipChangeError, match="never admitted"):
        m1.await_admission(timeout=0.2)
    m1.abandon(joins=[1])
    assert not kv.key_value_dir_get(c1._key(JOIN_PREFIX))


# ===================== elastic runner validation ========================
def test_elastic_runner_validation(devices8):
    kv, tmp = LocalKV(), tempfile.mkdtemp()
    tr = _trainer(_mesh_factory([0]))
    with pytest.raises(ValueError, match="mesh_factory"):
        MultiHostRunner(tr, tmp + "/ck", _coord(kv, 0, tmp),
                        elastic=True, monitor=False, sigterm=False)
    zr = MultiHostTrainer(_loss_fn, Sgd(0.3), mesh=_mesh_factory([0]),
                          zero1=True)
    with pytest.raises(ValueError, match="zero1"):
        MultiHostRunner(zr, tmp + "/ck", _coord(kv, 0, tmp),
                        elastic=True, mesh_factory=_mesh_factory,
                        monitor=False, sigterm=False)
    run = MultiHostRunner(tr, tmp + "/ck", _coord(kv, 0, tmp),
                          monitor=False, sigterm=False)
    try:
        with pytest.raises(MembershipChangeError, match="elastic"):
            run.request_leave()
    finally:
        run.close()


# ===================== join: mesh widens at the boundary ================
def test_join_widens_mesh_and_restacks_encoder(devices8):
    kv, tmp = LocalKV(), tempfile.mkdtemp()
    c0 = _coord(kv, 0, tmp, peer_timeout=8.0)
    runner = MultiHostRunner(
        _trainer(_mesh_factory([0]), wire="sparse", wire_capacity=1.0),
        tmp + "/ck", c0, save_every=4, elastic=True,
        mesh_factory=_mesh_factory, monitor=False, sigterm=False)
    params, opt = runner.resume_or_init(_init_params())
    assert opt["encoder"]["threshold"].shape[0] == 4
    for _ in range(4):
        params, opt, loss = runner.fit_batch(
            params, opt, _batch(runner.trainer, runner.step))

    err, admitted = [], []

    def joiner():
        try:
            c1 = _coord(kv, 1, tmp, peer_timeout=12.0)
            m1 = ElasticMembership(c1, members=[1])
            m1.announce_join()
            info = m1.await_admission(timeout=12.0)
            admitted.append(info)
            # adopt the members' counters, then heartbeat in lockstep
            # with the runner's remaining rounds (aligned step counts —
            # pumping more rounds than the runner drives would time out)
            c1.step = int(info["cstep"])
            c1.rounds = int(info["rounds"])
            for _ in range(4):
                c1.on_step()
        except Exception as e:  # noqa: BLE001 — assert on main thread
            err.append(e)

    t = threading.Thread(target=joiner)
    t.start()
    time.sleep(0.3)            # let the announcement land pre-boundary
    for _ in range(6):
        params, opt, loss = runner.fit_batch(
            params, opt, _batch(runner.trainer, runner.step))
    t.join(timeout=30)
    assert not err, f"joiner failed: {err}"
    assert c0.members == [0, 1]
    # dp mesh re-formed 4 -> 8 and the encoder stacks were re-stacked
    assert opt["encoder"]["threshold"].shape[0] == 8
    assert runner.trainer.mesh.devices.size == 8
    info = admitted[0]
    assert info["dp"] == 4 and info["step"] == runner.step - 6 + 2
    assert np.isfinite(float(np.asarray(jax.device_get(loss))))
    runner.finalize(params, opt)


# ===================== leave: mesh shrinks, leaver reaped ===============
def test_graceful_leave_shrinks_mesh_and_reaps(devices8):
    kv, tmp = LocalKV(), tempfile.mkdtemp()
    c0 = _coord(kv, 0, tmp)
    m0 = ElasticMembership(c0, members=[0, 1])
    runner = MultiHostRunner(
        _trainer(_mesh_factory([0, 1])), tmp + "/ck", c0, save_every=4,
        elastic=True, mesh_factory=_mesh_factory, membership=m0,
        monitor=False, sigterm=False)
    # departed-host KV state that must not outlive the leaver
    for k in ("metrics/1", "steps/1", "alive/1"):
        kv.key_value_set(c0._key(k), "{}")
    params, opt = runner.resume_or_init(_init_params())
    assert opt["encoder"]["threshold"].shape[0] == 8

    err = []

    def peer():
        try:
            c1 = _coord(kv, 1, tmp, peer_timeout=10.0)
            m1 = ElasticMembership(c1, members=[0, 1])
            for i in range(6):
                if i == 4:
                    m1.announce_leave()
                c1.on_step()   # the round-3 heartbeat carries the leave
        except Exception as e:  # noqa: BLE001
            err.append(e)

    t = threading.Thread(target=peer)
    t.start()
    for _ in range(6):
        params, opt, loss = runner.fit_batch(
            params, opt, _batch(runner.trainer, runner.step))
    t.join(timeout=30)
    assert not err, f"peer failed: {err}"
    assert c0.members == [0]
    assert opt["encoder"]["threshold"].shape[0] == 4
    live = {k for k, _ in kv.key_value_dir_get(c0._key(""))}
    for k in ("metrics/1", "steps/1", "alive/1", "em/leave/1"):
        assert c0._key(k) not in live, f"{k} must be reaped"
    assert not [k for k in live if "/hb/" in k and k.endswith("/1")], \
        "stale heartbeat keys of the leaver must be reaped"
    for _ in range(4):         # keeps training solo on the narrow mesh
        params, opt, loss = runner.fit_batch(
            params, opt, _batch(runner.trainer, runner.step))
    assert np.isfinite(float(np.asarray(jax.device_get(loss))))
    runner.finalize(params, opt)


# ===================== replace: silent peer -> restore verified =========
def test_peer_lost_triggers_replacement_not_death(devices8):
    kv, tmp = LocalKV(), tempfile.mkdtemp()
    c0 = _coord(kv, 0, tmp, peer_timeout=2.0)
    m0 = ElasticMembership(c0, members=[0, 1])
    runner = MultiHostRunner(
        _trainer(_mesh_factory([0, 1])), tmp + "/ck", c0, save_every=4,
        elastic=True, mesh_factory=_mesh_factory, membership=m0,
        monitor=False, sigterm=False)
    kv.key_value_set(c0._key("metrics/1"), "{}")
    params, opt = runner.resume_or_init(_init_params())

    def peer():
        c1 = _coord(kv, 1, tmp, peer_timeout=10.0)
        for _ in range(4):
            c1.on_step()       # rounds 1-2 heartbeat, then SILENCE

    t = threading.Thread(target=peer)
    t.start()
    trace = []                 # (step_after, loss) per fit_batch
    for _ in range(8):
        params, opt, loss = runner.fit_batch(
            params, opt, _batch(runner.trainer, runner.step))
        trace.append((runner.step,
                      None if loss is None else
                      float(np.asarray(jax.device_get(loss)))))
    t.join(timeout=30)

    # exactly one replacement transition: loss=None on the restore step
    restores = [i for i, (_, l) in enumerate(trace) if l is None]
    assert len(restores) == 1 and runner._replaces == 1
    i = restores[0]
    assert c0.members == [0]
    assert opt["encoder"]["threshold"].shape[0] == 4
    # the step REWOUND to the newest verified checkpoint (< save_every)
    assert trace[i - 1][0] - trace[i][0] in range(1, runner.save_every + 1)
    # deterministic replay: the re-trained step's loss equals the loss
    # originally computed at that step on the wide mesh — compress=True
    # residual state restored exactly with the params. Equal to 1e-6,
    # not to the bit: the narrower mesh sums the same terms in another
    # order (8th digit on the installed XLA:CPU); a residual or a
    # parameter that was NOT restored moves the loss in the 2nd or 3rd.
    by_step = {s: l for s, l in trace[:i]}
    s1, l1 = trace[i + 1]
    assert by_step[s1] == pytest.approx(l1, rel=1e-6), \
        "replayed step must reproduce the original loss"
    # the dead host's KV state was reaped by the lead survivor
    live = {k for k, _ in kv.key_value_dir_get(c0._key(""))}
    assert c0._key("metrics/1") not in live
    runner.finalize(params, opt)


# ===================== join_cluster: real joiner warm start =============
def test_join_cluster_warm_starts_and_adopts_counters(devices8):
    kv, tmp = LocalKV(), tempfile.mkdtemp()

    def trainer_factory(mesh):
        return _trainer(mesh)

    # phase 1: a solo pid-0 run writes a verified drain checkpoint at
    # step 4 on the NARROW (dp=4) mesh
    c0 = _coord(kv, 0, tmp)
    run0 = MultiHostRunner(trainer_factory(_mesh_factory([0])),
                           tmp + "/ck", c0, save_every=4,
                           monitor=False, sigterm=False)
    params, opt = run0.resume_or_init(_init_params())
    for _ in range(4):
        params, opt, _ = run0.fit_batch(
            params, opt, _batch(run0.trainer, run0.step))
    run0.finalize(params, opt)

    # phase 2: a synthetic leader admits the REAL joiner, which must
    # warm-start the step-4 state re-stacked 4 -> 8 and adopt the
    # members' step/round counters so lockstep holds from step one
    err = []

    def leader():
        try:
            cl = _coord(kv, 0, tmp, peer_timeout=10.0)
            ml = ElasticMembership(cl, members=[0])
            cl.fetch(f"{JOIN_PREFIX}1", timeout=10.0)
            ml.commit([1], [], info={"step": 4, "cstep": 4, "rounds": 2,
                                     "save_seq": 1, "dp": 4,
                                     "flushes": 2, "rollbacks": 0})
            cl.step, cl.rounds = 4, 2
            for _ in range(4):
                cl.on_step()
        except Exception as e:  # noqa: BLE001
            err.append(e)

    t = threading.Thread(target=leader)
    t.start()
    c1 = _coord(kv, 1, tmp, peer_timeout=10.0)
    runner, p1, o1 = MultiHostRunner.join_cluster(
        trainer_factory, tmp + "/ck", c1, _mesh_factory, _init_params(),
        timeout=10.0, save_every=4, monitor=False, sigterm=False)
    assert runner.step == 4 and runner.resumed_step == 4
    assert c1.members == [0, 1]
    assert c1.step == 4 and c1.rounds == 2 and runner._save_seq == 1
    assert o1["encoder"]["threshold"].shape[0] == 8
    for _ in range(4):
        p1, o1, loss = runner.fit_batch(
            p1, o1, _batch(runner.trainer, runner.step))
    t.join(timeout=30)
    assert not err, f"leader failed: {err}"
    assert runner.step == 8
    assert np.isfinite(float(np.asarray(jax.device_get(loss))))
    runner.finalize(p1, o1)


# ===================== host.join fault: both sides ======================
def test_host_join_fault_keeps_old_roster_authoritative(devices8):
    """faults.HOST_JOIN on the MEMBERS' side: the admission window dies
    mid-reform -> typed MembershipChangeError, announcements withdrawn,
    the OLD roster stays authoritative and training continues on it."""
    kv, tmp = LocalKV(), tempfile.mkdtemp()
    c0 = _coord(kv, 0, tmp, peer_timeout=8.0)
    runner = MultiHostRunner(
        _trainer(_mesh_factory([0])), tmp + "/ck", c0, save_every=4,
        elastic=True, mesh_factory=_mesh_factory,
        monitor=False, sigterm=False)
    params, opt = runner.resume_or_init(_init_params())
    m1 = ElasticMembership(_coord(kv, 1, tmp), members=[1])
    m1.announce_join()

    plan = faults.FaultPlan(seed=0).fail_at(faults.HOST_JOIN, 1)
    try:
        with plan:
            with pytest.raises(MembershipChangeError,
                               match="previous roster stays"):
                for _ in range(4):
                    params, opt, _ = runner.fit_batch(
                        params, opt, _batch(runner.trainer, runner.step))
        assert plan.fired[faults.HOST_JOIN] == 1
    finally:
        faults.clear_plan()
    step_at_fault = runner.step
    assert c0.members == [0]
    assert not kv.key_value_dir_get(c0._key(JOIN_PREFIX)), \
        "failed join's announcement must be withdrawn"
    # containment: the step's live buffers were donated into the jitted
    # step, but `_reform` drain-saved THIS step before the admission
    # window — the documented recovery is a resume, which lands exactly
    # on the step the fault interrupted, still on the OLD roster
    params, opt = runner.resume_or_init(_init_params())
    assert runner.step == step_at_fault
    assert opt["encoder"]["threshold"].shape[0] == 4
    for _ in range(2):
        params, opt, loss = runner.fit_batch(
            params, opt, _batch(runner.trainer, runner.step))
    assert np.isfinite(float(np.asarray(jax.device_get(loss))))
    runner.finalize(params, opt)


def test_host_join_fault_on_joiner_withdraws_announcement():
    """faults.HOST_JOIN on the JOINER's side: `join_cluster` dies before
    admission -> typed error, its announcement withdrawn, the running
    cluster's roster untouched."""
    kv, tmp = LocalKV(), tempfile.mkdtemp()
    c1 = _coord(kv, 1, tmp)
    plan = faults.FaultPlan(seed=0).fail_at(faults.HOST_JOIN, 1)
    try:
        with plan:
            with pytest.raises(MembershipChangeError,
                               match="announcement withdrawn"):
                MultiHostRunner.join_cluster(
                    lambda mesh: _trainer(mesh), tmp + "/ck", c1,
                    _mesh_factory, _init_params(), timeout=5.0,
                    monitor=False, sigterm=False)
        assert plan.fired[faults.HOST_JOIN] == 1
    finally:
        faults.clear_plan()
    assert not kv.key_value_dir_get(c1._key(JOIN_PREFIX))


# ===================== two-process elastic soaks (slow) =================
def _spawn_elastic(pid, port, out, ckpt, mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(TESTS_DIR))
    return subprocess.Popen(
        [sys.executable, os.path.join(TESTS_DIR, "elastic_worker.py"),
         str(pid), str(port), out, ckpt, mode],
        env=env, cwd=TESTS_DIR,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


def _finish(proc, name, timeout=240):
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
        pytest.fail(f"{name} timed out; output:\n{out[-4000:]}")
    return proc.returncode, out


def _load(path, who, out):
    assert os.path.exists(path), f"{who} wrote no result; log:\n{out[-4000:]}"
    with open(path) as f:
        return json.load(f)


def _reference_params(total):
    """Fixed-membership reference: compress=False makes the exchanged
    gradient the full-batch mean, identical at ANY dp width up to float
    reduction order — one solo trainer replays the soak's schedule."""
    tr = MultiHostTrainer(_loss_fn, Sgd(0.3), mesh=_mesh_factory([0]),
                          compress=False)
    p, s = tr.init(_init_params())
    root = jax.random.PRNGKey(0)
    for step in range(total):
        r = np.random.default_rng(1000 + step)
        b = global_batch(tr.mesh,
                         {"x": r.standard_normal((8, 6)).astype(np.float32)})
        p, s, _ = tr.fit_batch(p, s, b, jax.random.fold_in(root, step))
    return p


@pytest.mark.slow   # two real process boundaries + a SIGKILL mid-run
def test_two_process_kill_replace_rejoin(devices8, tmp_path):
    """THE headline elastic chaos: two independent jax processes train
    over the harness-owned TCP KV; worker 1 is hard-killed mid-run; the
    survivor re-forms on the reduced roster and keeps training from the
    newest verified checkpoint; a restarted worker 1 joins back through
    `join_cluster`; both finish, and the survivor's params land within
    float-accumulation distance of a fixed-membership reference."""
    from kv_server import KVServer
    ckpt = str(tmp_path / "ck")
    with KVServer() as srv:
        w0 = _spawn_elastic(0, srv.port, str(tmp_path / "w0.json"),
                            ckpt, "clean")
        w1 = _spawn_elastic(1, srv.port, str(tmp_path / "w1.json"),
                            ckpt, "die@12")
        rc1, out1 = _finish(w1, "w1(die@12)", timeout=180)
        assert rc1 == 27, f"w1 must die by its own hand:\n{out1[-4000:]}"
        # the replacement has (or will) run on w0; restart worker 1
        w1b = _spawn_elastic(1, srv.port, str(tmp_path / "w1b.json"),
                             ckpt, "join")
        rc0, out0 = _finish(w0, "w0(clean)", timeout=300)
        rc1b, out1b = _finish(w1b, "w1b(join)", timeout=300)
    r0 = _load(str(tmp_path / "w0.json"), "w0", out0)
    r1b = _load(str(tmp_path / "w1b.json"), "w1b", out1b)
    assert rc0 == 0 and r0.get("done"), f"w0 failed: {r0}\n{out0[-4000:]}"
    assert rc1b == 0 and r1b.get("done"), \
        f"rejoin failed: {r1b}\n{out1b[-4000:]}"
    assert r0["replaces"] == 1
    assert r0["members"] == [0, 1] == r1b["members"]
    # both hosts hold the identical final params (lockstep held through
    # replace + rejoin)...
    w0p = np.asarray(r0["params"]["W1"], np.float32)
    np.testing.assert_allclose(
        w0p, np.asarray(r1b["params"]["W1"], np.float32),
        rtol=0, atol=0)
    # ...and they match the fixed-membership reference within float
    # reduction-order distance (the chaos changed the mesh, not the math)
    ref = np.asarray(jax.device_get(_reference_params(40)["W1"]))
    np.testing.assert_allclose(w0p, ref, rtol=1e-4, atol=1e-5)


@pytest.mark.slow   # two real process boundaries, graceful drain
def test_two_process_graceful_leave_then_rejoin(devices8, tmp_path):
    """Graceful LEAVE across real process boundaries: worker 1 announces
    at step 12, drains clean at the agreed boundary (exit 0, left
    marker), the survivor continues on the narrow mesh, and a restarted
    worker 1 joins back and finishes in lockstep."""
    from kv_server import KVServer
    ckpt = str(tmp_path / "ck")
    with KVServer() as srv:
        w0 = _spawn_elastic(0, srv.port, str(tmp_path / "w0.json"),
                            ckpt, "clean")
        w1 = _spawn_elastic(1, srv.port, str(tmp_path / "w1.json"),
                            ckpt, "leave@12")
        rc1, out1 = _finish(w1, "w1(leave@12)", timeout=180)
        r1 = _load(str(tmp_path / "w1.json"), "w1", out1)
        assert rc1 == 0 and r1.get("left"), \
            f"leaver must drain clean: {r1}\n{out1[-4000:]}"
        w1b = _spawn_elastic(1, srv.port, str(tmp_path / "w1b.json"),
                             ckpt, "join")
        rc0, out0 = _finish(w0, "w0(clean)", timeout=300)
        rc1b, out1b = _finish(w1b, "w1b(join)", timeout=300)
    r0 = _load(str(tmp_path / "w0.json"), "w0", out0)
    r1b = _load(str(tmp_path / "w1b.json"), "w1b", out1b)
    assert rc0 == 0 and r0.get("done"), f"w0 failed: {r0}\n{out0[-4000:]}"
    assert rc1b == 0 and r1b.get("done"), \
        f"rejoin failed: {r1b}\n{out1b[-4000:]}"
    assert r0["replaces"] == 0, "a graceful leave is not a replacement"
    assert r0["members"] == [0, 1] == r1b["members"]
    np.testing.assert_allclose(
        np.asarray(r0["params"]["W1"], np.float32),
        np.asarray(r1b["params"]["W1"], np.float32), rtol=0, atol=0)
