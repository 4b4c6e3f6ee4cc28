"""Chaos worker for the two-process preemption / peer-loss tests.

Run as:  python multihost_chaos_worker.py <process_id> <port> <out_json>
             <ckpt_dir> <mode>

mode:
  clean       — train TOTAL steps, write the final param checksum
  preempt@R   — worker 1 injects a `PreemptionSignal` at `host.preempt`
                call R (≡ SIGTERM at an exact sync point); BOTH workers
                must agree, drain into a verified checkpoint, and exit
                cleanly with a "preempted" marker
  sigterm     — train, expecting a REAL kill -TERM from the test
                harness mid-run (prints step lines so the harness can
                time the kill)
  die@R       — worker 1 hard-exits (os._exit) inside sync round R:
                the survivor must surface `PeerLostError` + a peer
                report within its peer timeout, never hang
  sparse      — like clean, but the gradient exchange rides the sparse
                ragged wire format (per-bucket (index,sign) payloads
                over a REAL cross-process allgather, capacity = nnz):
                the final params must match a dense clean run

The trainer is the full multi-host stack: MultiHostTrainer with
threshold-encoded gradient exchange, CoordinatedGuardian, and a
MultiHostRunner doing coordinated saves (process 0 writes, worker 1
verifies the manifests). Batches and rng are derived from the step
number, so a preempted+resumed run must end BIT-IDENTICAL to a clean
one.
"""
import hashlib
import json
import os
import sys

pid = int(sys.argv[1])
port = sys.argv[2]
out_path = sys.argv[3]
ckpt_dir = sys.argv[4]
mode = sys.argv[5]

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import numpy as np

# distributed init precedes anything that can touch the XLA backend
from deeplearning4j_tpu.parallel.multihost import initialize

assert initialize(f"localhost:{port}", num_processes=2, process_id=pid,
                  connect_deadline=60, barrier_timeout=30)

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.updaters import Sgd
from deeplearning4j_tpu.parallel.multihost import (CoordinatedGuardian,
                                                   MultiHostRunner,
                                                   MultiHostTrainer,
                                                   PeerCoordinator,
                                                   global_batch)
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.resilience.errors import (PeerLostError,
                                                  PreemptionSignal)

TOTAL, SYNC, SAVE = 24, 4, 8
PEER_TIMEOUT = 8.0

assert jax.process_count() == 2
assert len(jax.devices()) == 8

plan = None
if "@" in mode:
    kind, r = mode.split("@")
    r = int(r)
    plan = faults.FaultPlan(seed=0, process_id=pid)
    if pid == 1:
        if kind == "preempt":
            plan.fail_at(faults.HOST_PREEMPT, r,
                         exc=lambda site, n: PreemptionSignal(
                             f"injected at {site} call {n}"))
        elif kind == "die":
            plan.fail_at(faults.HOST_PREEMPT, r,
                         exc=lambda site, n: os._exit(23))
    plan.install()


def loss_fn(params, batch, rng_key):
    h = jnp.tanh(batch["x"] @ params["W1"])
    logits = h @ params["W2"]
    return -jnp.mean(jnp.sum(batch["y"] * jax.nn.log_softmax(logits, -1),
                             -1))


rng = np.random.default_rng(0)           # same seed on both processes
W1 = (rng.standard_normal((8, 16)) * 0.3).astype(np.float32)
W2 = (rng.standard_normal((16, 4)) * 0.3).astype(np.float32)

coordinator = PeerCoordinator(sync_every=SYNC, peer_timeout=PEER_TIMEOUT,
                              dump_dir=os.path.dirname(out_path))
trainer = MultiHostTrainer(loss_fn, Sgd(0.2), compress=True,
                           wire="sparse" if mode == "sparse" else "dense",
                           wire_capacity=1.0,
                           compression_kw={"initial_threshold": 1e-3})
guardian = CoordinatedGuardian(coordinator, warmup_steps=100)
runner = MultiHostRunner(trainer, ckpt_dir, coordinator,
                         save_every=SAVE, guardian=guardian, rng_seed=7)


def make_batch(step):
    """Deterministic batch keyed by step — both processes generate the
    same full arrays; global_batch shards them over the 8-device mesh."""
    r = np.random.default_rng(1000 + step)
    xs = r.standard_normal((16, 8)).astype(np.float32)
    ys = np.eye(4, dtype=np.float32)[r.integers(0, 4, 16)]
    return global_batch(trainer.mesh, {"x": xs, "y": ys})


def host_scalar(a):
    return float(np.asarray(a.addressable_shards[0].data)) \
        if hasattr(a, "addressable_shards") else float(a)


def checksum(params):
    h = hashlib.md5()
    for k in sorted(params):
        a = params[k]
        h.update(np.array(a.addressable_shards[0].data).tobytes())
    return h.hexdigest()


result = {"pid": pid, "mode": mode}
losses = []
try:
    params, opt_state = runner.resume_or_init({"W1": W1, "W2": W2})
    result["resumed_at"] = runner.resumed_step
    while runner.step < TOTAL:
        params, opt_state, loss = runner.fit_batch(
            params, opt_state, make_batch(runner.step))
        losses.append(host_scalar(loss))
        print(f"worker {pid} step {runner.step}", flush=True)
    runner.finalize(params, opt_state)
    result.update(done=True, checksum=checksum(params),
                  losses=losses, steps=runner.step,
                  params={k: np.array(
                      params[k].addressable_shards[0].data).tolist()
                      for k in sorted(params)},
                  wire_stats=trainer.encoder_stats(opt_state)
                  if mode == "sparse" else None)
except PreemptionSignal as e:
    result.update(preempted=True, step=runner.step, reason=str(e))
    runner.close()
except PeerLostError as e:
    result.update(peer_lost=True, step=runner.step, error=str(e),
                  report=e.report_path,
                  report_exists=bool(e.report_path
                                     and os.path.exists(e.report_path)))
    with open(out_path, "w") as f:
        json.dump(result, f)
    print("worker", pid, "exit (peer lost):", result["error"], flush=True)
    # skip the interpreter-exit distributed shutdown: jax's shutdown
    # barrier can never complete with a dead peer and ABORTS the
    # process (client.h fatal) — the containment already did its job,
    # leave with a clean code for the supervisor
    sys.stdout.flush()
    os._exit(0)
except BaseException as e:  # noqa: BLE001 — persist the evidence first
    import traceback
    result.update(crashed=repr(e), traceback=traceback.format_exc(),
                  step=runner.step)
    with open(out_path, "w") as f:
        json.dump(result, f)
    print("worker", pid, "CRASH:", repr(e), flush=True)
    sys.stdout.flush()
    os._exit(1)

with open(out_path, "w") as f:
    json.dump(result, f)
print("worker", pid, "exit:", {k: v for k, v in result.items()
                               if k != "losses"}, flush=True)
