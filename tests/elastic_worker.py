"""Elastic chaos worker: true mid-run join / leave / replace across
REAL process boundaries.

Run as:  python elastic_worker.py <pid> <kv_port> <out_json> <ckpt_dir>
             <mode>

Each worker is an INDEPENDENT single-process jax instance (its own 8
virtual CPU devices — `jax.distributed` cannot lose a member, see
kv_server.py); the coordination plane (heartbeats, membership
announcements, admission tickets, barriers) rides the harness-owned TCP
KV, and the checkpoint warm-start rides the shared filesystem. The dp
mesh is `mesh_factory(members)` → 4 local devices per member (capped at
8), so re-forms exercise real mesh narrowing/widening; batches are
keyed by the step number and `compress=False`, so every host computes
the same full-batch mean gradient regardless of width and a chaos run
must land within float-accumulation distance of a fixed-membership
reference.

mode (worker 0 always runs "clean"):
  clean     — pre-wired member [0, 1]: train to TOTAL, write params
  die@N     — hard-exit (os._exit 27) before step N: the survivor must
              re-form on the reduced roster and keep training from the
              newest verified checkpoint
  leave@N   — request_leave() at step N: drain-clean exit at the agreed
              boundary ("left" marker, exit 0)
  join      — a (re)started host: announce, await admission, warm-start
              from the drain checkpoint, train to TOTAL in lockstep
"""
import json
import os
import sys
import time

pid = int(sys.argv[1])
kv_port = int(sys.argv[2])
out_path = sys.argv[3]
ckpt_dir = sys.argv[4]
mode = sys.argv[5]

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.updaters import Sgd
from deeplearning4j_tpu.parallel.multihost import (ElasticMembership,
                                                   MultiHostRunner,
                                                   MultiHostTrainer,
                                                   PeerCoordinator,
                                                   global_batch)
from deeplearning4j_tpu.resilience.errors import PreemptionSignal
from jax.sharding import Mesh
from kv_server import TcpKV

TOTAL, SYNC, SAVE = 40, 2, 4
PEER_TIMEOUT = 8.0


def loss_fn(params, batch, rng_key):
    h = jnp.tanh(batch["x"] @ params["W1"])
    return jnp.mean(h * h)


def mesh_factory(members):
    n = min(4 * len(members), 8)
    return Mesh(np.array(jax.devices()[:n]), ("dp",))


def trainer_factory(mesh):
    return MultiHostTrainer(loss_fn, Sgd(0.3), mesh=mesh, compress=False)


def make_batch(trainer, step):
    r = np.random.default_rng(1000 + step)
    xs = r.standard_normal((8, 6)).astype(np.float32)
    return global_batch(trainer.mesh, {"x": xs})


def init_params():
    r = np.random.default_rng(0)
    return {"W1": (r.standard_normal((6, 5)) * 0.5).astype(np.float32)}


kv = TcpKV("localhost", kv_port)
coordinator = PeerCoordinator(sync_every=SYNC, peer_timeout=PEER_TIMEOUT,
                              client=kv, process_id=pid, num_processes=2,
                              dump_dir=os.path.dirname(out_path))

result = {"pid": pid, "mode": mode}
die_at = leave_at = None
if mode.startswith("die@"):
    die_at = int(mode.split("@")[1])
elif mode.startswith("leave@"):
    leave_at = int(mode.split("@")[1])

try:
    if mode == "join":
        runner, params, opt_state = MultiHostRunner.join_cluster(
            trainer_factory, ckpt_dir, coordinator, mesh_factory,
            init_params(), timeout=90.0, save_every=SAVE,
            monitor=False, sigterm=False)
        result["joined_at"] = runner.step
        print(f"worker {pid} joined at step {runner.step}", flush=True)
    else:
        membership = ElasticMembership(coordinator, members=[0, 1])
        runner = MultiHostRunner(
            trainer_factory(mesh_factory([0, 1])), ckpt_dir, coordinator,
            save_every=SAVE, elastic=True, mesh_factory=mesh_factory,
            membership=membership, monitor=False, sigterm=False)
        params, opt_state = runner.resume_or_init(init_params())
        result["resumed_at"] = runner.resumed_step

    left = False
    while runner.step < TOTAL:
        if die_at is not None and runner.step >= die_at:
            print(f"worker {pid} dying at step {runner.step}", flush=True)
            sys.stdout.flush()
            os._exit(27)
        if leave_at is not None and not left and runner.step >= leave_at:
            runner.request_leave()
            left = True
            print(f"worker {pid} announced leave at {runner.step}",
                  flush=True)
        if len(coordinator.members) == 1 and runner.step == TOTAL - 6:
            # solo survivor: hold the last stretch open so a restarted
            # peer's announcement (cold python+jax boot) can land — the
            # admission itself happens at the next sync inside fit_batch
            deadline = time.monotonic() + 45.0
            while time.monotonic() < deadline and \
                    not kv.key_value_dir_get(coordinator._key("em/join/")):
                time.sleep(0.25)
        params, opt_state, loss = runner.fit_batch(
            params, opt_state, make_batch(runner.trainer, runner.step))
        print(f"worker {pid} step {runner.step} "
              f"members {len(coordinator.members)}", flush=True)
    runner.finalize(params, opt_state)
    result.update(done=True, steps=runner.step,
                  members=list(coordinator.members),
                  replaces=runner._replaces,
                  params={k: np.asarray(jax.device_get(v)).tolist()
                          for k, v in params.items()})
except PreemptionSignal as e:
    result.update(left=True, step=runner.step, reason=str(e))
    runner.close()
except BaseException as e:  # noqa: BLE001 — persist the evidence first
    import traceback
    result.update(crashed=repr(e), traceback=traceback.format_exc())
    with open(out_path, "w") as f:
        json.dump(result, f)
    print("worker", pid, "CRASH:", repr(e), flush=True)
    sys.stdout.flush()
    os._exit(1)

with open(out_path, "w") as f:
    json.dump(result, f)
print("worker", pid, "exit:",
      {k: v for k, v in result.items() if k != "params"}, flush=True)
