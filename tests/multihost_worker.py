"""Worker process for the two-process jax.distributed test.

Run as: python multihost_worker.py <process_id> <port> <out_json>
Each process owns 4 virtual CPU devices; the global mesh spans 8 devices
across the 2 processes — the SharedTrainingMaster topology (multi-host dp
over DCN) executed for real, not just gated code (round-1 VERDICT item 7).
"""
import json
import os
import sys

pid = int(sys.argv[1])
port = sys.argv[2]
out_path = sys.argv[3]

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import numpy as np
import jax

# distributed init MUST precede anything that can touch the XLA backend —
# including framework imports (deeplearning4j_tpu.ops touches jax at import)
from deeplearning4j_tpu.parallel.mesh import initialize_distributed

assert initialize_distributed(f"localhost:{port}", num_processes=2,
                              process_id=pid)

import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.sharded_trainer import ShardedTrainer
from deeplearning4j_tpu.nn.updaters import Sgd
assert jax.process_count() == 2, jax.process_count()
devs = jax.devices()
assert len(devs) == 8, devs  # 4 local + 4 remote

mesh = Mesh(np.array(devs), ("dp",))

rng = np.random.default_rng(0)  # same seed on both processes
W1 = (rng.standard_normal((8, 16)) * 0.3).astype(np.float32)
W2 = (rng.standard_normal((16, 4)) * 0.3).astype(np.float32)
xs = rng.standard_normal((16, 8)).astype(np.float32)
ys = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 16)]


def loss_fn(params, batch, rng_key):
    h = jnp.tanh(batch["x"] @ params["W1"])
    logits = h @ params["W2"]
    return -jnp.mean(jnp.sum(batch["y"] * jax.nn.log_softmax(logits, -1), -1))


trainer = ShardedTrainer(loss_fn, Sgd(0.2), mesh)
params, opt_state = trainer.init({"W1": W1, "W2": W2})

bsh = NamedSharding(mesh, P("dp"))


def gmake(arr):
    return jax.make_array_from_callback(arr.shape, bsh, lambda idx: arr[idx])


batch = {"x": gmake(xs), "y": gmake(ys)}
losses = []
for i in range(5):
    params, opt_state, loss = trainer.fit_batch(params, opt_state, batch,
                                                jax.random.PRNGKey(i))
    losses.append(float(loss))

flat = np.concatenate([np.asarray(jax.device_get(params[k])).ravel()
                       for k in sorted(params)])
result = {"pid": pid, "losses": losses,
          "checksum": float(np.abs(flat).sum())}

# -- cluster metrics plane over the REAL coordination KV (ISSUE 15) ------
# Each process publishes its registry snapshot at sync cadence; process
# 0 renders the fleet /metrics view and the /health cluster meta. A
# forced SLO breach on process 0 must flip health to degraded with the
# objective named, then recover once the breach clears.
import time

from deeplearning4j_tpu import monitoring as mon
from deeplearning4j_tpu import resilience
from deeplearning4j_tpu.monitoring import cluster as cluster_mod
from deeplearning4j_tpu.monitoring import slo as slo_mod
from deeplearning4j_tpu.parallel.coordination import PeerCoordinator

mon.enable()
reg = mon.get_registry()
reg.counter("dl4j.test.worker_steps").inc(len(losses))
coordinator = PeerCoordinator(sync_every=1, peer_timeout=30).install()
for _ in range(3):
    coordinator.on_step()
coordinator.barrier("metrics-published")

if pid == 0:
    text = cluster_mod.cluster_prometheus_text(coordinator)
    probe = "dl4j_test_worker_steps"
    result["cluster_metrics"] = {
        "host0": f'{probe}{{host="0"}}' in text,
        "host1": f'{probe}{{host="1"}}' in text,
        "cluster_sum": f'{probe}{{host="cluster"}} 10' in text,
        "age_gauge": "dl4j_cluster_snapshot_age_seconds" in text,
    }
    snap = resilience.health_snapshot()
    result["health_cluster"] = snap["distributed"]["cluster"]
    table = coordinator.peer_table()
    result["peer_steps_per_s"] = {
        str(k): v.get("steps_per_s") for k, v in table.items()}

# -- straggler plane over the REAL coordination KV (ISSUE 16) ------------
# Process 1 plays the straggler: its flight recorder reports a 60 ms
# dispatch phase vs process 0's 5 ms. One sync point publishes both
# digests; process 0 must name the host AND the phase on /stragglers,
# carry both timelines on /steps, render one training lane per host on
# /trace, and flip health degraded via the StragglerObjective — then
# auto-recover when the slowdown clears.
from deeplearning4j_tpu.monitoring import steps as steps_mod
from deeplearning4j_tpu.monitoring import stragglers as stragglers_mod

rec = steps_mod.recorder()
rec.clear()
dispatch_ms = 60.0 if pid == 1 else 5.0
for _ in range(4):
    rec.on_span("fit.data_next", 1.0)
    rec.on_span("sharded.dispatch", dispatch_ms)
coordinator.on_step()                      # sync-point publish
coordinator.barrier("slowed-published")

if pid == 0:
    att = stragglers_mod.attribution(coordinator)
    result["straggler"] = att["slowest"]
    result["timeline_hosts"] = sorted(att["hosts"])
    result["timeline_phases"] = {
        h: sorted(d["phases_p50_ms"]) for h, d in att["hosts"].items()}
    result["derived_exchange_ms"] = \
        stragglers_mod.derived_exchange_ms(coordinator)

    import urllib.request
    from deeplearning4j_tpu.ui.server import UIServer
    server = UIServer.getInstance()
    server.start(port=0)
    try:
        base = f"http://127.0.0.1:{server.port}"
        sdoc = json.load(urllib.request.urlopen(base + "/stragglers",
                                                timeout=10))
        result["http_stragglers"] = sdoc["slowest"]
        steps_doc = json.load(urllib.request.urlopen(base + "/steps",
                                                     timeout=10))
        result["http_steps_hosts"] = sorted(steps_doc.get("hosts", {}))
        tdoc = json.load(urllib.request.urlopen(base + "/trace",
                                                timeout=10))
        result["trace_lanes"] = sorted(
            e["args"]["name"] for e in tdoc["traceEvents"]
            if e["ph"] == "M" and e["name"] == "process_name"
            and str(e["args"].get("name", "")).startswith("train host"))
    finally:
        server.stop()

    sg_tracker = slo_mod.SloTracker(
        [slo_mod.StragglerObjective("straggler_ratio", max_ratio=2.0,
                                    coordinator=coordinator)],
        short_window=0.2, long_window=0.5, min_interval=0.0).install()
    deadline = time.monotonic() + 0.7
    while time.monotonic() < deadline:
        sg_tracker.evaluate(force=True)
        time.sleep(0.05)
    breach = resilience.health_snapshot()
    obj = breach["slo"]["objectives"]["straggler_ratio"]
    result["straggler_breach"] = {"status": breach["status"],
                                  "violated": breach["slo"]["violated"],
                                  "culprit": obj.get("culprit")}

coordinator.barrier("straggler-breach")

# the slowdown clears: both hosts republish healthy digests
rec.clear()
for _ in range(4):
    rec.on_span("fit.data_next", 1.0)
    rec.on_span("sharded.dispatch", 5.0)
coordinator.on_step()
coordinator.barrier("recovered-published")

if pid == 0:
    deadline = time.monotonic() + 0.7
    while time.monotonic() < deadline:
        sg_tracker.evaluate(force=True)
        time.sleep(0.05)
    recovered = resilience.health_snapshot()
    result["straggler_recovered"] = {
        "status": recovered["status"],
        "violated": recovered["slo"]["violated"]}
    sg_tracker.uninstall()

    # forced SLO breach: impossible latency objective over a loaded
    # histogram; tiny burn windows so breach AND recovery both land
    # inside the soak
    h = reg.histogram("dl4j.test.worker_lat", reservoir=256)
    for _ in range(256):
        h.observe(100.0)
    tracker = slo_mod.SloTracker(
        [slo_mod.LatencyObjective("worker_p99",
                                  metric="dl4j.test.worker_lat",
                                  max_value=5.0)],
        short_window=0.2, long_window=0.5, min_interval=0.0).install()
    deadline = time.monotonic() + 0.7
    while time.monotonic() < deadline:
        tracker.evaluate(force=True)
        time.sleep(0.05)
    breach = resilience.health_snapshot()
    result["slo_breach"] = {"status": breach["status"],
                            "violated": breach["slo"]["violated"]}
    for _ in range(512):                     # latency recovers
        h.observe(0.1)
    deadline = time.monotonic() + 0.7
    while time.monotonic() < deadline:
        tracker.evaluate(force=True)
        time.sleep(0.05)
    recovered = resilience.health_snapshot()
    result["slo_recovered"] = {"status": recovered["status"],
                               "violated": recovered["slo"]["violated"]}
    tracker.uninstall()

coordinator.barrier("slo-done")
coordinator.uninstall()
mon.disable()

with open(out_path, "w") as f:
    json.dump(result, f)
print("worker", pid, "done", result["losses"][0], "->", result["losses"][-1])
