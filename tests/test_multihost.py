"""Two-process jax.distributed execution test (≡ dl4j-spark ::
SharedTrainingMaster actually running across workers — round-1 VERDICT:
the multi-host path was gated code that had never executed).

Spawns two REAL processes, each with 4 virtual CPU devices; the dp mesh
spans all 8 devices across both processes and the gradient all-reduce
rides the distributed backend (gRPC here; DCN on a TPU pod).
"""
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

_WORKER = os.path.join(os.path.dirname(__file__), "multihost_worker.py")


def _free_port():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_sharded_trainer(tmp_path):
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    outs = [str(tmp_path / f"w{i}.json") for i in (0, 1)]
    procs = [subprocess.Popen(
        [sys.executable, _WORKER, str(i), str(port), outs[i]],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for i in (0, 1)]
    logs = []
    for p in procs:
        out, _ = p.communicate(timeout=400)
        logs.append(out)
    for i, p in enumerate(procs):
        assert p.returncode == 0, f"worker {i} failed:\n{logs[i][-3000:]}"

    results = [json.load(open(o)) for o in outs]
    # both processes observed the identical (replicated) loss trajectory
    np.testing.assert_allclose(results[0]["losses"], results[1]["losses"],
                               rtol=1e-6)
    # training made progress
    assert results[0]["losses"][-1] < results[0]["losses"][0]
    # replicated params agree bit-for-bit across processes
    assert results[0]["checksum"] == results[1]["checksum"]

    # cluster metrics plane (ISSUE 15): process 0's /metrics carries
    # BOTH hosts' series (host="0"/"1" labels) plus the cluster
    # aggregate, published over the real coordination KV
    cm = results[0]["cluster_metrics"]
    assert cm["host0"] and cm["host1"], cm
    assert cm["cluster_sum"] and cm["age_gauge"], cm
    # /health aggregates the per-host snapshot meta on process 0
    hc = results[0]["health_cluster"]
    assert hc["published"] == 2 and sorted(hc["hosts"]) == ["0", "1"]
    assert all(v is not None
               for v in results[0]["peer_steps_per_s"].values())
    # forced SLO breach flips health to degraded with the objective
    # named, then auto-recovers once the breach clears
    assert results[0]["slo_breach"]["status"] == "degraded"
    assert results[0]["slo_breach"]["violated"] == ["worker_p99"]
    assert results[0]["slo_recovered"]["status"] == "ok"
    assert results[0]["slo_recovered"]["violated"] == []

    # straggler plane (ISSUE 16): process 0 gathered BOTH hosts' step
    # timelines over the KV and named the artificially slowed peer —
    # host 1, dispatch phase — with the skew quantified
    r0 = results[0]
    assert r0["timeline_hosts"] == ["0", "1"]
    assert all("dispatch" in ph for ph in r0["timeline_phases"].values())
    assert r0["straggler"]["host"] == "1"
    assert r0["straggler"]["phase"] == "dispatch"
    assert r0["straggler"]["ratio"] > 2.0
    # the derived multi-process exchange exposure is the cross-host
    # dispatch skew (60 vs 5 ms feeds)
    assert 50.0 <= r0["derived_exchange_ms"] <= 60.0
    # HTTP surfaces on process 0: /stragglers names the culprit,
    # /steps carries every host's digest, /trace has one lane per host
    assert r0["http_stragglers"]["host"] == "1"
    assert r0["http_stragglers"]["phase"] == "dispatch"
    assert r0["http_steps_hosts"] == ["0", "1"]
    assert r0["trace_lanes"] == ["train host 0", "train host 1"]
    # straggler SLO: degraded with the culprit named, auto-recovered
    # once both hosts republished healthy digests
    assert r0["straggler_breach"]["status"] == "degraded"
    assert r0["straggler_breach"]["violated"] == ["straggler_ratio"]
    assert r0["straggler_breach"]["culprit"] == {"host": "1",
                                                 "phase": "dispatch"}
    assert r0["straggler_recovered"]["status"] == "ok"
    assert r0["straggler_recovered"]["violated"] == []


def test_orbax_restore_across_mesh_shape_change(tmp_path, devices8):
    """Elastic resume must re-place a checkpoint saved on one mesh layout
    onto a DIFFERENT mesh (shape change on restart — the elastic story)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deeplearning4j_tpu.parallel.elastic import ElasticCheckpointer

    rng = np.random.default_rng(3)
    W = rng.standard_normal((16, 32)).astype(np.float32)
    b = rng.standard_normal((32,)).astype(np.float32)

    # save under a 1-D dp=8 mesh, W sharded over rows
    mesh_a = Mesh(np.array(devices8), ("dp",))
    params_a = {
        "W": jax.device_put(W, NamedSharding(mesh_a, P("dp", None))),
        "b": jax.device_put(b, NamedSharding(mesh_a, P())),
    }
    ck = ElasticCheckpointer(tmp_path / "ck")
    ck.save(7, params_a, wait=True)

    # restore under a 2-D dp=2 x tp=4 mesh, W sharded over COLUMNS now
    mesh_b = Mesh(np.array(devices8).reshape(2, 4), ("dp", "tp"))
    like = {
        "W": jax.device_put(jnp.zeros_like(W),
                            NamedSharding(mesh_b, P(None, "tp"))),
        "b": jax.device_put(jnp.zeros_like(b), NamedSharding(mesh_b, P())),
    }
    step, state = ck.restore(like={"params": like})
    ck.close()
    assert step == 7
    got = state["params"]
    np.testing.assert_array_equal(np.asarray(got["W"]), W)
    np.testing.assert_array_equal(np.asarray(got["b"]), b)
    # and the restored arrays carry the NEW mesh's sharding
    assert got["W"].sharding.spec == P(None, "tp")
    assert got["W"].sharding.mesh.shape == {"dp": 2, "tp": 4}
