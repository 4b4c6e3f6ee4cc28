#!/usr/bin/env python
"""CPU microbench: in-step gradient accumulation + bucketed overlapped
exchange vs the naive per-microbatch loop (parallel/ — ISSUE 14), one
JSON line.

Measures the dispatch-amortization the accumulated step exists for,
with bench.py's median-of-≥5-windows + recorded-spread methodology (a
point sample of a ±20%-noise distribution is not a measurement), on the
8-virtual-device CPU mesh (dispatch/IO-bound: the model is small, so
per-dispatch host round-trips dominate):

- **naive arm** — what a G-sized effective batch costs today without
  in-step accumulation: G per-microbatch optimizer steps, i.e. G
  dispatches + G updater applications per effective batch.
- **accumulated arm** — `MultiHostTrainer(accumulation=G)`: ONE jitted
  dispatch per effective batch (the step scans the G microbatches,
  accumulates on device, applies one update), threshold-encoded and
  exchanged through byte-balanced buckets.

Acceptance: dispatches-per-optimizer-step == 1 at G=4 and G=8 for the
accumulated arm (vs G for naive), effective-batch/s ≥ 1.3× naive at
both G, and the compiled step's HLO passes the structural overlap
assertion (bucket k's collective scheduled before bucket k+1's encode
— `parallel.buckets.check_overlap_structure`). Also reports the
per-bucket encoded-bytes ledger from the encoder state.

The **sparse-wire arm** (ISSUE 17) measures the ragged wire format
against the dense pmean baseline at the MEASURED nnz: per-worker
per-bucket wire bytes ((capacity + header) int32 slots vs 4 bytes per
element dense), the nnz ledger those bytes track, and the wall cost of
an elastic re-form (mid-run JOIN: drain save + leader commit + mesh
rebuild 4→8 devices + encoder re-stack + re-place). Headline `value`
is the dense/wire byte ratio (higher = fewer bytes on the wire).

Run:  JAX_PLATFORMS=cpu python bench_multihost.py
"""
import argparse
import json
import os
import time

# 8 virtual devices BEFORE jax initializes (mirror tests/conftest.py)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_xf = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _xf:
    os.environ["XLA_FLAGS"] = (
        _xf + " --xla_force_host_platform_device_count=8").strip()

import numpy as np

# bench.py is import-safe (no device init at module scope) — share THE
# windowing helper instead of copying it, so the methodology cannot
# drift between benches
from bench import _median_of_windows

G_VALUES = (4, 8)
MICRO_BATCH = 64
FEATURES = 256
HIDDEN = 256
CLASSES = 16
STEPS_PER_WINDOW = 6      # effective (super-batch) steps per window
NUM_BUCKETS = 4
SPEEDUP_TARGET = 1.3


def _loss_fn(params, batch, rng):
    import jax
    import jax.numpy as jnp
    h = jnp.tanh(batch["x"] @ params["W1"] + params["b1"])
    logits = h @ params["W2"] + params["b2"]
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.sum(batch["y"] * logp, -1))


def _init_params(seed=0):
    r = np.random.default_rng(seed)
    return {
        "W1": (r.standard_normal((FEATURES, HIDDEN)) * 0.05
               ).astype(np.float32),
        "b1": np.zeros(HIDDEN, np.float32),
        "W2": (r.standard_normal((HIDDEN, CLASSES)) * 0.05
               ).astype(np.float32),
        "b2": np.zeros(CLASSES, np.float32),
    }


def _micro_batches(g, seed=1):
    r = np.random.default_rng(seed)
    xs = r.standard_normal((g, MICRO_BATCH, FEATURES)).astype(np.float32)
    ys = np.eye(CLASSES, dtype=np.float32)[
        r.integers(0, CLASSES, (g, MICRO_BATCH))]
    return xs, ys


def _make_trainer(g):
    from deeplearning4j_tpu.nn.updaters import Sgd
    from deeplearning4j_tpu.parallel.multihost import MultiHostTrainer
    return MultiHostTrainer(
        _loss_fn, Sgd(0.05), compress=True, accumulation=g,
        buckets=NUM_BUCKETS, compression_kw={"initial_threshold": 1e-4})


def _bench_arms(g):
    """Naive (G per-microbatch optimizer steps) vs accumulated (one
    jitted step per effective batch) at accumulation G. Returns the
    per-arm rates + dispatch counts + the accumulated trainer's wire
    ledger and HLO overlap verdict."""
    import jax

    from deeplearning4j_tpu.parallel.buckets import \
        check_overlap_structure
    from deeplearning4j_tpu.parallel.multihost import global_batch

    xs, ys = _micro_batches(g)
    key = jax.random.PRNGKey(0)

    # -- accumulated arm -------------------------------------------------
    acc = _make_trainer(g)
    p, s = acc.init(_init_params())
    super_batch = global_batch(acc.mesh, {"x": xs, "y": ys},
                               accumulation=g)
    step = acc.make_step()
    dispatches = {"accum": 0}

    def accum_step(p, s, rng):
        dispatches["accum"] += 1
        return step(p, s, super_batch, rng)

    p, s, _ = accum_step(p, s, key)          # warm the compile
    jax.block_until_ready(p)
    hlo = step.lower(p, s, super_batch, key).compile().as_text()
    overlap_problems = check_overlap_structure(
        hlo, acc.bucket_plan.num_buckets)
    # settle after the HLO lowering (it compiles a second executable,
    # which would otherwise cold-start the first timed window)
    p, s, _ = accum_step(p, s, key)
    jax.block_until_ready(p)

    def accum_window(i):
        nonlocal p, s
        dispatches["accum"] = 0
        t0 = time.perf_counter()
        for n in range(STEPS_PER_WINDOW):
            p, s, loss = accum_step(p, s, jax.random.fold_in(key, n))
        jax.block_until_ready(loss)
        wall = time.perf_counter() - t0
        assert dispatches["accum"] == STEPS_PER_WINDOW
        return STEPS_PER_WINDOW / wall

    acc_rate, acc_vals, acc_spread = _median_of_windows(accum_window)
    ledger = acc.encoder_stats(s)

    # -- naive arm: G separate optimizer steps per effective batch ------
    naive = _make_trainer(1)
    np_, ns_ = naive.init(_init_params())
    micro = [global_batch(naive.mesh, {"x": xs[i], "y": ys[i]})
             for i in range(g)]
    nstep = naive.make_step()

    def naive_effective_batch(p, s, rng):
        for i in range(g):
            dispatches["naive"] += 1
            p, s, loss = nstep(p, s, micro[i],
                               jax.random.fold_in(rng, i))
        return p, s, loss

    dispatches["naive"] = 0
    np_, ns_, _ = naive_effective_batch(np_, ns_, key)   # warm
    jax.block_until_ready(np_)

    def naive_window(i):
        nonlocal np_, ns_
        dispatches["naive"] = 0
        t0 = time.perf_counter()
        for n in range(STEPS_PER_WINDOW):
            np_, ns_, loss = naive_effective_batch(
                np_, ns_, jax.random.fold_in(key, n))
        jax.block_until_ready(loss)
        wall = time.perf_counter() - t0
        assert dispatches["naive"] == STEPS_PER_WINDOW * g
        return STEPS_PER_WINDOW / wall

    nv_rate, nv_vals, nv_spread = _median_of_windows(naive_window)

    return {
        "accumulation": g,
        "accum_steps_per_s": round(acc_rate, 2),
        "accum_windows": [round(v, 2) for v in acc_vals],
        "accum_spread_pct": round(acc_spread * 100, 1),
        "naive_steps_per_s": round(nv_rate, 2),
        "naive_windows": [round(v, 2) for v in nv_vals],
        "naive_spread_pct": round(nv_spread * 100, 1),
        "speedup": round(acc_rate / nv_rate, 2),
        "dispatches_per_opt_step": {"accum": 1, "naive": g},
        "num_buckets": acc.bucket_plan.num_buckets,
        "bucket_bytes": list(acc.bucket_plan.bucket_bytes),
        "bucket_encoded_bytes": ledger["bucket_encoded_bytes"],
        "encoded_bytes": ledger["encoded_bytes"],
        "overlap_structure_ok": not overlap_problems,
        "overlap_problems": overlap_problems,
    }


def _bench_sparse_wire(wire_capacity=0.05, steps=8):
    """Sparse ragged wire vs the dense pmean baseline at the measured
    nnz, on the same bucketed MLP: the dense exchange moves 4 bytes per
    PARAMETER per worker per step regardless of sparsity; the sparse
    wire moves (capacity + header) int32 slots per bucket — sized to
    the nnz ledger, not the parameter count."""
    import jax

    from deeplearning4j_tpu.nn.updaters import Sgd
    from deeplearning4j_tpu.parallel.multihost import (MultiHostTrainer,
                                                       global_batch)
    tr = MultiHostTrainer(
        _loss_fn, Sgd(0.05), compress=True, buckets=NUM_BUCKETS,
        wire="sparse", wire_capacity=wire_capacity,
        compression_kw={"initial_threshold": 1e-4})
    p, s = tr.init(_init_params())
    xs, ys = _micro_batches(1)
    batch = global_batch(tr.mesh, {"x": xs[0], "y": ys[0]})
    key = jax.random.PRNGKey(0)
    for n in range(steps):
        p, s, loss = tr.fit_batch(p, s, batch, jax.random.fold_in(key, n))
    jax.block_until_ready(loss)
    ledger = tr.encoder_stats(s)
    return {
        "wire_capacity_frac": wire_capacity,
        "wire_capacity_tokens": ledger["wire_capacity"],
        "nnz": ledger["nnz"],
        "nnz_wire_cost_bytes": ledger["encoded_bytes"],
        "wire_bytes": ledger["wire_bytes"],
        "dense_bytes": ledger["dense_bytes"],
        "dense_over_wire": round(
            ledger["dense_bytes"] / ledger["wire_bytes"], 2),
        "bucket_wire_bytes": ledger["bucket_wire_bytes"],
    }


def _bench_elastic_reform():
    """Wall cost of one mid-run JOIN re-form (drain save + leader
    commit + trainer rebuild on the widened 4→8-device mesh + encoder
    re-stack + re-place), measured around the runner's own `_reform` on
    the live coordination-KV flow."""
    import tempfile
    import threading

    import jax

    from deeplearning4j_tpu.nn.updaters import Sgd
    from deeplearning4j_tpu.parallel.multihost import (ElasticMembership,
                                                       LocalKV,
                                                       MultiHostRunner,
                                                       MultiHostTrainer,
                                                       PeerCoordinator,
                                                       global_batch)
    from jax.sharding import Mesh

    def mesh_factory(members):
        return Mesh(np.array(jax.devices()[:4 * len(members)]), ("dp",))

    kv, tmp = LocalKV(), tempfile.mkdtemp()
    c0 = PeerCoordinator(sync_every=2, peer_timeout=8.0, client=kv,
                         process_id=0, num_processes=1, dump_dir=tmp)
    tr = MultiHostTrainer(_loss_fn, Sgd(0.05), compress=True,
                          mesh=mesh_factory([0]), buckets=NUM_BUCKETS,
                          compression_kw={"initial_threshold": 1e-4})
    runner = MultiHostRunner(tr, tmp + "/ck", c0, save_every=100,
                             elastic=True, mesh_factory=mesh_factory,
                             monitor=False, sigterm=False)
    p, s = runner.resume_or_init(_init_params())
    xs, ys = _micro_batches(1)
    key = jax.random.PRNGKey(0)

    reform_ms = []
    orig = runner._reform

    def timed(*a, **k):
        t0 = time.perf_counter()
        out = orig(*a, **k)
        reform_ms.append((time.perf_counter() - t0) * 1000.0)
        return out

    runner._reform = timed

    def joiner():
        c1 = PeerCoordinator(sync_every=2, peer_timeout=12.0, client=kv,
                             process_id=1, num_processes=1, dump_dir=tmp)
        m1 = ElasticMembership(c1, members=[1])
        m1.announce_join()
        info = m1.await_admission(timeout=30.0)
        c1.step, c1.rounds = int(info["cstep"]), int(info["rounds"])
        # the runner drives 4 more fit_batch after the step-2 re-form
        # (sync_every=2 → 2 rounds): pump exactly those, or the runner
        # times out on a missing heartbeat and spuriously replaces us
        for _ in range(4):
            c1.on_step()

    t = threading.Thread(target=joiner)
    t.start()
    time.sleep(0.3)      # let the announcement land before step 1
    for n in range(6):   # the join lands at the first sync boundary
        batch = global_batch(runner.trainer.mesh,
                             {"x": xs[0], "y": ys[0]})
        p, s, _ = runner.fit_batch(p, s, batch,
                                   jax.random.fold_in(key, n))
    t.join(timeout=60)
    runner.close()
    assert reform_ms, "the join never re-formed — bench harness bug"
    return {"join_reform_ms": round(reform_ms[0], 1),
            "dp_after": int(s["encoder"]["threshold"].shape[0])}


def run():
    import jax
    result = {
        "devices": len(jax.devices()),
        "micro_batch": MICRO_BATCH,
        "model": f"mlp {FEATURES}x{HIDDEN}x{CLASSES}",
        "steps_per_window": STEPS_PER_WINDOW,
    }
    for g in G_VALUES:
        result[f"g{g}"] = _bench_arms(g)
    result["sparse_wire"] = _bench_sparse_wire()
    result["elastic_reform"] = _bench_elastic_reform()
    # artifact headline: the dense/wire byte ratio at the measured nnz
    # (higher is better)
    result["value"] = result["sparse_wire"]["dense_over_wire"]
    result["metric"] = "dense_bytes / sparse_wire_bytes"
    result["unit"] = "x"
    return result


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.parse_args()
    result = run()
    print(json.dumps(result))
    bad = []
    for g in G_VALUES:
        arm = result[f"g{g}"]
        if arm["speedup"] < SPEEDUP_TARGET:
            bad.append(f"g{g} speedup {arm['speedup']} < "
                       f"{SPEEDUP_TARGET}")
        if not arm["overlap_structure_ok"]:
            bad.append(f"g{g} overlap structure: "
                       + "; ".join(arm["overlap_problems"]))
    sw = result["sparse_wire"]
    if sw["wire_bytes"] >= sw["dense_bytes"]:
        bad.append(f"sparse wire moved {sw['wire_bytes']} bytes ≥ dense "
                   f"{sw['dense_bytes']} — the ragged format lost its "
                   f"reason to exist")
    if bad:
        raise SystemExit("bench targets missed: " + " | ".join(bad))


if __name__ == "__main__":
    main()
