#!/usr/bin/env python
"""CPU microbench: serving cold-start + steady-state latency with the
AOT executable cache (runtime/executables.py + parallel/inference.py).

Three measurements, one JSON line:

- **cold_start_s** — construct `ParallelInference` over an EMPTY
  executable cache, `warmup()` the bucket ladder (every rung pays a
  live trace + XLA compile), then serve the first request: the
  compile-before-the-first-served-step cost, scaled to a CPU-sized
  model. Model construction is
  reported separately (`model_build_s`) — a real replica restores a
  checkpoint; the cache's job is the compile side of cold start.
- **warm_start_s** — a "restarted replica": fresh model object, fresh
  ParallelInference, in-process jit caches dropped
  (`jax.clear_caches()`), pointed at the now-warm on-disk cache. The
  same `warmup()` deserializes every rung instead of compiling.
  Acceptance target: cold/warm >= 5x.
- **steady-state latency** — p50/p99 over a stream of mixed-size
  requests inside the ladder (zero compiles; asserted), plus the
  padding-waste ratio padded_rows / (rows + padded_rows) the ladder
  spends to keep the executable set closed.

Run:  JAX_PLATFORMS=cpu python bench_serving.py
"""
import argparse
import json
import os
import shutil
import tempfile
import time

import numpy as np


def _build_net(seed=7):
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       NeuralNetConfiguration, OutputLayer,
                                       Sgd)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    conf = (NeuralNetConfiguration.Builder()
            .seed(seed).updater(Sgd(0.05)).activation("relu")
            .list()
            .layer(DenseLayer.Builder().nOut(512).build())
            .layer(DenseLayer.Builder().nOut(512).build())
            .layer(DenseLayer.Builder().nOut(512).build())
            .layer(OutputLayer.Builder("mcxent").nOut(10)
                   .activation("softmax").build())
            .setInputType(InputType.feedForward(256))
            .build())
    return MultiLayerNetwork(conf).init()


def _start_replica(cache_dir, ladder):
    """Fresh replica against `cache_dir`: returns (pi, model-build
    seconds, serving cold-start seconds — ParallelInference
    construction through warmup to FIRST SERVED RESPONSE — and the
    warmup stats). Model build is timed separately: a real replica
    restores params from a checkpoint; the executable cache's job is
    the compile side."""
    from deeplearning4j_tpu.parallel.inference import ParallelInference
    t0 = time.perf_counter()
    net = _build_net()
    t1 = time.perf_counter()
    pi = (ParallelInference.Builder(net)
          .bucketLadder(ladder).executableCacheDir(cache_dir).build())
    stats = pi.warmup()
    first = pi.output(np.zeros((1, 256), np.float32))
    assert first.shape == (1, 10)
    return pi, t1 - t0, time.perf_counter() - t1, stats


def run(requests=200, seed=0):
    import jax

    from deeplearning4j_tpu import monitoring as mon
    ladder = [1, 2, 4, 8, 16, 32]
    work = tempfile.mkdtemp(prefix="dl4j-bench-serving-")
    # the executable store starts EMPTY and jax's persistent compile
    # cache is OFF for the run, so the cold arm is honestly cold
    from jax.experimental.compilation_cache import compilation_cache as _cc
    jax.config.update("jax_enable_compilation_cache", False)
    _cc.reset_cache()
    exec_dir = os.path.join(work, "exec")
    try:
        pi, build_cold, cold_s, cold_stats = _start_replica(exec_dir,
                                                            ladder)
        assert cold_stats["from_disk"] == 0
        pi.shutdown()

        # restarted replica: drop every in-process cache, keep disk
        jax.clear_caches()
        pi, build_warm, warm_s, warm_stats = _start_replica(exec_dir,
                                                            ladder)
        assert warm_stats["compiled"] == 0, warm_stats

        # steady state: mixed-size stream, measure per-request latency
        mon.enable()
        reg = mon.get_registry()
        rows0 = reg.counter(mon.SERVING_ROWS).value
        pad0 = reg.counter(mon.SERVING_PADDED_ROWS).value
        compiles0 = pi._store.stats["compiles"]
        rng = np.random.default_rng(seed)
        sizes = rng.integers(1, 33, requests)
        lat = []
        for n in sizes:
            x = rng.standard_normal((int(n), 256)).astype(np.float32)
            t0 = time.perf_counter()
            pi.output(x)
            lat.append(time.perf_counter() - t0)
        assert pi._store.stats["compiles"] == compiles0, \
            "steady state must not compile"
        rows = reg.counter(mon.SERVING_ROWS).value - rows0
        padded = reg.counter(mon.SERVING_PADDED_ROWS).value - pad0
        mon.disable()
        pi.shutdown()
        lat_ms = np.sort(np.asarray(lat)) * 1e3
        return {
            "ladder": ladder,
            "requests": int(requests),
            "model_build_s": {"cold": round(build_cold, 3),
                              "warm": round(build_warm, 3)},
            "cold_start_s": round(cold_s, 3),
            "warm_start_s": round(warm_s, 3),
            "cold_vs_warm_speedup": round(cold_s / warm_s, 2),
            "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
            "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
            "padding_waste_ratio": round(padded / max(1, rows + padded),
                                         4),
            "exec_cache_entries": len(ladder),
        }
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        _cc.reset_cache()
        shutil.rmtree(work, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=200)
    args = ap.parse_args()
    result = run(requests=args.requests)
    print(json.dumps(result))
    if result["cold_vs_warm_speedup"] < 5.0:
        raise SystemExit(
            f"cold-start speedup {result['cold_vs_warm_speedup']}x "
            "below the 5x target")


if __name__ == "__main__":
    main()
