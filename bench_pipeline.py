#!/usr/bin/env python
"""CPU microbench: async host pipeline vs the old per-step-sync loop.

Measures the overlap win the host pipeline (runtime/pipeline.py) buys
against an IO-bound synthetic loader — each `next()` sleeps `io_ms` to
model disk/decode/augment latency, the way a real input pipeline stalls
the host:

- **sync arm** (the pre-pipeline fit loop): prefetch disabled, plus a
  listener that reads `score()` every iteration — i.e. a blocking
  `float(loss)` per step. Each step costs loader + compute, serially.
- **async arm** (the pipeline): listener-free fit with the background
  device-staging prefetcher. Loader latency overlaps device compute, so
  a step costs ~max(loader, compute).

Why a microbench and not the TPU harness: the steady-state overlap has
not been measured on a chip (bench-measurement debt, ROADMAP S0); this
CPU-runnable bench demonstrates the same host-side mechanism anywhere:

    JAX_PLATFORMS=cpu python bench_pipeline.py

Prints one JSON line: steps/s for both arms + speedup. Acceptance
target for the PR: >= 1.3x with the default io-bound loader.
"""
import argparse
import json
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


class SlowLoader:
    """IO-bound DataSetIterator: deterministic in-memory batches plus a
    sleep per next() modelling loader latency (read/decode/augment)."""

    def __init__(self, n_batches, batch=256, n_in=256, n_classes=10,
                 io_ms=12.0, seed=0):
        rng = np.random.default_rng(seed)
        self._x = rng.standard_normal((n_batches, batch, n_in)) \
            .astype(np.float32)
        y = rng.integers(0, n_classes, (n_batches, batch))
        self._y = np.eye(n_classes, dtype=np.float32)[y]
        self._io_s = io_ms / 1e3
        self._cursor = 0

    def batch(self):
        return self._x.shape[1]

    def numExamples(self):
        return self._x.shape[0] * self._x.shape[1]

    def hasNext(self):
        return self._cursor < len(self._x)

    def next(self, num=None):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        time.sleep(self._io_s)     # the modelled IO stall
        ds = DataSet(self._x[self._cursor], self._y[self._cursor])
        self._cursor += 1
        return ds

    def reset(self):
        self._cursor = 0

    def resetSupported(self):
        return True

    def asyncSupported(self):
        return True

    def __iter__(self):
        self.reset()
        return self

    def __next__(self):
        if not self.hasNext():
            raise StopIteration
        return self.next()


class _SyncEveryStep:
    """The old loop's behavior as a listener: float(loss) every step."""

    def iterationDone(self, model, iteration, epoch):
        model.score()


def _time_fit(net, loader, steps, sync):
    t0 = time.perf_counter()
    net.fit(loader, epochs=1, prefetch=0 if sync else None)
    if not sync:
        # flush the async tail so the measurement covers ALL steps'
        # compute, not just their dispatch
        net.score()
    return steps / (time.perf_counter() - t0)


def run(steps=60, io_ms=None, warmup=6, batch=256, n_in=256):
    sync_net, async_net = _build_net(seed=7), _build_net(seed=7)
    sync_net.setListeners(_SyncEveryStep())

    # compile + cache warm for BOTH nets (identical shapes)
    for net in (sync_net, async_net):
        net.fit(SlowLoader(warmup, batch, n_in, io_ms=0.1), epochs=1,
                prefetch=0)
        net.score()

    if io_ms is None:
        # calibrate the IO stall to THIS host's measured step time, so
        # the ideal overlap win (~2x: loader fully hidden behind
        # compute) — and therefore the 1.3x acceptance margin — is
        # machine- and load-independent
        t0 = time.perf_counter()
        async_net.fit(SlowLoader(12, batch, n_in, io_ms=0.0), epochs=1,
                      prefetch=0)
        async_net.score()
        io_ms = max(2.0, (time.perf_counter() - t0) / 12 * 1e3)

    sync_sps = _time_fit(sync_net,
                         SlowLoader(steps, batch, n_in, io_ms=io_ms),
                         steps, sync=True)
    async_sps = _time_fit(async_net,
                          SlowLoader(steps, batch, n_in, io_ms=io_ms),
                          steps, sync=False)
    return {
        "steps": steps,
        "io_ms": round(io_ms, 2),
        "sync_steps_per_s": round(sync_sps, 2),
        "async_steps_per_s": round(async_sps, 2),
        "speedup": round(async_sps / sync_sps, 3),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--io-ms", type=float, default=None,
                    help="IO stall per batch; default: auto-calibrate to the measured step time")
    ap.add_argument("--warmup", type=int, default=6)
    args = ap.parse_args()
    result = run(steps=args.steps, io_ms=args.io_ms, warmup=args.warmup)
    print(json.dumps(result))
    if result["speedup"] < 1.3:
        raise SystemExit(
            f"speedup {result['speedup']}x below the 1.3x target")


if __name__ == "__main__":
    main()
