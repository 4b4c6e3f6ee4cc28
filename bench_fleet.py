#!/usr/bin/env python
"""CPU microbench: fleet routing overhead at equal total slots, plus
time-to-healthy after a replica kill (generation/fleet.py — ISSUE 20),
one JSON artifact.

Two claims under measurement:

1. **Routing is (nearly) free.** A 3-replica fleet of 2-slot servers
   versus ONE bare 2-slot replica over the same 24-request mixed
   workload (greedy + sampled + top-k). The headline `value` is the
   aggregate tok/s RATIO (fleet / single replica). On this single-core
   CPU host the three replicas time-share one core, so the ratio sits
   near 1.0 — what the number guards is ROUTER OVERHEAD (relay
   threads, health scans, the dispatch hook): a collapse means the
   routing hot path regressed. On an N-core (or N-device) host the
   same ratio approaches N — the artifact records the single-core
   floor, not the parallel ceiling. Streams must also be
   BIT-IDENTICAL across the arms: fleet-wide admission ids over
   seed-aligned replicas make a stream a pure function of (seed,
   admit id, prompt, sampling config), so window 0's fleet streams
   must equal the bare replica's token for token — routing must never
   perturb sampling.

2. **Replica loss is repaired in warm-spin-up time.** After the timed
   windows, each measurement kills one idle replica (`_die`), submits
   a probe request (served by a survivor; the router's background
   reviver kicks on the same dispatch), and clocks until the roster is
   back to full healthy strength. Every replacement must report ZERO
   live compiles — spin-up is a disk read from the shared
   FunctionStore, not a compile storm.

Methodology is bench.py's median-of->=5-windows + recorded-spread (a
point sample of a +-20%-noise distribution is not a measurement) for
BOTH metrics.

Run:  JAX_PLATFORMS=cpu python bench_fleet.py
"""
import argparse
import json
import os
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

# bench.py is import-safe (no device init at module scope) — share THE
# windowing helper instead of copying it, so the methodology cannot
# drift between benches
from bench import _median_of_windows

from deeplearning4j_tpu.generation import FleetRouter, GenerationServer
from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.recurrent import LSTM, RnnOutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Adam

VOCAB = 16
HIDDEN = 128
REPLICAS = 3
REPLICA_SLOTS = 2
SINGLE_SLOTS = REPLICA_SLOTS     # the single arm IS one bare replica
N_REQUESTS = 24
SEED = 11

# mixed sampling methods: the cross-arm identity assertion must cover
# the admission-id-dependent paths (sampled rngs), not just greedy.
# Budgets are sized so one window decodes ~500 tokens — long enough
# that the per-window rate is not a point sample of dispatch jitter
_MIX = [
    dict(prompt=[1, 2, 3], max_new_tokens=24),
    dict(prompt=[5, 4], max_new_tokens=20, method="sample",
         temperature=0.8),
    dict(prompt=[7, 3, 2, 1], max_new_tokens=24, method="top_k",
         temperature=0.9, top_k=3),
    dict(prompt=[2, 2, 5], max_new_tokens=16),
]
WORKLOAD = [dict(_MIX[i % len(_MIX)]) for i in range(N_REQUESTS)]


def _build_net():
    return MultiLayerNetwork(
        (NeuralNetConfiguration.Builder().seed(3).updater(Adam(1e-2))
         .weightInit("xavier").list()
         .layer(LSTM(nOut=HIDDEN, activation="tanh"))
         .layer(RnnOutputLayer(lossFunction="mcxent", nOut=VOCAB,
                               activation="softmax"))
         .setInputType(InputType.recurrent(VOCAB)).build())).init()


def _server(net, cache_dir, slots):
    return GenerationServer(
        net, slots=slots, cache_lengths=[48], prompt_buckets=[8],
        method="greedy", seed=SEED, exec_cache_dir=cache_dir)


def _serve_mix(submit):
    """One timed window: submit the whole 24-request mix through
    `submit`, consume every stream. Returns (streams, tok/s)."""
    t0 = time.perf_counter()
    reqs = [submit(**dict(w)) for w in WORKLOAD]
    streams = [r.result(timeout=300) for r in reqs]
    dt = time.perf_counter() - t0
    toks = sum(len(s) for s in streams)
    return streams, toks / dt


def _run_arm(submit, k_windows=5):
    """Median tokens/s over independent windows, after ONE untimed
    warm pass (`warmup()` compiles the greedy path; the sampled
    methods trace on first use, and that must not land inside a timed
    window). Window 0's streams ride along for the cross-arm identity
    verdict: both arms advance their admission counters 24 ids per
    pass, so window 0 spans ids [24, 48) in each — directly comparable
    even for sampled streams."""
    _serve_mix(submit)
    state = {"streams": None}

    def window(i):
        streams, rate = _serve_mix(submit)
        if i == 0:
            state["streams"] = streams
        return rate

    rate, vals, spread = _median_of_windows(window, k=k_windows)
    return {"rate": rate, "windows": [round(v, 1) for v in vals],
            "spread_pct": round(spread * 100, 1),
            "streams": state["streams"]}


def _time_to_healthy(router, k_windows=5):
    """Median ms from killing one idle replica to a fully-healthy
    roster again. The probe request lands on a survivor and kicks the
    background reviver; the replacement must warm from the shared disk
    store with zero live compiles."""
    zero_compile = [True]

    def window(i):
        victim = router._replicas[1 + i % (REPLICAS - 1)]
        victim.server._die(RuntimeError("bench kill"))
        t0 = time.perf_counter()
        router.submit(**dict(WORKLOAD[0])).result(timeout=60)
        deadline = t0 + 60
        while time.perf_counter() < deadline:
            if all(r["health"] == "healthy"
                   for r in router.status()["replicas"]):
                break
            time.sleep(0.002)
        dt_ms = (time.perf_counter() - t0) * 1e3
        assert all(r["health"] == "healthy"
                   for r in router.status()["replicas"]), \
            "roster never returned to healthy"
        if victim.server._store.stats["compiles"] != 0:
            zero_compile[0] = False
        return dt_ms

    ms, vals, spread = _median_of_windows(window, k=k_windows)
    return {"median_ms": round(ms, 1),
            "windows_ms": [round(v, 1) for v in vals],
            "spread_pct": round(spread * 100, 1),
            "kills": len(vals), "zero_compile": zero_compile[0]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="BENCH_FLEET_fresh.json")
    ap.add_argument("--windows", type=int, default=5)
    args = ap.parse_args(argv)

    net = _build_net()
    cache_dir = tempfile.mkdtemp(prefix="bench-fleet-exec-")

    print(f"# single arm: 1 server x {SINGLE_SLOTS} slots")
    single_srv = _server(net, cache_dir, SINGLE_SLOTS)
    single_srv.warmup()
    try:
        single = _run_arm(single_srv.submit, k_windows=args.windows)
    finally:
        single_srv.shutdown()
    print(f"# single: {single['rate']:.1f} tok/s "
          f"(spread {single['spread_pct']}%)")

    print(f"# fleet arm: {REPLICAS} replicas x {REPLICA_SLOTS} slots")
    router = FleetRouter(
        factory=lambda i: _server(net, cache_dir, REPLICA_SLOTS),
        num_replicas=REPLICAS, restart_budget=12)
    warm = router.warmup()
    try:
        fleet = _run_arm(router.submit, k_windows=args.windows)
        print(f"# fleet: {fleet['rate']:.1f} tok/s "
              f"(spread {fleet['spread_pct']}%)")
        healthy = _time_to_healthy(router, k_windows=args.windows)
        replacements = router.status()["replacements"]
    finally:
        router.shutdown()
    print(f"# time-to-healthy: {healthy['median_ms']} ms median over "
          f"{healthy['kills']} kills")

    identical = single["streams"] == fleet["streams"]
    assert identical, "fleet streams diverged from the bare server"
    assert healthy["zero_compile"], \
        "a replacement replica compiled live instead of warming " \
        "from the shared disk store"
    value = round(fleet["rate"] / single["rate"], 3)
    # single-core host: the three replicas time-share one core, so no
    # parallel speedup exists to claim — the ratio guards ROUTER
    # OVERHEAD, and falling far below 1.0 means the
    # relay/health/dispatch path regressed catastrophically
    assert value >= 0.5, f"fleet routing overhead ratio {value}"
    assert healthy["median_ms"] < 10_000, healthy

    doc = {
        "model": f"lstm_h{HIDDEN}_v{VOCAB}",
        "requests": N_REQUESTS,
        "single": {"slots": SINGLE_SLOTS,
                   "tok_per_s": round(single["rate"], 1),
                   "windows": single["windows"],
                   "spread_pct": single["spread_pct"]},
        "fleet": {"replicas": REPLICAS, "slots": REPLICA_SLOTS,
                  "tok_per_s": round(fleet["rate"], 1),
                  "windows": fleet["windows"],
                  "spread_pct": fleet["spread_pct"],
                  "warmup": warm,
                  "replacements": replacements},
        "time_to_healthy": healthy,
        "token_identity": {"requests": N_REQUESTS,
                           "identical": identical},
        "value": value,
        "metric": "fleet_3_replicas_vs_1_aggregate_tok_per_s",
        "unit": "x",
        "provenance": {"host": "cpu-1core", "jax": jax.__version__,
                       "windows": args.windows},
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"# headline: {value}x aggregate tok/s at equal slots, "
          f"{healthy['median_ms']} ms to healthy -> {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
