"""Observability disabled-path microbench (CPU): the ISSUE 15 guard,
re-armed for every observability PR since (the ISSUE 19 ops event
journal's emission hooks ride the same enabled-guard and the same two
workloads below).

Request tracing, the cluster metrics plane, and SLO tracking must be
FREE when off — every instrumentation point this PR adds is one
`timeline is None` / enabled-guard branch on the hot path. This bench
proves it empirically, the same way the fastpath lint proves it
structurally: an interleaved A/B between the current tree and a
baseline checkout WITHOUT the observability changes, monitoring
disabled in both arms, on the two hot paths the PR touches:

- **fit50** — the 50-step training fit (the PR 4 guard workload);
- **decode_k8** — steady-state greedy decode at superstep k=8
  (the generation hot path the request timelines ride).

Windows alternate base/head (base, head, base, head, ...) so
shared-box load drift hits both arms equally — single-window numbers
on this class of box swing ±20%. The verdict is "within noise": the
relative delta must not exceed the measured window spread.

Run:  JAX_PLATFORMS=cpu python bench_obs.py [--ref <git-ref>]

`--ref` (default `DL4J_OBS_BASE_REF` or HEAD) names the baseline
commit; with the PR uncommitted in the working tree, HEAD *is* the
pre-observability baseline. After it lands, pass the parent commit.
"""
from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.abspath(__file__))

WINDOWS = int(os.environ.get("DL4J_OBS_BENCH_WINDOWS", "5"))


# ===================== child workloads =================================
def _child_fit50():
    """Median seconds for 50 fit steps (tiny MLP), monitoring off."""
    import numpy as np
    from deeplearning4j_tpu.datasets import DataSet
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       NeuralNetConfiguration,
                                       OutputLayer, Sgd)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.Builder()
            .seed(1).updater(Sgd(0.1)).activation("relu")
            .list()
            .layer(DenseLayer.Builder().nOut(256).build())
            .layer(DenseLayer.Builder().nOut(256).build())
            .layer(OutputLayer.Builder("mcxent").nOut(4)
                   .activation("softmax").build())
            .setInputType(InputType.feedForward(64))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((128, 64)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 128)]
    ds = DataSet(x, y)
    for _ in range(5):                      # warmup: compile + caches
        net.fit(ds)
    vals = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(50):
            net.fit(ds)
        vals.append(time.perf_counter() - t0)
    return statistics.median(vals)


def _child_decode_k8():
    """Median seconds for a 192-token greedy decode at superstep k=8,
    monitoring off; executables come from a per-tree disk store so only
    the first window of each arm pays compiles."""
    from deeplearning4j_tpu.generation import GenerationServer
    from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.recurrent import (LSTM,
                                                      RnnOutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updaters import Adam

    V = 16
    net = MultiLayerNetwork(
        (NeuralNetConfiguration.Builder().seed(3).updater(Adam(1e-2))
         .weightInit("xavier").list()
         .layer(LSTM(nOut=64, activation="tanh"))
         .layer(RnnOutputLayer(lossFunction="mcxent", nOut=V,
                               activation="softmax"))
         .setInputType(InputType.recurrent(V)).build())).init()
    srv = GenerationServer(net, slots=2, cache_lengths=[256],
                           prompt_buckets=[8], method="greedy", seed=11,
                           superstep=8,
                           exec_cache_dir=os.environ.get(
                               "DL4J_OBS_EXEC_CACHE"))
    try:
        srv.warmup()
        srv.generate([1, 4, 2], max_new_tokens=32, timeout=120)
        vals = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(8):
                toks = srv.generate([5, 6, 1], max_new_tokens=240,
                                    timeout=120)
                assert len(toks) == 240
            vals.append(time.perf_counter() - t0)
        return statistics.median(vals)
    finally:
        srv.shutdown()


CHILD_WORKLOADS = {"fit50": _child_fit50, "decode_k8": _child_decode_k8}


def _run_child(workload, tree, exec_cache):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = tree
    env["DL4J_OBS_EXEC_CACHE"] = exec_cache
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", workload],
        env=env, cwd=tempfile.gettempdir(), capture_output=True,
        text=True, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(f"child {workload} failed in {tree}:\n"
                           f"{out.stdout[-2000:]}\n{out.stderr[-2000:]}")
    return float(out.stdout.strip().splitlines()[-1])


def _checkout_base(ref, dst):
    """Materialize the baseline package tree at `ref` into dst."""
    os.makedirs(dst, exist_ok=True)
    tar = subprocess.run(["git", "-C", REPO, "archive", ref,
                          "deeplearning4j_tpu"],
                         capture_output=True, timeout=120)
    if tar.returncode != 0:
        raise RuntimeError(tar.stderr.decode()[-500:])
    subprocess.run(["tar", "-x", "-C", dst], input=tar.stdout,
                   check=True, timeout=120)
    return dst


def _spread(vals):
    m = statistics.median(vals)
    return (max(vals) - min(vals)) / m if m else 0.0


def run(ref):
    results = {"metric": "observability disabled-path overhead",
               "base_ref": ref, "windows": WINDOWS}
    with tempfile.TemporaryDirectory(prefix="dl4j-obs-bench-") as tmp:
        base_tree = _checkout_base(ref, os.path.join(tmp, "base"))
        caches = {"base": os.path.join(tmp, "cache-base"),
                  "head": os.path.join(tmp, "cache-head")}
        for c in caches.values():
            os.makedirs(c, exist_ok=True)
        trees = {"base": base_tree, "head": REPO}
        for workload in ("fit50", "decode_k8"):
            vals = {"base": [], "head": []}
            for i in range(WINDOWS):
                # alternate which arm goes first so slow drift within
                # a round cancels too
                order = ("base", "head") if i % 2 == 0 \
                    else ("head", "base")
                for arm in order:
                    vals[arm].append(_run_child(workload, trees[arm],
                                                caches[arm]))
            base_med = statistics.median(vals["base"])
            head_med = statistics.median(vals["head"])
            delta = (head_med - base_med) / base_med
            noise = max(_spread(vals["base"]), _spread(vals["head"]),
                        0.02)
            results[workload] = {
                "base_s": round(base_med, 4),
                "head_s": round(head_med, 4),
                "base_windows_s": [round(v, 4) for v in vals["base"]],
                "head_windows_s": [round(v, 4) for v in vals["head"]],
                "delta": round(delta, 4),
                "window_spread": round(noise, 4),
                "within_noise": abs(delta) <= noise,
            }
    results["pass"] = all(results[w]["within_noise"]
                          for w in ("fit50", "decode_k8"))
    return results


def main(argv):
    if len(argv) >= 2 and argv[0] == "--child":
        fn = CHILD_WORKLOADS[argv[1]]
        print(fn())
        return 0
    ref = os.environ.get("DL4J_OBS_BASE_REF", "HEAD")
    if len(argv) >= 2 and argv[0] == "--ref":
        ref = argv[1]
    results = run(ref)
    print(json.dumps(results, indent=2))
    return 0 if results["pass"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
