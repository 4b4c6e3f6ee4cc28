#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell's file under workloads/ names its
configuration (configs/) and its driver (drivers/), the configuration its
family (families/), and each per-layer metric's file under layer_metrics/
its reader (readers/). Adding one more of any of them edits nothing here.

Runs only on a TPU that has the cell's chips; anywhere else it exits 1 and
prints no result. The last line of standard output is the result."""
from __future__ import annotations

import time

T_START = time.time()        # the process's start: log lines count from it
#: set-up is counted from here. `main` moves it to the moment the TPU
#: runtime is up (`import jax` and the first `jax.devices()`): that start-up
#: took the chip machine 10 to 16 s and shifted by 5 s from one quarter of an
#: hour to the next with no change of code (PERF.md, PR 25), which no bound
#: of 10 % on a set-up of 20 s survives and no PR to the program can move
T_UP = T_START

import argparse              # noqa: E402
import importlib             # noqa: E402
import json                  # noqa: E402
import os                    # noqa: E402
import sys                   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(HERE, ".cache")     # git-ignored; executables, traces


def say(msg):
    """A line of the run's log, stamped with the seconds since the
    process started, so that a slow set-up shows which phase was slow."""
    print(f"[bench +{time.time() - T_START:7.2f}s] {msg}", flush=True)


def load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def metrics_of(manifest_, cell, section):
    """The manifest's metrics of `section` that `cell` reports."""
    return [m for m in manifest_[section]
            if cell in m.get("workloads", [cell])]


def device_info():
    import jax
    devs = jax.devices()
    return devs, {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}


def run_cell(name, workload, config, seed, seconds, trace_on, devices,
             device, cache_dir=CACHE, on_chip=True):
    """Set up, warm, check, measure, count. Returns the result object.
    (`benchmarks/tests/` calls this with toy files on the CPU.)"""
    from benchmarks.harness import trace

    built = importlib.import_module(
        f"benchmarks.families.{config['family']}").build(config, seed)
    say("built")
    driver = importlib.import_module(
        f"benchmarks.drivers.{workload['driver']}").Driver(
            built, workload, seed, cache_dir, on_chip)
    driver.setup()
    say("driver set up")
    warm = driver.warm()
    say(f"warm-up: {warm}")
    checked = driver.check()
    say(f"checked: {checked}")
    tracer = None
    if trace_on:
        tracer = trace.Tracer(os.path.join(cache_dir, "trace", name))
    end_to_end = driver.measure(seconds, tracer)
    # set-up runs from the runtime's coming up to the opening of the
    # window, so it holds the driver's warm traffic or first steps too
    end_to_end["setup_s"] = driver.opened_wall - T_UP
    correct, attempted, failed = driver.counts()
    for note in driver.notes:
        say(note)
    say(f"end to end: {end_to_end}")

    section = "per_layer" if trace_on else "end_to_end"
    units = {m["name"]: m["unit"]
             for m in metrics_of(manifest(), name, section)}
    result = {"correct": bool(checked and correct), "attempted": attempted,
              "failed": failed}
    device = dict(device)
    # the allocator's peak leaves out the region the runtime reserves for
    # the programs' temporaries (8.8 GB for the ResNet-50 step), which it
    # reports beside it: the chip's peak is both
    peak = 0
    for d in devices:
        mem = d.memory_stats() or {}
        peak = max(peak, mem.get("peak_bytes_in_use", 0)
                   + mem.get("peak_bytes_reserved", 0))
    device["memory_peak_bytes"] = peak
    if not trace_on:
        values = {k: end_to_end[k] for k in units if k in end_to_end}
    else:
        summary = tracer.reduce()
        if summary is None:
            raise RuntimeError("the traced run recorded no device operation")
        ctx = {"trace": summary, "driver": driver.context, "built": built,
               "config": config, "workload": workload,
               "device_kind": device["kind"]}
        values = {}
        for metric in units:        # each has a file of its name
            spec = load("layer_metrics", metric)
            reader = importlib.import_module(
                f"benchmarks.readers.{spec['reader']}")
            value = reader.read(ctx, spec)
            if value is not None:           # nothing to read: left out
                values[metric] = value
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in values.items()}
    result["device"] = device
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    workload = load("workloads", args.workload)
    config = load("configs", workload["config"])

    global T_UP
    devices, device = device_info()              # the first act on jax
    T_UP = time.time()
    if device["platform"] != "tpu" or device["count"] < workload["chips"]:
        print(f"[bench] need {workload['chips']} TPU chip(s), found "
              f"{device}: no result", file=sys.stderr, flush=True)
        return 1
    say(f"cell {args.workload} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace} on {device}")
    say(f"runtime up {T_UP - T_START:.2f} s after the process started: "
        f"set-up is counted from here")

    # the program's one compile-cache rule: $JAX_COMPILATION_CACHE_DIR where
    # set, else <checkout>/.jax_cache/ (a fixed path inside the checkout)
    from deeplearning4j_tpu.runtime import executables
    say(f"compile cache at {executables.configure_persistent_cache()}")
    # the program keeps what compiles in under 2 s out of the cache (a
    # fault of XLA:CPU's serialized code); on the chip that would compile
    # some fifteen (serving) to ninety (ResNet-50) small programs again in
    # every run, on the host's shared cores
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    result = run_cell(args.workload, workload, config, args.seed,
                      args.seconds, bool(args.trace),
                      devices[:workload["chips"]], device)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
