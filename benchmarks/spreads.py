#!/usr/bin/env python3
"""Where the serving bounds of `BENCHMARK.json` come from.

`benchmarks/spreads.json` holds the chip runs the bounds were set from, in
sets of six at the committed window, every run's value and the machine it
ran on, and the rule's constants (here they are only read). This module is
the rule that turns the runs into bounds, and the loop that makes them:

    python3 benchmarks/spreads.py rule
        prints each set's spreads, what the driver's check would read of
        each cell on each machine, and each metric's bound
    python3 benchmarks/spreads.py measure --workload <cell> --seconds <s> \\
            --seeds 1,2,3 --set <label> --out chiprun_out/spreads/<file>.jsonl
        one `benchmarks/run.py` process a seed, one after another (a chip
        belongs to one process: this one never touches jax); a line a run
    python3 benchmarks/spreads.py collect <file.jsonl>...
        puts those runs' sets into `spreads.json` (a set is named by its
        cell, label, window and machine; one that is there is replaced)

The rule. The driver's check of a benchmark reads a cell on one machine as
two sets of six runs, and refuses a bound as too tight where the mean of the
two sets' spreads, each set without its run farthest from its median, is
over half the bound. So a cell's READING on a machine is that mean over its
two widest sets there (one set: that set), and a metric's reading is the
widest over the serving cells and the machines. It is taken in two
measures: the distance between the quartiles
(`statistics.quantiles(values, n=4)`; the driver's), and the range (the
issue's, never the narrower of the two on five runs), each over the median.
The bound is the smallest multiple of `step` that is at least
`times_quartiles` x the one and `times_range` x the other, no lower than
`floor` and no higher than `cap`. No run is pooled or dropped but each set's
one farthest, which the driver drops too. More machines can only raise a
reading; a later `benchmark` PR that steadies a cell replaces that cell's
sets and lets the rule give the bound again.

The driver's own check is a machine too. Where it refused a bound as too
tight, `checks` holds what it read: the cell, the metric, the median and the
two sets' spreads in the metric's unit, each without its farthest run. Such
a line asks for `times_check` x its wider spread over the median; where one
spread is `far_off` x the other or more, a few runs read far from the rest
(the host stalled) and the narrower one counts. A `benchmark` PR that
steadies the cell takes the cell's lines out with its sets.

`rule` prints the other end too: the driver refuses a bound as too loose
where it is over `too_loose` x the widest quartile spread of a whole set
that it reads, so each machine's widest set says how high a bound may go
there. That is a check and no input: where a quiet machine's end lies under
the bound the noisy one's reading stands, and `rule` says so."""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
FILE = os.path.join(HERE, "spreads.json")


def quartiles(values):
    """Distance between the first and the third quartile over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def span(values):
    """Range over the median."""
    return (max(values) - min(values)) / statistics.median(values)


def without_farthest(values):
    """The runs without the one farthest from their median."""
    mid = statistics.median(values)
    rest = list(values)
    rest.remove(max(rest, key=lambda v: abs(v - mid)))
    return rest


def values(a_set, metric):
    return [r["metrics"][metric] for r in a_set["runs"]]


def sets_at_window(data, cell):
    return [s for s in data["cells"][cell]
            if s["seconds"] == data["window_seconds"]]


def readings(data, metric, measure):
    """{(cell, machine): the mean, over the cell's two widest sets there, of
    the set's `measure` without its farthest run}."""
    out = {}
    for cell in data["cells"]:
        by_machine = {}
        for s in sets_at_window(data, cell):
            by_machine.setdefault(s["machine"], []).append(
                measure(without_farthest(values(s, metric))))
        for machine, spreads in by_machine.items():
            out[cell, machine] = statistics.mean(sorted(spreads)[-2:])
    return out


def check_readings(data, metric):
    """{(cell, by): what a refusing check of the driver's read}: the wider
    of its two sets' spreads over the median, the narrower where the wider
    is `far_off` x it or more."""
    out = {}
    for c in data.get("checks", []):
        if c["metric"] == metric:
            narrow, wide = sorted(c["spreads"])
            alike = wide < data["rule"]["far_off"] * narrow
            out[c["cell"], c["by"]] = (wide if alike else narrow) / c["median"]
    return out


def bound(data, metric):
    rule = data["rule"]
    need = max([rule["times_quartiles"]
                * max(readings(data, metric, quartiles).values()),
                rule["times_range"]
                * max(readings(data, metric, span).values())]
               + [rule["times_check"] * r
                  for r in check_readings(data, metric).values()])
    steps = math.ceil(need / rule["step"] - 1e-9)
    return round(min(rule["cap"], max(rule["floor"], steps * rule["step"])),
                 6)


def too_loose_over(data, metric):
    """{machine: the bound over which the driver's check would call it too
    loose there}: `too_loose` x the widest whole set's quartile spread."""
    widest = {}
    for cell in data["cells"]:
        for s in sets_at_window(data, cell):
            widest[s["machine"]] = max(widest.get(s["machine"], 0.0),
                                       quartiles(values(s, metric)))
    return {m: data["rule"]["too_loose"] * w for m, w in widest.items()}


def load(path=FILE):
    with open(path) as f:
        return json.load(f)


# -- the runs ---------------------------------------------------------------
def _machine():
    """What tells one chip machine (lease) from another."""
    try:
        with open("/proc/sys/kernel/random/boot_id") as f:
            boot = f.read().strip()
    except OSError:
        boot = "unknown"
    return f"{os.uname().nodename}/{boot}"


def measure(args):
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    machine, bad = _machine(), 0
    for seed in (int(s) for s in args.seeds.split(",")):
        cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
               args.workload, "--seed", str(seed), "--seconds",
               str(args.seconds), "--trace", str(args.trace)]
        t = time.time()
        done = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                              text=True)
        lines = done.stdout.strip().splitlines()
        line = {"workload": args.workload, "seconds": args.seconds,
                "seed": seed, "set": args.set, "trace": args.trace,
                "machine": machine, "at": round(t), "rc": done.returncode,
                "wall_s": round(time.time() - t, 2)}
        try:
            result = json.loads(lines[-1])
            line.update(correct=result["correct"], failed=result["failed"],
                        attempted=result["attempted"],
                        device=result["device"],
                        metrics={k: v["value"]
                                 for k, v in result["metrics"].items()})
            if "breakdown" in result:
                line["breakdown"] = result["breakdown"]
        except (IndexError, ValueError, KeyError):
            line["tail"] = lines[-30:]
        if not line.get("correct"):
            bad += 1
            line.setdefault("tail", lines[-30:])
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps({k: line.get(k) for k in (
            "workload", "seconds", "seed", "set", "rc", "correct",
            "wall_s", "metrics")}), flush=True)
    return 1 if bad else 0


def collect(args):
    data, new = load(), {}
    for path in args.files:
        with open(path) as f:
            for r in map(json.loads, f):
                if r["trace"] or not r.get("correct"):
                    continue
                key = (r["workload"], r["set"], r["seconds"], r["machine"])
                new.setdefault(key, []).append(
                    {"seed": r["seed"], "metrics": r["metrics"]})
    for (cell, label, seconds, machine), runs in new.items():
        kept = [s for s in data["cells"].setdefault(cell, [])
                if (s["set"], s["seconds"], s["machine"])
                != (label, seconds, machine)]
        data["cells"][cell] = kept + [{"set": label, "seconds": seconds,
                                       "machine": machine, "runs": runs}]
    with open(FILE, "w") as f:
        json.dump(data, f, indent=1)
        f.write("\n")
    return 0


def rule(_args):
    data = load()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    held = {m["name"]: m["bound"] for m in manifest["end_to_end"]}
    metrics, consts = data["rule"]["metrics"], data["rule"]
    print(f"window {data['window_seconds']} s, run_seconds "
          f"{manifest['run_seconds']}; rule {consts}")
    print("a set: median; quartiles and range without the farthest run; "
          "quartiles of all its runs (%)")
    for cell, sets in sorted(data["cells"].items()):
        for s in sets:
            print(f"{cell} {s['set']} ({s['seconds']} s, {len(s['runs'])} "
                  f"runs, {s['machine'][-12:]}): " + "; ".join(
                      f"{m} {statistics.median(v):.6g}: "
                      f"{100 * quartiles(without_farthest(v)):.2f} "
                      f"{100 * span(without_farthest(v)):.2f} "
                      f"{100 * quartiles(v):.2f}"
                      for m in metrics for v in [values(s, m)]))
    bad = 0
    for m in metrics:
        by_q, by_r = readings(data, m, quartiles), readings(data, m, span)
        for key in sorted(by_q):
            print(f"{m} reading on {key[1][-12:]} of {key[0]}: quartiles "
                  f"{100 * by_q[key]:.3f} %, range {100 * by_r[key]:.3f} %")
        by_c = check_readings(data, m)
        for key, r in sorted(by_c.items()):
            print(f"{m} read by {key[1]} of {key[0]}: {100 * r:.3f} %")
        b = bound(data, m)
        same = "as committed" if held[m] == b else f"COMMITTED {held[m]}"
        bad += held[m] != b
        print(f"{m}: {consts['times_quartiles']} x "
              f"{100 * max(by_q.values()):.3f} %, {consts['times_range']} x "
              f"{100 * max(by_r.values()):.3f} %" + (
                  f", {consts['times_check']} x "
                  f"{100 * max(by_c.values()):.3f} % (a check)"
                  if by_c else "") + f" -> bound {b} ({same})")
        for machine, top in sorted(too_loose_over(data, m).items()):
            print(f"{m} too loose on {machine[-12:]} over "
                  f"{100 * top:.2f} %" + (
                      ": UNDER THE BOUND, the reading stands"
                      if top < b else ""))
    return 1 if bad else 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="what", required=True)
    sub.add_parser("rule")
    m = sub.add_parser("measure")
    m.add_argument("--workload", required=True)
    m.add_argument("--seconds", type=int, required=True)
    m.add_argument("--seeds", required=True)
    m.add_argument("--set", required=True)
    m.add_argument("--trace", type=int, choices=(0, 1), default=0)
    m.add_argument("--out", required=True)
    c = sub.add_parser("collect")
    c.add_argument("files", nargs="+")
    args = ap.parse_args(argv)
    return {"rule": rule, "measure": measure, "collect": collect}[
        args.what](args)


if __name__ == "__main__":
    sys.exit(main())
