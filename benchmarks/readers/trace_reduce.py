"""Per-layer metrics read from the reduced profiler trace (`ctx["trace"]`,
see harness/trace.py). Nothing traced, or nothing to tell the programs
apart by, gives None and the metric is left out."""
from __future__ import annotations


def dominant(tr):
    """The program that took most device time in the span: in a training
    cell, the train step."""
    if not tr or not tr["programs"]:
        return None
    return max(tr["programs"].values(), key=lambda p: p["seconds"])


def programs_of(ctx, spec):
    """The traced programs of `spec["role"]`: those a driver's
    `after:<role>` marks point at; or, with `heaviest_without`, the one
    with most device time among the programs no mark of those roles points
    at (the decode superstep, once the admit programs are set aside: the
    only other program of a healthy server's window is the tiny `retire`).
    Only a driver that says it writes those marks (`context["marks"]`) is
    believed when none is in the span; otherwise the programs cannot be
    told apart and nothing is reported."""
    progs = ctx["trace"]["programs"].values()
    marked = ctx["driver"].get("marks", ())
    if "heaviest_without" in spec:
        if not all(r in marked for r in spec["heaviest_without"]):
            return []
        rest = [p for p in progs if not any(
            r in p.get("roles", ()) for r in spec["heaviest_without"])]
        return [max(rest, key=lambda p: p["seconds"])] if rest else []
    return [p for p in progs if spec["role"] in p.get("roles", ())]


def read(ctx, spec):
    tr = ctx["trace"]
    if tr is None:
        return None
    key = spec["key"]
    if key == "idle_share":
        return tr["idle_share"]
    if key == "step_busy_ms":
        # device busy time between the first and the last start of the
        # step program, over the whole steps in between
        p = dominant(tr)
        if p is None or p["count"] < 2:
            return None
        return 1e3 * p["busy_between"] / (p["count"] - 1)
    if key == "program_ms":
        # mean device time of one execution of the programs of a role
        runs = programs_of(ctx, spec)
        count = sum(p["count"] for p in runs)
        return (1e3 * sum(p["seconds"] for p in runs) / count
                if count else None)
    raise ValueError(f"trace_reduce: unknown key {key!r}")
