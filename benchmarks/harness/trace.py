"""Profiler trace: start and stop, the benchmark's own host annotations,
and the reduction from a trace to device busy time, per-program time, the
heaviest device operations and the longest idle gaps.

The reduction reads the trace with `jax.profiler.ProfileData` alone. Times
inside this module are seconds on the trace's own clock.

    python3 benchmarks/harness/trace.py <file.xplane.pb>   # what is in a trace
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import shutil
import sys

if __name__ == "__main__":      # run as a script: find the package
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks.harness import stats

#: every annotation the benchmark writes starts with this, so that the
#: reduction tells them from the program's and jax's own
PREFIX = "bench."
SPAN = PREFIX + "trace-span"
#: `bench.after:<role>` marks the moment right after the host received the
#: result of a program of that role: the program that finished last before
#: the mark is one of that role. (The store names every function `run`, so
#: the programs cannot be told apart by name.)
AFTER = PREFIX + "after:"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TOP_OPS, TOP_GAPS, NAME_CHARS = 8, 5, 120
#: a traced run traces this long, in the middle of the window
TRACE_SECONDS = 3.0


def trace_after(seconds):
    """How long after the window opens a traced run starts tracing."""
    return max(0.5, (seconds - TRACE_SECONDS) / 2)


def annotate(name):
    """A host span in the profiler's trace (costs a flag test when no
    trace is being taken). Idle gaps on the device are named by the
    innermost of these that was open."""
    import jax
    return jax.profiler.TraceAnnotation(PREFIX + name)


class Tracer:
    """One profiler session into a fixed directory, emptied first."""

    def __init__(self, directory):
        self.directory = directory
        self._open = []

    def start(self, inside=None):
        """Start tracing. `inside` names the benchmark annotation that is
        open around the caller now: one opened before the trace started is
        not in it, so it is written again, over the whole span."""
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # the interpreter's own calls: no
        opts.host_tracer_level = 2       # TraceAnnotations: yes
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        for name in [SPAN] + ([PREFIX + inside] if inside else []):
            self._open.append(jax.profiler.TraceAnnotation(name))
            self._open[-1].__enter__()

    def stop(self):
        import jax
        while self._open:
            self._open.pop().__exit__(None, None, None)
        jax.profiler.stop_trace()

    def path(self):
        found = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None

    def reduce(self):
        path = self.path()
        return None if path is None else summarise(*load(path))


def load(path):
    """(ops, modules, annotations) of a trace file: per chip a list of
    (name, start, end) for the `XLA Ops` and the `XLA Modules` lines of
    each TPU plane, and one list for the benchmark's host annotations."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, modules, annotations = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                dest = ops if line.name == OPS_LINE else modules
                dest.setdefault(int(m.group(1)), []).extend(
                    (e.name, e.start_ns / 1e9,
                     (e.start_ns + e.duration_ns) / 1e9)
                    for e in line.events)
            elif not m:
                annotations.extend(
                    (e.name, e.start_ns / 1e9,
                     (e.start_ns + e.duration_ns) / 1e9)
                    for e in line.events if e.name.startswith(PREFIX))
    return ops, modules, annotations


def _innermost(annotations, at):
    """Name (without the prefix) of the shortest benchmark annotation,
    other than the span marker, that was open at time `at`."""
    best = None
    for name, s, e in annotations:
        if name != SPAN and not name.startswith(AFTER) \
                and s <= at <= e and (best is None or e - s < best[1]):
            best = (name[len(PREFIX):], e - s)
    return best[0] if best else "unattributed"


def summarise(ops, modules, annotations):
    """Reduce per-chip op and program intervals to what the readers use.

    The span is the benchmark's own `trace-span` annotation (host and
    device share a clock), cut to where the device's first operation starts
    and its last one ends; without the annotation, just that.
    Returns None when no operation ran on a device."""
    chips = sorted(c for c in ops if ops[c])
    if not chips:
        return None
    first = min(s for c in chips for _, s, _ in ops[c])
    last = max(e for c in chips for _, _, e in ops[c])
    span = next(((s, e) for n, s, e in annotations if n == SPAN), None)
    if span is None or span[1] < first or span[0] > last:
        span = (first, last)
    lo, hi = max(span[0], first), min(span[1], last)
    if hi <= lo:
        return None
    busy = [stats.covered(stats.clip([(s, e) for _, s, e in ops[c]], lo, hi))
            for c in chips]
    by_op = collections.Counter()
    for c in chips:
        for name, s, e in ops[c]:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                by_op[name] += (e - s) / len(chips)
    chip = chips[0]
    idle = stats.gaps([(s, e) for _, s, e in ops[chip]], lo, hi)[:TOP_GAPS]
    programs = {}
    for name, s, e in modules.get(chip, ()):
        if s >= lo and e <= hi:              # whole executions only
            p = programs.setdefault(name, {"count": 0, "seconds": 0.0,
                                           "first": s, "last": s})
            p["count"] += 1
            p["seconds"] += e - s
            p["first"], p["last"] = min(p["first"], s), max(p["last"], s)
    ends = sorted((e, name) for name, s, e in modules.get(chip, ())
                  if name in programs)
    for mark, at, _ in annotations:
        if mark.startswith(AFTER) and lo <= at <= hi:
            i = bisect.bisect_right(ends, (at, "\uffff"))
            if i:
                roles = programs[ends[i - 1][1]].setdefault("roles", {})
                role = mark[len(AFTER):]
                roles[role] = roles.get(role, 0) + 1
    chip_ops = [(s, e) for _, s, e in ops[chip]]
    for p in programs.values():
        # from the first execution's start to the last one's: count - 1
        # whole periods, and the device time that fell in them
        p["busy_between"] = stats.covered(
            stats.clip(chip_ops, p["first"], p["last"]))
    return {
        "chips": len(chips), "span": (lo, hi), "window_s": hi - lo,
        "busy_s": sum(busy) / len(busy),
        "idle_share": 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo)),
        # an op's name in the trace is its whole HLO line: keep its head
        "device_ops": [[n[:NAME_CHARS], t]
                       for n, t in by_op.most_common(TOP_OPS)],
        "idle_gaps": [[_innermost(annotations, (s + e) / 2), e - s]
                      for s, e in idle],
        "programs": programs,
    }


def _describe(path, out=sys.stdout):
    """What a trace holds: planes, lines, the commonest event names and the
    stats of one event per line. For reading a trace by hand."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines", file=out)
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            names = collections.Counter(e.name for e in events)
            print(f"  line {line.name!r}: {len(events)} events, "
                  f"{len(names)} names", file=out)
            for name, n in names.most_common(12):
                tot = sum(e.duration_ns for e in events if e.name == name)
                print(f"    {n:7d} x {name[:100]!r} {tot / 1e6:.3f} ms",
                      file=out)
            e = events[len(events) // 2]
            try:
                st = {k: (str(v)[:80]) for k, v in e.stats}
            except Exception as exc:  # noqa: BLE001 — a reading aid only
                st = {"stats unreadable": repr(exc)}
            print(f"    e.g. start_ns={e.start_ns} dur_ns={e.duration_ns} "
                  f"stats={st}", file=out)
    print(f"summary: {summarise(*load(path))}", file=out)


if __name__ == "__main__":
    _describe(sys.argv[1])
