"""Per-layer metrics read from what the PROGRAM writes into the profiler's
trace: its host spans (`dl4j.<name>`, `monitoring.span()`, with their
stats) and the names it gives to what the device runs (`jit_superstep`,
the `flash_decode` and `kv_write` scopes).

`ctx` carries no path to the trace file, so the reader finds the run's
`.xplane.pb` under `benchmarks/.cache/trace/` itself: the newest file whose
`bench.trace-span`, cut as `harness/trace.py` cuts it, is `ctx["trace"]
["span"]`. A device operation's scope path is a stat of its event METADATA
(`tf_op`), which `jax.profiler.ProfileData` does not hand out, so the file
is parsed here, against the few fields of `xplane.proto` that are read.
Times are seconds on the trace's own clock, as in `harness/trace.py`.

A program without the spans or names (the parent of the PR that added
them) gives None, and the metric is left out of the line.

    python3 benchmarks/readers/program_span.py <file.xplane.pb>

prints device time by scope for each program and host self time by span.
"""
from __future__ import annotations

import bisect
import collections
import functools
import glob
import os
import re
import sys

if __name__ == "__main__":      # run as a script: find the package
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks.harness import stats, trace, work
from benchmarks.harness.peaks import peaks

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACES = os.path.join(HERE, ".cache", "trace")
#: what `monitoring.span()` puts before a span's name in the trace
SPAN_PREFIX = "dl4j."
#: the stat of a device operation's metadata that holds its scope path,
#: as in 'jit(superstep)/while/body/closed_call/layer0/attn/flash_decode/
#: flash_fwd/pallas_call:' (read by hand on the chip, PR 26)
SCOPE_STAT = "tf_op"
#: parts of a scope path that jax puts there, not the program
STRUCTURAL = {"while", "body", "cond", "closed_call", "core_call",
              "checkpoint", "remat", "custom_jvp_call", "custom_vjp_call"}
#: the runtime's own host events that are kept: the call that enqueues a
#: program. When the device's queue of programs is full the host waits
#: inside it (90 ms of a 94 ms ResNet-50 step, read by hand, PR 26), so a
#: span's time less this is what the host itself did
RUNTIME_CALLS = {"PJRT_LoadedExecutable_Execute"}
BRANCH = re.compile(r"^branch_\d+_fun$")
LAYER = re.compile(r"^layer\d+$")
SAME_SPAN_S = 1e-6
TABLE_ROWS = 14


# -- the file ---------------------------------------------------------------
@functools.lru_cache(maxsize=1)
def _xspace_class():
    """The message class for an XSpace, from the fields read here
    (tsl/profiler/protobuf/xplane.proto; maps as repeated entries, which
    is what they are on the wire)."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    kinds = {"i": F.TYPE_INT64, "u": F.TYPE_UINT64, "d": F.TYPE_DOUBLE,
             "s": F.TYPE_STRING, "b": F.TYPE_BYTES}
    f = descriptor_pb2.FileDescriptorProto(
        name="benchmarks/program_span_xplane.proto",
        package="benchmarks.program_span", syntax="proto3")

    def message(name, fields, oneof=()):
        m = f.message_type.add(name=name)
        if oneof:
            m.oneof_decl.add(name="value")
        for fname, number, kind in fields:
            fd = m.field.add(name=fname, number=number)
            if kind.startswith("*"):
                fd.label = F.LABEL_REPEATED
                kind = kind[1:]
            else:
                fd.label = F.LABEL_OPTIONAL
            if kind in kinds:
                fd.type = kinds[kind]
            else:
                fd.type = F.TYPE_MESSAGE
                fd.type_name = f".{f.package}.{kind}"
            if fname in oneof:
                fd.oneof_index = 0

    message("XStat", [("metadata_id", 1, "i"), ("double_value", 2, "d"),
                      ("uint64_value", 3, "u"), ("int64_value", 4, "i"),
                      ("str_value", 5, "s"), ("bytes_value", 6, "b"),
                      ("ref_value", 7, "u")],
            oneof=("double_value", "uint64_value", "int64_value",
                   "str_value", "bytes_value", "ref_value"))
    message("XEvent", [("metadata_id", 1, "i"), ("offset_ps", 2, "i"),
                       ("duration_ps", 3, "i"), ("stats", 4, "*XStat")])
    message("XLine", [("id", 1, "i"), ("name", 2, "s"),
                      ("timestamp_ns", 3, "i"), ("events", 4, "*XEvent")])
    message("XEventMetadata", [("id", 1, "i"), ("name", 2, "s"),
                               ("stats", 5, "*XStat")])
    message("XStatMetadata", [("id", 1, "i"), ("name", 2, "s")])
    message("EventMetadataEntry", [("key", 1, "i"),
                                   ("value", 2, "XEventMetadata")])
    message("StatMetadataEntry", [("key", 1, "i"),
                                  ("value", 2, "XStatMetadata")])
    message("XPlane", [("id", 1, "i"), ("name", 2, "s"),
                       ("lines", 3, "*XLine"),
                       ("event_metadata", 4, "*EventMetadataEntry"),
                       ("stat_metadata", 5, "*StatMetadataEntry")])
    message("XSpace", [("planes", 1, "*XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{f.package}.XSpace"))


def _stats(xstats, stat_names):
    out = {}
    for st in xstats:
        which = st.WhichOneof("value")
        if which is None:
            continue
        value = getattr(st, which)
        if which == "ref_value":
            value = stat_names.get(value, "")
        out[stat_names.get(st.metadata_id, str(st.metadata_id))] = value
    return out


def load(path):
    """What the readers use of a trace file:

    - `ops`: (name, start, end, scope path) of chip 0's `XLA Ops`;
    - `modules`: (name, start, end) of its `XLA Modules`;
    - `spans`: (thread, name without the prefix, start, end, stats) of the
      program's host spans, `thread` numbering the host's lines;
    - `runtime`: (name, start, end) of the host's `RUNTIME_CALLS`;
    - `bench_span`: the benchmark's `trace-span` annotation, or None."""
    with open(path, "rb") as fh:
        space = _xspace_class().FromString(fh.read())
    ops, modules, spans, runtime, bench_span = [], [], [], [], None
    chip = min((int(m.group(1)) for m in map(
        trace.DEVICE_PLANE.match, (p.name for p in space.planes)) if m),
        default=None)
    thread = 0
    for plane in space.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) != chip:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        scopes = {}
        for line in plane.lines:
            if m and line.name not in (trace.OPS_LINE, trace.MODULES_LINE):
                continue
            thread += 1
            for ev in line.events:
                md = meta.get(ev.metadata_id)
                name = md.name if md is not None else ""
                if not m and not name.startswith(SPAN_PREFIX) \
                        and name != trace.SPAN \
                        and name not in RUNTIME_CALLS:
                    continue
                start = (line.timestamp_ns + ev.offset_ps / 1e3) / 1e9
                end = start + ev.duration_ps / 1e12
                if m and line.name == trace.MODULES_LINE:
                    modules.append((name, start, end))
                elif m:
                    if ev.metadata_id not in scopes:
                        scopes[ev.metadata_id] = str(_stats(
                            md.stats, stat_names).get(SCOPE_STAT, "")) \
                            if md is not None else ""
                    ops.append((name, start, end, scopes[ev.metadata_id]))
                elif name == trace.SPAN:
                    bench_span = (start, end)
                elif name in RUNTIME_CALLS:
                    runtime.append((name, start, end))
                else:
                    spans.append((thread, name[len(SPAN_PREFIX):], start,
                                  end, _stats(ev.stats, stat_names)))
    return {"ops": ops, "modules": modules, "spans": spans,
            "runtime": runtime, "bench_span": bench_span}


def span_of(tr):
    """The span of a loaded trace, cut as `harness/trace.summarise` cuts
    it: the benchmark's annotation, held to where the device's first
    operation starts and its last one ends."""
    if not tr["ops"]:
        return None
    first = min(s for _, s, _, _ in tr["ops"])
    last = max(e for _, _, e, _ in tr["ops"])
    span = tr["bench_span"]
    if span is None or span[1] < first or span[0] > last:
        span = (first, last)
    return max(span[0], first), min(span[1], last)


@functools.lru_cache(maxsize=2)
def _load_at(path, mtime):
    return load(path)


def find(ctx):
    """This run's trace, loaded: the newest file under the benchmark's
    trace directory whose span is the one `ctx["trace"]` was reduced
    over. None where there is none."""
    want = (ctx.get("trace") or {}).get("span")
    if want is None:
        return None
    files = glob.glob(os.path.join(TRACES, "*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    for path in sorted(files, key=os.path.getmtime, reverse=True):
        tr = _load_at(path, os.path.getmtime(path))
        got = span_of(tr)
        if got is not None and abs(got[0] - want[0]) < SAME_SPAN_S \
                and abs(got[1] - want[1]) < SAME_SPAN_S:
            return tr
    return None


# -- the reduction ----------------------------------------------------------
def scope_parts(path):
    """The program's own scopes in a device operation's scope path: what
    is left of 'jit(superstep)/while/body/closed_call/layer0/attn/
    flash_decode/flash_fwd/pallas_call:' is ['layer0', 'attn',
    'flash_decode', 'flash_fwd'] (the last part names the primitive)."""
    parts = path.split("/")[:-1]
    return [p for p in parts if p and "(" not in p and p not in STRUCTURAL
            and not BRANCH.match(p)]


def self_times(ops):
    """[(op, self seconds)]: an operation's time less that of the
    operations nested in it (a `while` holds its body's operations on the
    same line)."""
    out, stack = [], []        # stack of [op, end, time of its children]
    for op in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and op[1] >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[0][2] - done[0][1] - done[2]))
        if stack:
            stack[-1][2] += op[2] - op[1]
        stack.append([op, op[2], 0.0])
    while stack:
        done = stack.pop()
        out.append((done[0], done[0][2] - done[0][1] - done[2]))
    return out


def executions(tr, span, program):
    """Whole executions, inside `span`, of the modules `jit_<program>`:
    [(start, end)]."""
    lo, hi = span
    head = f"jit_{program}("
    return sorted((s, e) for name, s, e in tr["modules"]
                  if name.startswith(head) and s >= lo and e <= hi)


def by_scope(tr, runs):
    """Device self time inside the executions `runs`, by scope: a Counter
    keyed (scope parts, None), and for what lies outside every scope
    ((), the operation's own name): ((), 'copy.148')."""
    out = collections.Counter()
    if not runs:
        return out
    starts = [s for s, _ in runs]
    inside = []
    for op in tr["ops"]:
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[2] <= runs[i][1]:
            inside.append(op)
    for (name, _, _, path), t in self_times(inside):
        parts = tuple(scope_parts(path))
        own = None if parts else name.split(" ", 1)[0].lstrip("%")
        out[(parts, own)] += t
    return out


def scope_seconds(table, scope):
    """Time of every row of a `by_scope` table with `scope` in its path."""
    return sum(t for (parts, _), t in table.items() if scope in parts)


def spans_in(tr, span, names, thread=None):
    lo, hi = span
    return [sp for sp in tr["spans"]
            if sp[1] in names and sp[2] >= lo and sp[3] <= hi
            and (thread is None or sp[0] == thread)]


def thread_of(tr, span, name):
    """The host thread that wrote most spans called `name`."""
    count = collections.Counter(sp[0] for sp in spans_in(tr, span, {name}))
    return count.most_common(1)[0][0] if count else None


def self_seconds(tr, span, names, less=(), thread=None, less_runtime=()):
    """Time covered by the spans `names`, less what the spans `less`, and
    the runtime's calls `less_runtime` on whichever thread, cover inside
    them."""
    cover = stats.union([(s, e) for _, _, s, e, _ in spans_in(
        tr, span, set(names), thread)])
    cut = [(s, e) for _, _, s, e, _ in spans_in(tr, span, set(less),
                                                thread)]
    cut += [(s, e) for name, s, e in tr.get("runtime", ())
            if name in less_runtime]
    return stats.covered(cover) - sum(
        stats.covered(stats.clip(cut, s, e)) for s, e in cover)


def _lookup(ctx, keys):
    value = ctx
    for k in keys:
        value = value[k]
    return value


def read(ctx, spec):
    key = spec["key"]
    if key == "store_load_s":
        # a counter of the program, not of the trace
        from deeplearning4j_tpu.runtime import executables
        loads = [s["load_seconds"] for s in executables.status()["stores"]
                 if "load_seconds" in s]
        return sum(loads) if loads else None
    tr = find(ctx)
    if tr is None:
        return None
    span = ctx["trace"]["span"]
    if key == "stat_p95":
        values = [sp[4][spec["stat"]] for sp in spans_in(
            tr, span, {spec["span"]}) if sp[4].get(spec["stat"], -1) >= 0]
        return stats.percentile(values, 95) * spec.get("scale", 1.0) \
            if values else None
    if key == "stat_share":
        values = [sp[4][spec["stat"]] for sp in spans_in(
            tr, span, {spec["span"]}) if spec["stat"] in sp[4]]
        whole = _lookup(ctx, spec["of"])
        return 100.0 * sum(values) / (len(values) * whole) \
            if values else None
    if key == "host_ms_per":
        # time on the thread that writes the `per` spans, or with
        # `any_thread` on whichever thread, per `per` span
        per = spans_in(tr, span, {spec["per"]})
        if not per:
            return None
        thread = None if spec.get("any_thread") \
            else thread_of(tr, span, spec["per"])
        return 1e3 * self_seconds(
            tr, span, spec["spans"], spec.get("less", ()), thread,
            spec.get("less_runtime", ())) / len(per)
    if key in ("scope_ms", "scope_kv_share"):
        runs = executions(tr, span, spec["program"])
        if not runs:
            return None
        seconds = scope_seconds(by_scope(tr, runs), spec["scope"])
        if not seconds:
            return None
        ms = 1e3 * seconds / len(runs)
        if key == "scope_ms":
            return ms
        rows = ctx["driver"].get("mean_rows_in_use")
        if rows is None:
            return None
        itemsize = {"float32": 4, "bfloat16": 2}[
            ctx["config"]["serving"]["dtype"]]
        need = rows * int(ctx["workload"]["clients"]) \
            * work.bert_kv_bytes_per_position(ctx["config"]["model"],
                                              itemsize)
        return 100.0 * need / peaks(ctx["device_kind"])["hbm_bytes_per_s"] \
            / (ms / 1e3)
    raise ValueError(f"program_span: unknown key {key!r}")


# -- by hand ----------------------------------------------------------------
def _collapse(parts):
    return "/".join("layer*" if LAYER.match(p) else p for p in parts)


def describe(tr, out=None):
    span = span_of(tr)
    if span is None:
        print("no device operation in the trace", file=out)
        return
    print(f"span {span[1] - span[0]:.3f} s", file=out)
    programs = sorted({name.split("(", 1)[0][len("jit_"):]
                       for name, _, _ in tr["modules"]
                       if name.startswith("jit_")})
    for program in programs:
        runs = executions(tr, span, program)
        if not runs:
            continue
        whole = sum(e - s for s, e in runs)
        print(f"\nprogram jit_{program}: {len(runs)} whole executions, "
              f"{1e3 * whole / len(runs):.3f} ms each", file=out)
        rows = collections.Counter()
        for (parts, own), t in by_scope(tr, runs).items():
            rows[_collapse(parts) if parts else "(no scope) " + own] += t
        scoped = sum(t for k, t in rows.items()
                     if not k.startswith("(no scope)"))
        shown = 0.0
        print(f"  {'ms/execution':>12} {'share':>7}  scope", file=out)
        for name, t in rows.most_common(TABLE_ROWS * 2):
            shown += t
            print(f"  {1e3 * t / len(runs):12.3f} {100 * t / whole:6.1f}%  "
                  f"{name}", file=out)
        print(f"  scopes {100 * scoped / whole:.1f}% of the program's "
              f"device time; rows shown {100 * shown / whole:.1f}%",
              file=out)
    print("\nhost self time by span (thread, span, count, total ms, "
          "mean ms):", file=out)
    inside = spans_in(tr, span, {sp[1] for sp in tr["spans"]})
    by_thread = collections.defaultdict(list)
    for sp in inside:
        by_thread[sp[0]].append(sp)
    for thread, sps in sorted(by_thread.items()):
        total = collections.Counter()
        count = collections.Counter()
        as_ops = [(sp[1], sp[2], sp[3], "") for sp in sps]
        for (name, _, _, _), t in self_times(as_ops):
            total[name] += t
            count[name] += 1
        for name, t in total.most_common():
            print(f"  thread {thread:3d}  {name:22s} {count[name]:6d} "
                  f"{1e3 * t:10.3f} {1e3 * t / count[name]:9.3f}", file=out)


    waits = collections.Counter()
    for name, s, e in tr.get("runtime", ()):
        around = [sp for sp in inside if sp[2] <= s and e <= sp[3]]
        if around:
            waits[min(around, key=lambda sp: sp[3] - sp[2])[1]] += e - s
    if waits:
        print("\nof it inside the runtime's call that enqueues a program "
              "(a full queue makes the host wait there), by innermost "
              "span, ms:", file=out)
        for name, t in waits.most_common():
            print(f"  {name:22s} {1e3 * t:10.3f}", file=out)


if __name__ == "__main__":
    describe(load(sys.argv[1]))
