"""CPU rehearsal of `readers/program_span.py` on a small hand-built trace
(one superstep, two scopes, three host spans), run by hand like the rest:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q"""
from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import trace  # noqa: E402
from benchmarks.readers import program_span as ps  # noqa: E402

DECODE = "jit(superstep)/while/body/closed_call/layer0/attn/flash_decode/" \
    "flash_fwd/pallas_call:"
WRITE = "jit(superstep)/while/body/closed_call/layer0/kv_write/scatter:"
MODEL = {"hidden_size": 8, "num_hidden_layers": 2}


def _trace():
    """One whole superstep of 100 ms (a `while` of 90 ms holding a kernel of
    20 ms, a cache write of 30 ms and an unscoped copy of 25 ms) between
    two that the span cuts, and the loop thread's spans around it."""
    ops = [("%while.1 = while(...)", 1.005, 1.095, ""),
           ("%flash_fwd.3 = custom-call(...)", 1.010, 1.030, DECODE),
           ("%scatter.2 = scatter(...)", 1.030, 1.060, WRITE),
           ("%copy.148 = copy(...)", 1.060, 1.085, ""),
           ("%flash_fwd.3 = custom-call(...)", 0.910, 0.930, DECODE),
           ("%flash_fwd.3 = custom-call(...)", 1.110, 1.130, DECODE)]
    modules = [("jit_superstep(77)", 0.900, 1.000),
               ("jit_superstep(77)", 1.000, 1.100),
               ("jit_superstep(77)", 1.100, 1.200),
               ("jit_admit(78)", 1.0, 1.001)]
    spans = [(1, "serve.dispatch", 1.000, 1.002,
              {"step": 7, "k": 1, "active": 3}),
             (1, "serve.deliver", 1.002, 1.092, {"step": 6, "tokens": 3}),
             (1, "serve.fetch", 1.003, 1.091, {"step": 6}),
             (1, "serve.admit", 0.990, 0.999,
              {"req": 9, "prompt_len": 5, "bucket": 8,
               "queue_wait_us": 150000}),
             (2, "serve.submit", 1.050, 1.051, {"req": 10})]
    # the runtime's enqueue call: 1.5 of the dispatch's 2 ms
    runtime = [("PJRT_LoadedExecutable_Execute", 1.0004, 1.0019)]
    return {"ops": ops, "modules": modules, "spans": spans,
            "runtime": runtime, "bench_span": (0.95, 1.15)}


@pytest.fixture
def ctx(monkeypatch):
    tr = _trace()
    monkeypatch.setattr(ps, "find", lambda ctx: tr)
    return {"trace": {"span": ps.span_of(tr)},
            "driver": {"mean_rows_in_use": 10.0},
            "config": {"model": MODEL,
                       "serving": {"slots": 4, "dtype": "float32"}},
            "workload": {"clients": 4}, "device_kind": "TPU v5 lite"}


def test_span_is_cut_like_the_harness_cuts_it():
    tr = _trace()
    assert ps.span_of(tr) == (0.95, 1.130)   # held to the last operation
    tr["bench_span"] = None
    assert ps.span_of(tr) == (0.910, 1.130)


def test_scope_parts_and_self_times():
    assert ps.scope_parts(DECODE) == ["layer0", "attn", "flash_decode",
                                      "flash_fwd"]
    assert ps.scope_parts("jit(f)/jit(main)/sample/jit(sort)/sort:") \
        == ["sample"]
    assert ps.scope_parts("") == []
    own = {op[0].split(" ")[0]: t
           for op, t in ps.self_times(_trace()["ops"][:4])}
    assert own["%while.1"] == pytest.approx(0.090 - 0.075)
    assert own["%copy.148"] == pytest.approx(0.025)


def test_device_time_by_scope_in_whole_executions(ctx):
    tr = _trace()
    runs = ps.executions(tr, ctx["trace"]["span"], "superstep")
    assert runs == [(1.000, 1.100)]          # the other two are cut
    table = ps.by_scope(tr, runs)
    assert table[(("layer0", "attn", "flash_decode", "flash_fwd"), None)] \
        == pytest.approx(0.020)
    assert table[((), "copy.148")] == pytest.approx(0.025)
    assert sum(table.values()) == pytest.approx(0.090)
    kernel = {"key": "scope_ms", "scope": "flash_decode",
              "program": "superstep"}
    assert ps.read(ctx, kernel) == pytest.approx(20.0)
    assert ps.read(ctx, dict(kernel, scope="kv_write")) \
        == pytest.approx(30.0)
    assert ps.read(ctx, dict(kernel, scope="nothing")) is None
    assert ps.read(ctx, dict(kernel, program="verify")) is None
    # 10 rows x 4 clients x 2 layers x 2 (K and V) x 8 wide x 4 bytes in
    # 20 ms, against 819 GB/s
    share = ps.read(ctx, dict(kernel, key="scope_kv_share"))
    assert share == pytest.approx(100 * 5120 / 819e9 / 0.020)


def test_host_spans_and_their_stats(ctx):
    assert ps.read(ctx, {"key": "stat_p95", "span": "serve.admit",
                         "stat": "queue_wait_us", "scale": 0.001}) \
        == pytest.approx(150.0)
    assert ps.read(ctx, {"key": "stat_share", "span": "serve.dispatch",
                         "stat": "active",
                         "of": ["config", "serving", "slots"]}) \
        == pytest.approx(75.0)
    # admit 9 ms + dispatch 2 ms + deliver 90 ms, less the 88 ms fetch
    assert ps.read(ctx, {
        "key": "host_ms_per", "per": "serve.dispatch", "less":
        ["serve.fetch"], "spans": ["serve.admit", "serve.dispatch",
                                   "serve.deliver"]}) \
        == pytest.approx(13.0)
    assert ps.read(ctx, {"key": "host_ms_per", "spans": ["serve.fetch"],
                         "per": "serve.dispatch"}) == pytest.approx(88.0)
    # ... and less the runtime's enqueue call, where a full queue blocks
    assert ps.read(ctx, {
        "key": "host_ms_per", "per": "serve.dispatch",
        "spans": ["serve.dispatch"],
        "less_runtime": ["PJRT_LoadedExecutable_Execute"]}) \
        == pytest.approx(0.5)
    # the client's thread is not the loop's
    assert ps.read(ctx, {"key": "host_ms_per", "spans": ["serve.submit"],
                         "per": "serve.dispatch"}) == pytest.approx(0.0)
    assert ps.read(ctx, {"key": "host_ms_per", "spans": ["serve.submit"],
                         "per": "serve.dispatch", "any_thread": True}) \
        == pytest.approx(1.0)
    assert ps.read(ctx, {"key": "host_ms_per", "spans": ["train.stage"],
                         "per": "train.dispatch"}) is None


def test_no_trace_of_this_run_reads_nothing(monkeypatch):
    monkeypatch.setattr(ps, "TRACES", os.path.join(ROOT, "no-such-dir"))
    ctx = {"trace": {"span": (0.0, 1.0)}}
    assert ps.find(ctx) is None
    assert ps.read(ctx, {"key": "scope_ms", "scope": "flash_decode",
                         "program": "superstep"}) is None
    assert ps.find({"trace": None}) is None


def test_file_round_trip_and_table(tmp_path, monkeypatch, capsys):
    """A file written with the same few fields reads back as the lists it
    was built from, and `find` picks it by its span."""
    space = ps._xspace_class()()
    dev = space.planes.add(name="/device:TPU:0")
    host = space.planes.add(name="/host:CPU")
    for plane in (dev, host):
        for i, name in enumerate(["tf_op", "step", "active"], 1):
            e = plane.stat_metadata.add(key=i)
            e.value.id, e.value.name = i, name

    def event(plane, line, mid, name, start, end, scope=None, **stats):
        if not any(m.key == mid for m in plane.event_metadata):
            m = plane.event_metadata.add(key=mid)
            m.value.id, m.value.name = mid, name
            if scope is not None:
                m.value.stats.add(metadata_id=1, str_value=scope)
        ev = line.events.add(metadata_id=mid,
                             offset_ps=int(round(start * 1e12)),
                             duration_ps=int(round((end - start) * 1e12)))
        for k, v in stats.items():
            ev.stats.add(metadata_id={"step": 2, "active": 3}[k],
                         int64_value=v)

    ops_line = dev.lines.add(name=trace.OPS_LINE)
    mods_line = dev.lines.add(name=trace.MODULES_LINE)
    dev.lines.add(name="Steps")
    event(dev, ops_line, 1, "%flash_fwd.3 = custom-call(...)", 1.01, 1.03,
          scope=DECODE)
    event(dev, ops_line, 2, "%copy.148 = copy(...)", 1.06, 1.085)
    event(dev, ops_line, 2, "%copy.148 = copy(...)", 0.90, 0.91)
    event(dev, ops_line, 2, "%copy.148 = copy(...)", 1.19, 1.20)
    event(dev, mods_line, 3, "jit_superstep(77)", 1.0, 1.1)
    loop = host.lines.add(name="python3")
    event(host, loop, 1, "dl4j.serve.dispatch", 1.0, 1.002, step=7,
          active=3)
    event(host, loop, 2, trace.SPAN, 0.5, 2.0)
    event(host, loop, 3, "PjitFunction(superstep)", 1.0, 1.001)
    event(host, host.lines.add(name="main/7"), 4,
          "PJRT_LoadedExecutable_Execute", 1.0005, 1.0015)
    path = tmp_path / "cell" / "plugins" / "profile" / "t" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(space.SerializeToString())

    tr = ps.load(str(path))
    assert [o[3] for o in tr["ops"]] == [DECODE, "", "", ""]
    assert tr["ops"][0][1:3] == (pytest.approx(1.01), pytest.approx(1.03))
    assert tr["modules"] == [("jit_superstep(77)", pytest.approx(1.0),
                              pytest.approx(1.1))]
    assert [(s[1], s[4]) for s in tr["spans"]] \
        == [("serve.dispatch", {"step": 7, "active": 3})]
    assert tr["bench_span"] == (pytest.approx(0.5), pytest.approx(2.0))
    assert [r[0] for r in tr["runtime"]] == ["PJRT_LoadedExecutable_Execute"]
    monkeypatch.setattr(ps, "TRACES", str(tmp_path))
    assert ps.find({"trace": {"span": (0.90, 1.20)}}) is not None
    assert ps.find({"trace": {"span": (0.90, 1.5)}}) is None
    ps.describe(tr)
    out = capsys.readouterr().out
    assert "layer*/attn/flash_decode/flash_fwd" in out
    assert "(no scope) copy.148" in out and "serve.dispatch" in out
    assert "inside the runtime's call" in out
