"""`benchmarks/readers/idle_by_span.py` and the ten per-layer metrics of
ISSUE 39 on a small loaded trace built by hand (the dict
`program_span.load` returns): the device's idle time split by what the
serving loop was doing, the loop's CPU share and the runtime's enqueue
time, each against the value reckoned by hand here."""
import json
import os

import pytest

from benchmarks.readers import idle_by_span, program_span

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOOP, OTHER = 1, 2
#: the ten metrics of ISSUE 39, each a file under `layer_metrics/` and an
#: entry of `BENCHMARK.json` for the four serving cells
NEW_METRICS = (
    "serve.idle_in_admit_ms", "serve.idle_in_dispatch_ms",
    "serve.idle_in_deliver_ms", "serve.idle_in_gc_ms",
    "serve.idle_elsewhere_ms", "serve.loop_cpu_share", "serve.args_ms",
    "serve.call_ms", "serve.enqueue_ms", "serve.deliver_ms")
SERVING_CELLS = ["bert_serve_decode", "nemotron3_super_serve_decode",
                 "keye_vl2_serve_longdoc", "kanana2_serve_longdoc",
                 "trinity_mini_serve_longdoc", "lfm2_24b_serve_longdoc"]


def _op(s, e):
    return ("fusion", s, e, "jit(superstep)/layer0/ffn/dot:")


def _span(name, s, e, thread=LOOP, **stats):
    return (thread, name, s, e, stats)


def _trace(new_spans=True):
    """The device busy over [0, 10], [20, 30], [50, 60], [90, 100] (s):
    idle [10, 20], [30, 50], [60, 90], 60 s in all. With `new_spans`, the
    program of ISSUE 39 (calls, `cpu_us`, a collection); without, its
    parent's (the phases alone)."""
    ops = [_op(0, 10), _op(20, 30), _op(50, 55), _op(54, 60), _op(90, 100)]
    spans = [
        # [10, 20]: 4 s under the dispatch, 4 under the deliver, 2 outside
        _span("serve.dispatch", 8, 14, step=1, cpu_us=-1),
        _span("serve.args", 8, 9),
        _span("serve.call", 9, 12, program="superstep"),
        _span("serve.deliver", 14, 18, step=1, tokens=4),
        _span("serve.fetch", 14, 16, step=1),
        _span("serve.call", 16, 17, program="retire"),
        # [30, 50]: the admit is outermost over a nested dispatch until the
        # collection on another thread takes [40, 47]; 3 s outside
        _span("serve.admit", 28, 45, req=7),
        _span("serve.call", 29, 31, program="admit"),
        _span("serve.dispatch", 32, 36, step=2, cpu_us=12_000_000),
        _span("gc", 40, 47, thread=OTHER, generation=0, collected=3),
        # [60, 90]: 10 s under a dispatch, 10 under a deliver, 10 waiting
        _span("serve.dispatch", 55, 70, step=3, cpu_us=11_500_000),
        _span("serve.deliver", 70, 80, step=3, tokens=2),
        _span("serve.idle", 80, 90),
        # another thread's call is not the loop's
        _span("serve.call", 86, 88, thread=OTHER, program="admit"),
    ]
    runtime = [("PJRT_LoadedExecutable_Execute", 10, 11.5),
               ("PJRT_LoadedExecutable_Execute", 16.2, 16.8),
               ("PJRT_LoadedExecutable_Execute", 85, 86),
               ("PJRT_LoadedExecutable_Execute", 86.5, 87)]
    if not new_spans:
        spans = [(t, n, s, e, {k: v for k, v in st.items() if k != "cpu_us"})
                 for t, n, s, e, st in spans
                 if n not in ("serve.args", "serve.call", "gc")]
    return {"ops": ops, "modules": [], "spans": spans, "runtime": runtime,
            "bench_span": (0, 100)}


def _read(monkeypatch, tr, spec):
    monkeypatch.setattr(program_span, "find", lambda ctx: tr)
    return idle_by_span.read({"trace": {"span": program_span.span_of(tr)}},
                             spec)


def _spec(name):
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_the_phases_sum_to_the_idle_time_gc_first_outermost_wins():
    tr = _trace()
    seconds, dispatches = idle_by_span.split(tr, program_span.span_of(tr))
    assert dispatches == 3
    # a gap across two phases and the outside is split by time
    assert seconds == pytest.approx({
        "gc": 7, "serve.admit": 10, "serve.dispatch": 14,
        "serve.deliver": 14, "elsewhere": 15})
    assert sum(seconds.values()) == pytest.approx(60)


@pytest.mark.parametrize("name,value", [
    ("serve.idle_in_admit_ms", 1e3 * 10 / 3),
    ("serve.idle_in_dispatch_ms", 1e3 * 14 / 3),
    ("serve.idle_in_deliver_ms", 1e3 * 14 / 3),
    ("serve.idle_in_gc_ms", 1e3 * 7 / 3),
    ("serve.idle_elsewhere_ms", 1e3 * 15 / 3),
    # (12 + 11.5) s of CPU over (32 - 8) + (55 - 32) s between starts
    ("serve.loop_cpu_share", 100 * 23.5 / 47),
    ("serve.args_ms", 1e3 * 1 / 3),
    # the loop's calls, 3 + 1 + 2 s, less the runtime's 1.5 + 0.6 in them
    ("serve.call_ms", 1e3 * 3.9 / 3),
    ("serve.enqueue_ms", 1e3 * 2.1 / 3),
    # deliver [14, 18] less its fetch and call, and [70, 80]
    ("serve.deliver_ms", 1e3 * 11 / 3),
])
def test_each_new_metric_reads_what_was_reckoned(monkeypatch, name, value):
    assert _read(monkeypatch, _trace(), _spec(name)) == pytest.approx(value)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_the_parents_trace_and_an_empty_one_give_none(monkeypatch, name):
    for tr in (_trace(new_spans=False),
               {**_trace(), "spans": [], "runtime": []}):
        assert _read(monkeypatch, tr, _spec(name)) is None, name


def test_the_ten_are_registered_for_the_serving_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW_METRICS:
        spec = _spec(name)
        assert manifest[name]["workloads"] == SERVING_CELLS, name
        assert manifest[name]["moves"] == spec["moves"] \
            == "serve_tokens_per_s"
        assert spec["reader"] == "idle_by_span"
